"""Programs obtained (compiled, or loaded from the persistent cache) while
the window was open, from JAX's backend-compile monitoring event."""


def read(rec: dict, name: str):
    n = rec.get("compiles_in_window")
    return None if n is None else float(n)
