"""Mean duration of the program's ``sched.decode`` spans (the decode jit
launch until its tokens are on the host) that began inside the window, ms."""


def read(rec: dict, name: str):
    ticks = [s["dur_ns"] / 1e6 for s in rec.get("spans", [])
             if s["name"] == "sched.decode" and rec["w0"] <= s["ts_ns"] < rec["w1"]]
    return sum(ticks) / len(ticks) if ticks else None
