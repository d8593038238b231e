"""Share of the traced slice in which no operation ran on the device:
1 - (union of device op intervals) / slice, averaged over the chips, %."""


def read(rec: dict, name: str):
    tr = rec.get("trace")
    if not tr or not tr.get("window_s"):
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
