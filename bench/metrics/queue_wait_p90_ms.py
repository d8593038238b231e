"""90th percentile of the program's ``req.queue_wait`` spans (submit to
prefill start) of requests submitted inside the window, in ms."""
import numpy as np


def read(rec: dict, name: str):
    waits = [s["dur_ns"] / 1e6 for s in rec.get("spans", [])
             if s["name"] == "req.queue_wait" and rec["w0"] <= s["ts_ns"] < rec["w1"]]
    return float(np.percentile(waits, 90)) if waits else None
