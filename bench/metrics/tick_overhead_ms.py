"""Host time per scheduler tick outside the prefill and decode programs, ms.

For each harness step that began inside the window, the durations of the
program's host-phase spans (``sched.expire``, ``sched.admit``,
``sched.first_token``, ``sched.insert``, ``sched.decode_prep``,
``sched.decode_commit``) that began inside that step are summed; the
reading is the mean over those steps. It is read from the program's own
spans over the whole window, not from the profiled slice. None when the
program records no such span."""
import bisect

PHASES = frozenset(("sched.expire", "sched.admit", "sched.first_token", "sched.insert",
                    "sched.decode_prep", "sched.decode_commit"))


def read(rec: dict, name: str):
    phases = sorted((s["ts_ns"], s["dur_ns"]) for s in rec.get("spans", [])
                    if s["name"] in PHASES)
    steps = [(a, b) for _, a, b, _ in rec.get("steps", []) if rec["w0"] <= a < rec["w1"]]
    if not phases or not steps:
        return None
    starts = [t for t, _ in phases]
    total = 0
    for a, b in steps:
        total += sum(d for _, d in phases[bisect.bisect_left(starts, a):
                                          bisect.bisect_left(starts, b)])
    return total / len(steps) / 1e6
