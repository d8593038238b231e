"""Reduce one profiler trace (``.xplane.pb``) to the numbers the benchmark
reports: device busy and idle time over the traced slice, device time per
operation, device time inside each host annotation, and the breakdown.

Planes: every ``/device:TPU:<n>`` plane that ran an operation is a chip;
its ``XLA Ops`` line holds one event per operation run. The host plane
holds the annotations (``jax.profiler.TraceAnnotation``): the benchmark's
own ``bench.*`` spans and the program's ``sched.*`` run spans. Host and
device events share the trace's clock, though device timestamps were seen
to run about 1 ms ahead of the host's, so a device op is attributed to the
host annotation open at its midpoint. The slice is the ``bench.slice``
annotation; everything is clipped to it.
"""
from __future__ import annotations

import bisect
import collections
import re
from typing import Dict, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SLICE = "bench.slice"
#: host annotations that device time and idle gaps are attributed to
ANNOTATIONS = re.compile(r"^(bench|sched)\.")


def _stats(ev) -> dict:
    return {k: v for k, v in ev.stats}


#: control-flow ops whose events span the ops of their bodies: counted in
#: the busy union, left out of per-op sums so that no time counts twice
CONTAINERS = re.compile(r"^(while|conditional|call)(\.\d+)?$")


def op_label(name: str) -> str:
    """The HLO instruction name of a device op event. On the TPU the event
    is named by the instruction's text (``%fusion.5 = bf16[...] fusion(...)``);
    Pallas kernels keep their kernel's name (``vocab_topk_pallas.14``)."""
    return name.split(" = ", 1)[0].strip().lstrip("%")


def union_length(intervals: List[Tuple[int, int]]) -> int:
    total, end = 0, None
    start = None
    for a, b in sorted(intervals):
        if end is None or a > end:
            if end is not None:
                total += end - start
            start, end = a, b
        else:
            end = max(end, b)
    if end is not None:
        total += end - start
    return total


def gaps(intervals: List[Tuple[int, int]], lo: int, hi: int) -> List[Tuple[int, int]]:
    """The parts of [lo, hi) that no interval covers."""
    out, cur = [], lo
    for a, b in sorted(intervals):
        if a > cur:
            out.append((cur, min(a, hi)))
        cur = max(cur, b)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(a, b) for a, b in out if b > a]


class _Annotations:
    """Host annotation intervals, innermost lookup by time."""

    def __init__(self, items: List[Tuple[int, int, str, dict]]):
        self.items = sorted(items)
        self.starts = [a for a, _, _, _ in self.items]

    def at(self, t: int) -> str:
        """Name of the shortest annotation open at ``t`` ("" if none)."""
        best, best_len = "", None
        i = bisect.bisect_right(self.starts, t)
        for a, b, name, _ in self.items[max(0, i - 64):i]:
            if a <= t < b and (best_len is None or b - a < best_len):
                best, best_len = name, b - a
        return best

    def inside(self, name: str, t: int) -> bool:
        i = bisect.bisect_right(self.starts, t)
        return any(a <= t < b and n == name for a, b, n, _ in self.items[max(0, i - 64):i])


def reduce(path: str, top: int = 10) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    host = []
    devices = []
    for plane in pd.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == SLICE or ANNOTATIONS.match(ev.name):
                        host.append((int(ev.start_ns), int(ev.end_ns), ev.name, _stats(ev)))
        elif DEVICE_PLANE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    ops.append((int(ev.start_ns), int(ev.end_ns), op_label(ev.name)))
            if ops:
                devices.append(ops)
    slices = [(a, b) for a, b, n, _ in host if n == SLICE]
    if not slices or not devices:
        return {"window_s": 0.0, "busy_s": 0.0, "device_ops": [], "idle_gaps": [],
                "ops": {}, "ops_in": {}, "steps": [], "calls": [], "call_share": 0.0,
                "chips": len(devices)}
    lo, hi = slices[0]
    anns = _Annotations([h for h in host if h[2] != SLICE and h[0] < hi and h[1] > lo])

    ops_total: Dict[str, int] = collections.Counter()
    ops_in: Dict[str, Dict[str, int]] = collections.defaultdict(collections.Counter)
    idle: Dict[str, int] = collections.Counter()
    busy = []
    for ops in devices:
        clipped = [(max(a, lo), min(b, hi), lab) for a, b, lab in ops if b > lo and a < hi]
        busy.append(union_length([(a, b) for a, b, _ in clipped]))
        for a, b, lab in clipped:
            if CONTAINERS.match(lab):
                continue
            ops_total[lab] += b - a
            mid = (a + b) // 2  # device timestamps run ~1 ms ahead of the host's
            where = anns.at(mid)
            if where:
                ops_in[where][lab] += b - a
            for name in ("sched.decode", "sched.prefill"):
                if name != where and anns.inside(name, mid):
                    ops_in[name][lab] += b - a
        for a, b in gaps([(a, b) for a, b, _ in clipped], lo, hi):
            idle[anns.at((a + b) // 2) or "(no annotation)"] += b - a
    n = len(devices)
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy) / n / 1e9,
        "chips": n,
        "device_ops": [[k, v / n / 1e9] for k, v in ops_total.most_common(top)],
        "idle_gaps": [[k, v / n / 1e9] for k, v in idle.most_common(top)],
        "ops": {k: v / n / 1e9 for k, v in ops_total.items()},
        "ops_in": {a: {k: v / n / 1e9 for k, v in d.items()} for a, d in ops_in.items()},
        "steps": sorted(int(s["step"]) for a, b, name, s in anns.items
                        if name == "bench.step" and "step" in s and lo <= a and b <= hi),
        "calls": sorted(int(s["call"]) for a, b, name, s in anns.items
                        if name == "bench.call" and "call" in s and lo <= a and b <= hi),
        # bench.call annotations counted by the share of each inside the slice
        "call_share": sum((min(b, hi) - max(a, lo)) / (b - a) for a, b, name, _ in anns.items
                          if name == "bench.call" and b > a),
    }
