"""What every driver shares: the run context, the compile counter, the
collector's settling and pauses, the traced slice of the window, the device
reading and the printed checks."""
from __future__ import annotations

import dataclasses
import gc
import glob
import os
import shutil
import sys
import tempfile
import time
from typing import Callable, List, Optional

import numpy as np


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def now_ns() -> int:
    return time.perf_counter_ns()


def percentile(values, q: float) -> Optional[float]:
    """The q-th percentile (linear interpolation) or None without data."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


@dataclasses.dataclass
class Check:
    """One number compared with its limit: correct while value <= limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(self.value <= self.limit)

    def as_json(self) -> dict:
        return {"value": self.value, "limit": self.limit}


@dataclasses.dataclass
class Ctx:
    """One run of one cell."""

    cell: str
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    t_start_ns: int
    peaks: dict
    #: test hook: called with (stage, object) so a test can plant a fault
    #: in the timed path; None in every benchmark run
    fault: Optional[Callable] = None


@dataclasses.dataclass
class Outcome:
    """What a driver hands back to ``run.py``."""

    attempted: int
    failed: int
    setup_s: float
    end_to_end: dict  # metric name -> value
    record: dict  # what the per-layer readers read
    checks: List[Check]
    memory_peak_bytes: int


class CompileCounter:
    """Counts programs obtained (compiled or loaded from the persistent
    cache) while armed, from JAX's backend-compile monitoring event."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.armed = False
        self.count = 0
        self.names: List[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kw):
        if self.armed and event == self.EVENT:
            self.count += 1
            self.names.append(str(kw.get("fun_name", "?")))


def settle() -> None:
    """End of set-up: collect, then move every object set-up made (traced
    programs, weights' handles, the import graph) out of the collector's
    reach, so that a full collection inside the window walks only what
    the window itself allocates. ``gc.unfreeze()`` once the window has
    closed hands them back."""
    gc.collect()
    gc.freeze()


class GcWatch:
    """Pauses of the garbage collector while armed, per generation."""

    def __init__(self):
        self.armed = False
        self.pauses: List[tuple] = []  # (generation, seconds)
        self._t0 = 0
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase, info):
        if phase == "start":
            self._t0 = now_ns()
        elif self.armed:
            self.pauses.append((info["generation"], (now_ns() - self._t0) / 1e9))

    def close(self) -> None:
        self.armed = False
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)

    def summary(self) -> str:
        full = [s for g, s in self.pauses if g == 2]
        return (f"{len(self.pauses)} collections, {sum(s for _, s in self.pauses):.3f} s "
                f"(longest {max((s for _, s in self.pauses), default=0.0):.3f} s); "
                f"full: {len(full)}, {sum(full):.3f} s; {gc.get_freeze_count()} objects frozen")


def memory_peak_bytes(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


class TraceSlice:
    """Profiles [start, stop) of the window (host ns) when tracing is on;
    ``poll`` is called from the driver loop, ``result`` reduces the trace
    in this process and deletes the files."""

    def __init__(self, enabled: bool, start_ns: int, stop_ns: int):
        self.enabled = enabled
        self.start_ns, self.stop_ns = start_ns, stop_ns
        self.state = "idle"
        self.dir = None
        self.t0 = self.t1 = 0
        self._ann = None

    def poll(self, t_ns: int) -> None:
        if not self.enabled:
            return
        import jax

        if self.state == "idle" and t_ns >= self.start_ns:
            self.dir = tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(self.dir)
            self._ann = jax.profiler.TraceAnnotation("bench.slice")
            self._ann.__enter__()
            self.t0 = now_ns()
            self.state = "on"
        elif self.state == "on" and t_ns >= self.stop_ns:
            self.finish()

    def finish(self) -> None:
        if self.state != "on":
            return
        import jax

        self.t1 = now_ns()
        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.state = "done"

    def result(self) -> Optional[dict]:
        """The reduced trace (``bench.trace_reduce.reduce``) or None."""
        self.finish()
        if self.state != "done":
            return None
        from bench import trace_reduce

        try:
            files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                              recursive=True)
            if not files:
                return None
            out = trace_reduce.reduce(files[0])
            out["host_slice_s"] = (self.t1 - self.t0) / 1e9
            return out
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
