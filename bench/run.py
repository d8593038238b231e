#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print the result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from ``BENCHMARK.json``: the cell's
configuration file (``bench/configs/<config>.json``), its traffic mix
(``bench/traffic/<traffic>.json``, which names its driver in
``bench/drivers/``) and, with ``--trace 1``, one reader per per-layer
metric (``bench/metrics/<name>.py``, else the reader of the name's part
before its first dot). ``--trace 0`` reports the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics and a breakdown of the traced
slice of the window.

It runs only on a TPU: with no accelerator, or fewer chips than the cell
asks for, it exits non-zero and prints no result. The numbers compared to
decide ``correct`` go to standard error as the last lines and into the
result line under ``checks``, its last key.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time

T_START_NS = time.perf_counter_ns()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")


class NoChip(RuntimeError):
    pass


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def resolve(bench: dict, workload: str) -> dict:
    """Everything a cell needs, found by name."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, config["file"])) as f:
        entry = json.load(f)
    with open(os.path.join(BENCH, "traffic", f"{cell['traffic']}.json")) as f:
        mix = json.load(f)

    def reports(metric):
        return workload in metric.get("workloads", [workload])

    return {
        "cell": cell, "config": entry, "traffic": mix,
        "driver": mix["driver"],
        "end_to_end": [m for m in bench["end_to_end"] if reports(m)],
        "per_layer": [m for m in bench["per_layer"] if reports(m)],
    }


def reader_path(metric_name: str) -> str:
    """``bench/metrics/<name>.py``, else ``<stem>.py`` for ``stem.suffix``."""
    exact = os.path.join(BENCH, "metrics", f"{metric_name}.py")
    if os.path.exists(exact):
        return exact
    return os.path.join(BENCH, "metrics", f"{metric_name.split('.')[0]}.py")


def _load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric_name: str):
    return _load_module(reader_path(metric_name), "bench_metric_" + metric_name.replace(".", "_"))


def load_driver(name: str):
    return _load_module(os.path.join(BENCH, "drivers", f"{name}.py"), "bench_driver_" + name)


def chips(n: int, require_tpu: bool = True):
    import jax

    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < n):
        raise NoChip(f"needs {n} TPU chip(s); JAX has {len(devs)} "
                     f"{devs[0].platform} device(s)")
    return devs[:n]


def configure_jax() -> None:
    import jax

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    # every program, however quick to compile, is kept: only the first run
    # of a cell in a checkout compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, t_start_ns: int = T_START_NS,
             overrides=None, fault=None) -> dict:
    """One run of one cell; returns the result line as a dict. ``overrides``
    (config, traffic) and ``fault`` are for the harness's own tests."""
    if os.path.join(ROOT, "src") not in sys.path:
        sys.path.insert(0, os.path.join(ROOT, "src"))
    spec = resolve(load_benchmark(), workload)
    if overrides:
        overrides(spec)
    devices = chips(spec["cell"]["chips"], require_tpu)
    if require_tpu:
        configure_jax()
    t_backend_s = (time.perf_counter_ns() - t_start_ns) / 1e9
    from repro.obs import metrics as obs_metrics
    from repro.obs import trace as obs_trace

    from bench.harness import Check, Ctx, log
    from bench.peaks import peaks

    kind = devices[0].device_kind
    ctx = Ctx(cell=workload, config=spec["config"], traffic=spec["traffic"],
              seed=seed, seconds=seconds, trace=trace, t_start_ns=t_start_ns,
              peaks=peaks(kind) if require_tpu else {"bf16_flops": 1.0, "hbm_bytes_per_s": 1.0},
              fault=fault)
    # counters on through set-up (ladder fallbacks are decided while
    # programs are traced); drivers turn them off for an untraced window
    obs_trace.set_enabled(True)
    out = load_driver(spec["driver"]).run(ctx)
    fallbacks = sum(obs_metrics.counter(n).total() for n in
                    ("resilience.fallbacks", "resilience.forced", "sched.retries"))
    checks = out.checks + [Check("fallbacks_or_retries", fallbacks, 0)]

    if trace:
        metrics = {}
        for m in spec["per_layer"]:
            value = load_reader(m["name"]).read(out.record, m["name"])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        metrics = {}
        values = dict(out.end_to_end, setup_s=out.setup_s)
        for m in spec["end_to_end"]:
            if m["name"] not in values:
                raise RuntimeError(f"driver {spec['driver']} gave no {m['name']}")
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}

    correct = all(c.ok for c in checks)
    device = {"platform": devices[0].platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": out.memory_peak_bytes}
    line = {"correct": correct, "attempted": out.attempted, "failed": out.failed,
            "metrics": metrics, "device": device}
    tr = out.record.get("trace")
    if trace and tr:
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        line["breakdown"] = {"device_ops": tr["device_ops"], "idle_gaps": tr["idle_gaps"]}
    line["checks"] = {c.name: c.as_json() for c in checks}
    log(f"[run] {workload} seed {seed}: correct={correct} attempted={out.attempted} "
        f"failed={out.failed} setup_s={out.setup_s:.3f} (backend up at {t_backend_s:.3f} s)")
    for c in checks:
        log(f"check {c.name}: {c.value!r} (limit {c.limit!r}) {'ok' if c.ok else 'FAILED'}")
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    try:
        line = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"[run] {e}", file=sys.stderr)
        return 3
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
