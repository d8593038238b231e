"""Benchmark harness — one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run            # all sorter benches
  PYTHONPATH=src python -m benchmarks.run --roofline # + roofline table
  PYTHONPATH=src python -m benchmarks.run --obs      # + Chrome trace
Prints ``name,us_per_call,derived`` CSV rows (benchmarks.common.emit).
``--obs`` forces the obs layer on for the whole run and writes a
perfetto-loadable ``BENCH_sort.trace.json`` next to the JSON (schema
checked via :func:`repro.obs.validate_chrome_trace`).

Also writes the repo-root ``BENCH_sort.json`` trajectory — one entry per
(op, shape, dtype, backend) with wall time and the XLA-level op-count
proxy from :mod:`benchmarks.fused_pipeline` — so fused-path perf
regressions stay visible across PRs (CI gates on bit-equality and the
op-count proxy, never on wall time, which is noisy on shared runners).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH_JSON = os.path.join(os.path.dirname(__file__), "..", "BENCH_sort.json")


def write_bench_json(rows) -> str:
    path = os.path.abspath(BENCH_JSON)
    import jax

    payload = {
        "schema": 1,
        "generated_unix": int(time.time()),
        "platform": jax.default_backend(),
        "note": ("wall_us is informational (interpret mode off-TPU); "
                 "xla_ops is the deterministic regression proxy"),
        "rows": rows,
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
        f.write("\n")
    return path


def main() -> None:
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--roofline", action="store_true",
                    help="also print the dry-run roofline table")
    ap.add_argument("--obs", action="store_true",
                    help="enable span tracing/metrics and write a Chrome "
                         "trace next to BENCH_sort.json")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()

    if args.obs:
        # before any benchmark module (and hence repro) import work runs,
        # so planner/dispatch trace-time spans are captured too
        os.environ["REPRO_OBS"] = "1"

    from . import api_dispatch, dist_sort, fig11_12_speed_2way
    from . import fig13_resources_2way, fig14_17_lut_modes, fig18_20_3way
    from . import fused_pipeline, moe_routing, segmented, serve
    from . import streaming_merge

    modules = {
        "fig11_12": fig11_12_speed_2way,
        "fig13": fig13_resources_2way,
        "fig14_17": fig14_17_lut_modes,
        "fig18_20": fig18_20_3way,
        "moe_routing": moe_routing,
        "streaming": streaming_merge,
        "api_dispatch": api_dispatch,
        "dist_sort": dist_sort,
        "fused": fused_pipeline,
        "segmented": segmented,
        "serve": serve,
    }
    print("name,us_per_call,derived")
    # the BENCH_sort.json trajectory collects rows from every module that
    # returns (rows, failures) — currently the fused pipeline and the
    # segmented raggedness sweep
    bench_rows = []
    wrote_any = False
    for name, mod in modules.items():
        if args.only and args.only != name:
            continue
        print(f"# --- {name} ---", file=sys.stderr)
        out = mod.run()
        if name in ("fused", "segmented"):
            bench_rows += out[0]
            wrote_any = True
    if wrote_any:
        path = write_bench_json(bench_rows)
        print(f"# wrote {path}", file=sys.stderr)
    if args.obs:
        import repro.obs as obs

        trace_path = os.path.abspath(BENCH_JSON).replace(
            ".json", ".trace.json")
        snap = obs.snapshot()
        obs.write_chrome_trace(trace_path, snap)
        with open(trace_path) as f:
            errs = obs.validate_chrome_trace(json.load(f))
        for e in errs:
            print(f"# OBS-TRACE-INVALID {e}", file=sys.stderr)
        print(f"# wrote {trace_path} ({len(snap['spans'])} spans, "
              f"{len(snap['metrics'])} metric series)", file=sys.stderr)
        if errs:
            sys.exit(1)
    if args.roofline:
        from . import roofline

        roofline.run("pod")


if __name__ == "__main__":
    main()
