#!/usr/bin/env python3
"""Readings that set each cell's correctness limit, many seeds in one process.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 20

For every seed it drives the cell's own timed path at the cell's own load
for a short window and prints, as one JSON line per seed, the numbers the
benchmark compares (the program's reading) beside the control's reading:
the plain reference that the configuration file names (``"reference"``,
``bench/reference.py`` without the key; ``bench.drivers.serve.reference``),
computed one precision below the configuration's (float8 e4m3 for bf16),
put in the program's place.
Both go through the harness's own ``Check`` at the committed limit
(``program_ok``, ``control_ok``): the control has to come out not ok.

* Serving cells: the program's widest served-token gap, and the widest gap
  of the tokens the fp8 reference draws itself (top-k at the mix's
  temperature) over the same prompts and served tokens.
* Sampler cell: rows whose top-k values differ from the exact top-k, for
  the program and for a top-k that ranks the same logits rounded to fp8.

``--sweep r1,r2,...`` instead drives the chat cell at each offered rate
(requests/s) and prints the tails and the backlog left, to find the knee.
It runs on the chip only, like ``bench/run.py``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import run as bench_run  # noqa: E402
from bench.harness import Check, Ctx, log, now_ns, percentile  # noqa: E402


def fp8_topk_rows_differing(logits, ks) -> int:
    """Rows whose top-k, ranked on the logits rounded to float8 e4m3 under
    a per-row scale, holds other values than the exact top-k."""
    import ml_dtypes
    import numpy as np

    bad = 0
    for s, k in enumerate(ks):
        row = logits[s].astype(np.float32)
        scale = np.abs(row).max() / 448.0
        low = (row / scale).astype(ml_dtypes.float8_e4m3fn).astype(np.float32)
        pick = np.argsort(-low, kind="stable")[:k]
        got = np.sort(row[pick])[::-1]
        bad += int(not np.array_equal(got, np.sort(row)[::-1][:k]))
    return bad


def sampler_readings(ctx: Ctx, calls: int = 32) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench.drivers import sampler
    from bench.weights import seed_key

    mix = ctx.traffic
    vocab = ctx.config["model"]["vocab_size"]
    ks, tps = tuple(mix["k"]), tuple(float(p) for p in mix["top_p"])
    rows = mix["rows"]
    call = sampler._call_fn(rows, vocab, ks, tuple(p < 1.0 and k > 1 for k, p in zip(ks, tps)))
    temps = jnp.full((rows,), mix["temperature"], jnp.float32)
    tp = jnp.asarray(np.asarray(tps, np.float32))
    prog = ctrl = bad_tok = 0
    for i in range(calls):
        x = (jax.random.normal(seed_key(ctx.seed, salt=1000 + i), (rows, vocab))
             * mix["logit_scale"]).astype(jnp.bfloat16)
        vals, toks = call(x, seed_key(ctx.seed, salt=2000 + i), temps, tp)
        host = np.asarray(x.astype(jnp.float32))
        bv, bt = sampler.compare(host, np.asarray(vals.astype(jnp.float32)), np.asarray(toks),
                                 ks, tps, mix["temperature"])
        prog += bv
        bad_tok += bt
        ctrl += fp8_topk_rows_differing(host, ks)
    limit = mix["check"]["topk_rows_differing"]
    return {"topk_rows_differing": prog, "tokens_outside_candidates": bad_tok,
            "control_topk_rows_differing": ctrl, "rows": calls * rows,
            "program_ok": Check("topk_rows_differing", prog, limit).ok,
            "control_ok": Check("topk_rows_differing", ctrl, limit).ok}


def serve_readings(srv, ctx: Ctx) -> dict:
    from bench.drivers import serve

    rec = srv.drive(ctx)
    e2e = srv.end_to_end(rec)
    checked = srv.checked_requests(srv.pick_checked(rec, ctx.seed))
    prog = serve.served_gaps(srv.params, ctx.config, srv.mix, checked)
    ctrl = serve.served_gaps(srv.params, ctx.config, srv.mix, checked, "fp8",
                             control_seed=ctx.seed)
    limit = srv.mix["check"]["served_token_gap"]
    return {"served_token_gap": max(prog), "per_request": prog,
            "program_ok": Check("served_token_gap", max(prog), limit).ok,
            "control_ok": Check("served_token_gap", max(ctrl), limit).ok,
            "control_served_token_gap": max(ctrl), "control_per_request": ctrl,
            "tokens": sum(len(t) for _, t, _ in checked),
            "ttft_p50_ms": e2e.get("ttft_p50_ms"), "itl_p95_ms": e2e.get("itl_p95_ms"),
            "output_tok_per_s": e2e["output_tok_per_s"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--sweep", default="")
    args = ap.parse_args(argv)
    spec = bench_run.resolve(bench_run.load_benchmark(), args.workload)
    devices = bench_run.chips(spec["cell"]["chips"])
    bench_run.configure_jax()
    from repro.obs import trace as obs_trace

    from bench.peaks import peaks

    def ctx_for(seed, traffic=None):
        return Ctx(cell=args.workload, config=spec["config"], traffic=traffic or spec["traffic"],
                   seed=seed, seconds=args.seconds, trace=False, t_start_ns=now_ns(),
                   peaks=peaks(devices[0].device_kind))

    obs_trace.set_enabled(False)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    if spec["driver"] == "sampler":
        for seed in seeds:
            print(json.dumps({"seed": seed, **sampler_readings(ctx_for(seed))}), flush=True)
        return 0
    from bench.drivers import serve

    t0 = time.perf_counter()
    srv = serve.Server(ctx_for(seeds[0] if seeds else 1))
    log(f"[control] set-up {time.perf_counter() - t0:.1f} s")
    if args.sweep:
        for rate in (float(r) for r in args.sweep.split(",")):
            mix = dict(spec["traffic"], rate_per_s=rate, drain_s=10)
            srv.mix = mix
            rec = srv.drive(ctx_for(seeds[0] if seeds else 1, mix))
            e2e = srv.end_to_end(rec)
            due = srv.due_in_window(rec)
            queued = len(srv.eng.queue)
            print(json.dumps({
                "rate_per_s": rate, "due_in_window": len(due),
                "ttft_p50_ms": e2e.get("ttft_p50_ms"),
                "ttft_p90_ms": percentile(e2e["ttft_ms"], 90), "itl_p50_ms": percentile(e2e["itl_ms"], 50),
                "itl_p95_ms": e2e.get("itl_p95_ms"), "output_tok_per_s": e2e["output_tok_per_s"],
                "queued_at_end": queued, "steps": len(rec["steps"])}), flush=True)
            srv.fresh_engine()
        return 0
    for seed in seeds:
        srv.set_weights(seed)
        out = serve_readings(srv, ctx_for(seed))
        print(json.dumps({"seed": seed, **out}), flush=True)
        srv.fresh_engine()
    return 0


if __name__ == "__main__":
    sys.exit(main())
