"""Decode microbenchmark → ``BENCH_serve.json`` (+ the obs smoke gate).

For each case this runs the real serving path — ``init_cache`` →
``prefill`` → jitted decode loop with donated cache — on a smoke-scale
model config, with ``ServeConfig.time_steps`` on so every decode step is
host-timed, and reports:

* ``tok_per_s``             — decode throughput (batch tokens / decode wall)
* ``prefill_us``            — one synchronized prefill
* ``decode_step_p50/95/99`` — per-step latency percentiles

Rows land in the repo-root ``BENCH_serve.json`` trajectory (schema
mirrors ``BENCH_sort.json``). Wall numbers are informational off-TPU
(interpret-mode kernels); the ``--check`` gate asserts *structure*, never
timing:

* every case produced tokens in-range and ``tok_per_s > 0``;
* the p50/p95/p99 fields are present and ordered;
* with obs forced on, one generate() leaves ``serve.prefill`` /
  ``serve.decode`` spans and serve counters in the snapshot, and the
  exported Chrome trace (written next to the JSON) passes the
  trace-event schema check — the CI obs-enabled benchmark row.

Offered-load rows (``kind="offered_load"``): Poisson arrivals drive the
request scheduler (repro.serving.scheduler) — mixed per-request sampling
configs through paged slots and continuous batching — and report request
throughput plus p50/p99 request-latency and TTFT. ``--check``
additionally gates the scheduler rows: every request drains with the
right token count, the latency percentiles are ordered, and a sampled
pair of requests is re-run solo through one-shot ``generate()`` and must
match bit-for-bit (the scheduler's oracle contract).

Fault rows (``kind="faults"``, opt-in via ``--faults``): the offered-load
case re-run under seeded probabilistic faults on every scheduler seam
(``sched.prefill/insert/decode``) plus tight ``ttl_ticks`` deadlines,
reporting how many faults fired and how the requests ended
(done / timed-out / failed). The gate is the §16 drain invariant — every
request terminal, no slot or page leaked — plus bit-equality of every
*completed* request against the fault-free run.
"""
from __future__ import annotations

import json
import os
import sys
import time

import jax
import numpy as np

from .common import emit

BENCH_SERVE_JSON = os.path.join(os.path.dirname(__file__), "..",
                                "BENCH_serve.json")

#: (model, batch, prompt_len, new_tokens, top_k, temperature)
CASES = [
    ("chatglm3-6b", 2, 16, 8, 8, 1.0),
    ("qwen3-8b", 2, 12, 6, 0, 0.0),  # greedy decode
]


def _run_case(model, batch_size, prompt_len, new_tokens, top_k, temperature):
    import jax.numpy as jnp

    from repro.configs import get_smoke_config
    from repro.models import model_init
    from repro.serving.engine import ServeConfig, generate

    cfg = get_smoke_config(model)
    params, _ = model_init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (batch_size, prompt_len)), jnp.int32)}
    sc = ServeConfig(max_new_tokens=new_tokens, top_k=top_k,
                     temperature=temperature, time_steps=True)
    out = generate(params, batch, cfg, sc)
    row = {
        "model": model,
        "batch": batch_size,
        "prompt_len": prompt_len,
        "new_tokens": new_tokens,
        "top_k": top_k,
        "temperature": temperature,
        "tok_per_s": round(float(out["tok_per_s"]), 2),
        "prefill_us": round(float(out["prefill_s"]) * 1e6, 1),
        "decode_us": round(float(out["decode_s"]) * 1e6, 1),
        "p50_us": round(out["decode_step_p50_us"], 1),
        "p95_us": round(out["decode_step_p95_us"], 1),
        "p99_us": round(out["decode_step_p99_us"], 1),
        # first decode step = jit compile; reported apart so the
        # steady-state percentiles above stay compile-free
        "compile_us": round(out["decode_step_compile_us"], 1),
        "platform": jax.default_backend(),
    }
    failures = []
    toks = out["tokens"]
    if toks.shape != (batch_size, new_tokens):
        failures.append(f"{model}: tokens shape {toks.shape}")
    if not ((toks >= 0).all() and (toks < cfg.vocab_size).all()):
        failures.append(f"{model}: tokens out of vocab range")
    if not out["tok_per_s"] > 0:
        failures.append(f"{model}: tok_per_s {out['tok_per_s']}")
    if not (row["p50_us"] <= row["p95_us"] <= row["p99_us"]):
        failures.append(f"{model}: decode percentiles not ordered")
    if not row["compile_us"] > 0:
        failures.append(f"{model}: compile_us {row['compile_us']}")
    return row, failures


#: (model, n_requests, rate req/tick, prompt_lo, prompt_hi, new_tokens,
#:  n_slots, page_size, pages_per_slot, seed)
LOAD_CASES = [
    ("chatglm3-6b", 8, 0.5, 3, 12, 4, 2, 8, 4, 0),
]


def _run_load_case(model, n_req, rate, p_lo, p_hi, new_tokens,
                   n_slots, page_size, pages_per_slot, seed):
    from repro.configs import get_smoke_config
    from repro.models import model_init
    from repro.serving.engine import ServeConfig, generate
    from repro.serving.scheduler import (
        SamplingParams, ScheduledEngine, SchedulerConfig)

    cfg = get_smoke_config(model)
    params, _ = model_init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(seed)
    # Poisson offered load: exponential inter-arrival gaps at `rate`
    # requests per scheduler tick, floored onto the virtual tick clock
    arrivals = np.floor(np.cumsum(
        rng.exponential(1.0 / rate, n_req))).astype(int)
    plens = rng.integers(p_lo, p_hi + 1, n_req)
    prompts = [rng.integers(1, cfg.vocab_size, n).astype(np.int32)
               for n in plens]
    sps = [SamplingParams(k=int(rng.choice([1, 4, 8])),
                          temperature=float(rng.choice([0.0, 0.7, 1.0])),
                          top_p=float(rng.choice([1.0, 0.9])),
                          max_new_tokens=new_tokens, seed=int(i))
           for i in range(n_req)]
    sched = SchedulerConfig(n_slots=n_slots, page_size=page_size,
                            pages_per_slot=pages_per_slot)
    eng = ScheduledEngine(params, cfg, sched)
    t0 = time.perf_counter()
    rids = [eng.submit(p, sp, arrival=int(a))
            for p, sp, a in zip(prompts, sps, arrivals)]
    out = eng.run()
    wall = time.perf_counter() - t0
    lat_ms = np.asarray([(eng.requests[r].t_finish - eng.requests[r].t_submit)
                         * 1e3 for r in rids])
    ttft_ms = np.asarray([(eng.requests[r].t_first - eng.requests[r].t_submit)
                          * 1e3 for r in rids])
    total_toks = sum(len(v) for v in out.values())
    row = {
        "kind": "offered_load",
        "model": model,
        "n_requests": n_req,
        "rate_per_tick": rate,
        "n_slots": n_slots,
        "page_size": page_size,
        "pages_per_slot": pages_per_slot,
        "ticks": eng.t,
        "throughput_tok_per_s": round(total_toks / max(wall, 1e-9), 2),
        "req_p50_ms": round(float(np.percentile(lat_ms, 50)), 2),
        "req_p99_ms": round(float(np.percentile(lat_ms, 99)), 2),
        "ttft_p50_ms": round(float(np.percentile(ttft_ms, 50)), 2),
        "ttft_p99_ms": round(float(np.percentile(ttft_ms, 99)), 2),
        "platform": jax.default_backend(),
    }
    failures = []
    if sorted(out) != sorted(rids):
        failures.append(f"{model}: offered-load drain incomplete")
    for rid in rids:
        if rid in out and out[rid].shape != (new_tokens,):
            failures.append(f"{model}: rid {rid} token count {out[rid].shape}")
    if not (row["req_p50_ms"] <= row["req_p99_ms"]):
        failures.append(f"{model}: request latency percentiles not ordered")
    if not (row["ttft_p50_ms"] <= row["ttft_p99_ms"]):
        failures.append(f"{model}: TTFT percentiles not ordered")
    # the oracle gate: a sampled pair of scheduled requests must match a
    # solo one-shot generate() bit for bit (equal cache capacity)
    for rid in rids[:2]:
        sp = sps[rids.index(rid)]
        sc = ServeConfig(max_new_tokens=sp.max_new_tokens, top_k=sp.k,
                         top_p=sp.top_p, temperature=sp.temperature,
                         seed=sp.seed, cache_len=sched.slot_capacity)
        solo = generate(params, {"tokens": prompts[rids.index(rid)][None]},
                        cfg, sc)["tokens"][0]
        if not np.array_equal(out[rid], solo):
            failures.append(
                f"{model}: rid {rid} scheduler tokens differ from solo "
                f"generate ({out[rid].tolist()} vs {solo.tolist()})")
    return row, failures


def _run_fault_case(model, n_req, rate, p_lo, p_hi, new_tokens,
                    n_slots, page_size, pages_per_slot, seed):
    """The chaos row (DESIGN.md §16): the offered-load case re-run with
    seeded probabilistic faults armed across every scheduler seam plus a
    couple of tight virtual-tick deadlines. Reports how the engine
    degraded (done / timed-out / failed / retried); the gate asserts the
    drain invariant — every request terminal, no slot or page leaked —
    and that whatever *completed* matches the fault-free run bit for
    bit."""
    from repro.resilience import failpoints, fires, reset_failpoints
    from repro.serving.scheduler import (
        SamplingParams, ScheduledEngine, SchedulerConfig, TERMINAL_STATES)

    cfg = get_smoke_config_cached(model)
    params = model_params_cached(model)
    rng = np.random.default_rng(seed)
    arrivals = np.floor(np.cumsum(
        rng.exponential(1.0 / rate, n_req))).astype(int)
    plens = rng.integers(p_lo, p_hi + 1, n_req)
    prompts = [rng.integers(1, cfg.vocab_size, n).astype(np.int32)
               for n in plens]
    # the last two requests carry tight virtual-tick TTLs so the row also
    # exercises deadline reclamation, not just launch faults
    sps = [SamplingParams(k=int(rng.choice([1, 4, 8])),
                          temperature=float(rng.choice([0.0, 0.7, 1.0])),
                          max_new_tokens=new_tokens, seed=int(i),
                          ttl_ticks=(3 if i >= n_req - 2 else None))
           for i in range(n_req)]

    def _drive(chaos: bool):
        sched = SchedulerConfig(n_slots=n_slots, page_size=page_size,
                                pages_per_slot=pages_per_slot,
                                max_retries=1, retry_backoff_s=0.0)
        eng = ScheduledEngine(params, cfg, sched)
        rids = [eng.submit(p, sp, arrival=int(a))
                for p, sp, a in zip(prompts, sps, arrivals)]
        if chaos:
            with failpoints({"sched": f"p:0.2:{seed + 7}"}):
                out = eng.run()
                n_fired = fires("sched")
        else:
            out, n_fired = eng.run(), 0
        return eng, rids, out, n_fired

    ref_eng, ref_rids, ref_out, _ = _drive(chaos=False)
    reset_failpoints()
    eng, rids, out, n_fired = _drive(chaos=True)
    by_state = {}
    for r in eng.requests.values():
        by_state[r.state.value] = by_state.get(r.state.value, 0) + 1
    row = {
        "kind": "faults",
        "model": model,
        "n_requests": n_req,
        "faults_injected": n_fired,
        "done": by_state.get("done", 0),
        "timed_out": by_state.get("timed_out", 0),
        "failed": by_state.get("failed", 0),
        "ticks": eng.t,
        "platform": jax.default_backend(),
    }
    failures = []
    nonterminal = [r.rid for r in eng.requests.values()
                   if r.state not in TERMINAL_STATES]
    if nonterminal:
        failures.append(f"{model}: non-terminal requests under faults: "
                        f"{nonterminal}")
    if eng.slots.free_slot_count != n_slots:
        failures.append(f"{model}: leaked slots under faults")
    if eng.slots.free_page_count != eng.pool.n_pages - 1:
        failures.append(f"{model}: leaked pages under faults")
    # a TTL request can *complete* under chaos yet time out fault-free
    # (a failed neighbor frees its slot earlier), so compare only the
    # requests that finished in both runs — the pytest chaos suite owns
    # the strict solo-generate oracle
    for rid, ref_rid in zip(rids, ref_rids):
        if (rid in out and ref_rid in ref_out
                and not np.array_equal(out[rid], ref_out[ref_rid])):
            failures.append(
                f"{model}: rid {rid} completed under faults but differs "
                f"from the fault-free run")
    return row, failures


def get_smoke_config_cached(model):
    from repro.configs import get_smoke_config

    return get_smoke_config(model)


_PARAMS_CACHE = {}


def model_params_cached(model):
    from repro.models import model_init

    if model not in _PARAMS_CACHE:
        _PARAMS_CACHE[model] = model_init(
            jax.random.PRNGKey(0), get_smoke_config_cached(model))[0]
    return _PARAMS_CACHE[model]


def collect_fault_rows():
    rows, failures = [], []
    for case in LOAD_CASES:
        row, fails = _run_fault_case(*case)
        rows.append(row)
        failures += fails
        emit(f"serve_faults_{case[0]}_n{case[1]}", row["ticks"],
             f"fired {row['faults_injected']} done {row['done']} "
             f"timed_out {row['timed_out']} failed {row['failed']}")
    return rows, failures


def collect_load_rows():
    rows, failures = [], []
    for case in LOAD_CASES:
        row, fails = _run_load_case(*case)
        rows.append(row)
        failures += fails
        emit(f"serve_load_{case[0]}_n{case[1]}", row["req_p50_ms"] * 1e3,
             f"tok/s {row['throughput_tok_per_s']} "
             f"p99 {row['req_p99_ms']}ms ttft50 {row['ttft_p50_ms']}ms")
    return rows, failures


def write_serve_json(rows) -> str:
    path = os.path.abspath(BENCH_SERVE_JSON)
    payload = {
        "schema": 1,
        "generated_unix": int(time.time()),
        "platform": jax.default_backend(),
        "note": ("tokens/sec + per-decode-step latency percentiles; "
                 "wall numbers are informational off-TPU (interpret-mode "
                 "kernels) — CI gates on structure, never timing"),
        "rows": rows,
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
        f.write("\n")
    return path


def _obs_smoke(failures) -> None:
    """The obs-enabled benchmark row: rerun one case with obs forced on,
    assert the snapshot carries serve spans + counters, and write a
    schema-validated Chrome trace next to BENCH_serve.json."""
    import repro.obs as obs

    prev = obs.set_enabled(True)
    obs.trace.clear()
    obs.metrics.reset()
    try:
        _run_case(*CASES[0])
        snap = obs.snapshot()
        names = {sp["name"] for sp in snap["spans"]}
        for want in ("serve.prefill", "serve.decode"):
            if want not in names:
                failures.append(f"obs: span {want!r} missing from snapshot")
        for want in ("serve.decode_steps", "serve.tokens", "plan.decisions"):
            if want not in snap["metrics"]:
                failures.append(f"obs: metric {want!r} missing from snapshot")
        trace_path = os.path.abspath(BENCH_SERVE_JSON).replace(
            ".json", ".trace.json")
        obs.write_chrome_trace(trace_path, snap)
        with open(trace_path) as f:
            errs = obs.validate_chrome_trace(json.load(f))
        for e in errs:
            failures.append(f"obs: chrome trace schema: {e}")
        print(f"# wrote {trace_path} ({len(snap['spans'])} spans)",
              file=sys.stderr)
    finally:
        obs.set_enabled(prev)


def _trace_requests(failures) -> None:
    """``--trace-requests``: drive the scheduler with obs forced on and
    write the per-request waterfall trace — one perfetto timeline row per
    request (queue-wait → prefill → insert → decode ticks) plus the
    ``waterfalls`` summary — into ``BENCH_serve.trace.json``. Gates the
    §17 reconciliation contract against the engine's *measured* markers:
    for every completed request the non-decode stage spans sum exactly
    (integer ns) to its TTFT, the root span matches its request latency,
    and unaccounted scheduler overhead is never negative."""
    import repro.obs as obs
    from repro.configs import get_smoke_config
    from repro.serving.scheduler import (
        SamplingParams, ScheduledEngine, SchedulerConfig)

    model, n_req, rate, p_lo, p_hi, new_tokens, n_slots, page_size, \
        pages_per_slot, seed = LOAD_CASES[0]
    cfg = get_smoke_config(model)
    params = model_params_cached(model)
    rng = np.random.default_rng(seed)
    arrivals = np.floor(np.cumsum(
        rng.exponential(1.0 / rate, n_req))).astype(int)
    prompts = [rng.integers(1, cfg.vocab_size, n).astype(np.int32)
               for n in rng.integers(p_lo, p_hi + 1, n_req)]
    sps = [SamplingParams(k=int(rng.choice([1, 4, 8])),
                          temperature=float(rng.choice([0.0, 0.7, 1.0])),
                          max_new_tokens=new_tokens, seed=int(i))
           for i in range(n_req)]

    prev = obs.set_enabled(True)
    obs.trace.clear()
    obs.metrics.reset()
    obs.recorder.clear()
    try:
        eng = ScheduledEngine(params, cfg, SchedulerConfig(
            n_slots=n_slots, page_size=page_size,
            pages_per_slot=pages_per_slot))
        rids = [eng.submit(p, sp, arrival=int(a))
                for p, sp, a in zip(prompts, sps, arrivals)]
        eng.run()
        snap = obs.snapshot()
        wfs = obs.request_waterfalls(snap)
        if sorted(w["rid"] for w in wfs) != sorted(rids):
            failures.append(
                f"trace: waterfalls cover {sorted(w['rid'] for w in wfs)}, "
                f"expected {sorted(rids)}")
        for w in wfs:
            r = eng.requests[w["rid"]]
            if w["state"] != "done":
                continue
            if w["ttft_ns"] != r.t_first_ns - r.t_submit_ns:
                failures.append(
                    f"trace: rid {w['rid']} stage sum {w['ttft_ns']}ns != "
                    f"measured TTFT {r.t_first_ns - r.t_submit_ns}ns")
            if w["total_ns"] != r.t_finish_ns - r.t_submit_ns:
                failures.append(
                    f"trace: rid {w['rid']} root span != request latency")
            if w["unaccounted_ns"] < 0:
                failures.append(
                    f"trace: rid {w['rid']} negative unaccounted time")
            stages = [s["name"] for s in w["stages"]]
            for want in ("req.queue_wait", "req.prefill", "req.insert"):
                if want not in stages:
                    failures.append(
                        f"trace: rid {w['rid']} missing stage {want}")
            if w["decode_ticks"] != new_tokens - 1:
                failures.append(
                    f"trace: rid {w['rid']} has {w['decode_ticks']} decode "
                    f"ticks, expected {new_tokens - 1}")
        trace = obs.request_chrome_trace(snap)
        for e in obs.validate_chrome_trace(trace):
            failures.append(f"trace: chrome trace schema: {e}")
        trace_path = os.path.abspath(BENCH_SERVE_JSON).replace(
            ".json", ".trace.json")
        with open(trace_path, "w") as f:
            json.dump(trace, f, indent=1, sort_keys=True, default=str)
            f.write("\n")
        print(f"# wrote {trace_path} ({len(wfs)} request waterfalls, "
              f"{len(trace['traceEvents'])} events)", file=sys.stderr)
    finally:
        obs.set_enabled(prev)


def collect_rows():
    rows, failures = [], []
    for case in CASES:
        row, fails = _run_case(*case)
        rows.append(row)
        failures += fails
        emit(f"serve_{case[0]}_b{case[1]}", row["p50_us"],
             f"tok/s {row['tok_per_s']} p99 {row['p99_us']}us")
    return rows, failures


def run():
    rows, failures = collect_rows()
    lrows, lfails = collect_load_rows()
    rows += lrows
    failures += lfails
    if rows:
        path = write_serve_json(rows)
        print(f"# wrote {path}", file=sys.stderr)
    for f in failures:
        print(f"SERVE-CHECK-FAIL {f}", file=sys.stderr)
    return rows, failures


def main(check: bool = False, faults: bool = False,
         trace_requests: bool = False) -> int:
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    failures = []
    if trace_requests:
        # standalone mode: only the request-trace gate runs (CI's schema
        # smoke); rows are untouched so the committed trajectory and the
        # sentinel baseline stay stable
        _trace_requests(failures)
        for f in failures:
            print(f"SERVE-CHECK-FAIL {f}", file=sys.stderr)
        return 1 if failures else 0
    rows, failures = collect_rows()
    lrows, lfails = collect_load_rows()
    rows += lrows
    failures += lfails
    if faults:
        frows, ffails = collect_fault_rows()
        rows += frows
        failures += ffails
    if check:
        _obs_smoke(failures)
    if rows:
        path = write_serve_json(rows)
        print(f"# wrote {path}", file=sys.stderr)
    for f in failures:
        print(f"SERVE-CHECK-FAIL {f}", file=sys.stderr)
    if check and failures:
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main(check="--check" in sys.argv,
                  faults="--faults" in sys.argv,
                  trace_requests="--trace-requests" in sys.argv))
