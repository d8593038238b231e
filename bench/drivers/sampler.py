"""Closed-loop driver of the decode tick's sampling call alone.

The call is the tail of the scheduler's decode program, as a library caller
sampling over a full vocabulary makes it: one mixed-k ``segment_topk`` over
every row, then per row the temperature / top-p draw over its own candidates
and the tie-canonical token. Each call is blocked on its tokens before the
next is sent. The logits are drawn on the device from the seed.

Correctness: for a sample of calls drawn from the seed (the last call
always among them), every row's selected values must equal the row's exact
top-k (numpy sort of the same bf16 logits), and every drawn token must lie
in its row's candidate set (top-k, and the nucleus where top-p applies).
Both are exact counts with the limit 0. A program obtained (compiled or
loaded) while the window is open, which a fallback would need, also makes
the run not correct.
"""
from __future__ import annotations

import gc

import numpy as np

from bench import work
from bench.harness import (Check, CompileCounter, Ctx, GcWatch, Outcome, TraceSlice, log,
                           memory_peak_bytes, now_ns, settle)


def _call_fn(rows: int, vocab: int, ks: tuple, topp_rows: tuple):
    import jax
    import jax.numpy as jnp

    from repro.api import segment_topk
    from repro.serving.sample import canonical_token, scored_draw

    offsets = tuple(range(0, (rows + 1) * vocab, vocab))

    def call(logits, key, temps, tps):
        vals, _, out_offs = segment_topk(logits.reshape(-1), offsets, ks)
        subs = jax.random.split(key, rows)
        toks = []
        for s in range(rows):
            row = logits[s]
            if ks[s] == 1:
                toks.append(jnp.argmax(row, axis=-1).astype(jnp.int32))
                continue
            vals_s = vals[out_offs[s]:out_offs[s + 1]][None]
            choice = scored_draw(subs[s], vals_s, temps[s],
                                 tps[s] if topp_rows[s] else None)
            toks.append(canonical_token(row[None], vals_s, choice)[0])
        return vals, jnp.stack(toks)

    return jax.jit(call)


def exact_topk(row: np.ndarray, k: int) -> np.ndarray:
    return np.sort(row.astype(np.float32))[::-1][:k]


def nucleus_floor(top: np.ndarray, temperature: float, top_p: float) -> float:
    """Smallest candidate value the nucleus keeps (top-1 always kept)."""
    z = top.astype(np.float64) / temperature
    p = np.exp(z - z.max())
    p /= p.sum()
    cum = np.cumsum(p)
    keep = np.concatenate([[True], cum[:-1] < top_p])
    return float(top[keep].min())


def compare(logits: np.ndarray, vals: np.ndarray, toks: np.ndarray, ks, tps,
            temperature: float, slack: float = 1e-6):
    """(rows whose values differ from the exact top-k, tokens outside their
    candidate set) for one call. ``slack`` only absorbs the float64 cumsum
    of the nucleus edge; top-k membership is exact."""
    bad_vals = bad_toks = 0
    off = 0
    for s, k in enumerate(ks):
        top = exact_topk(logits[s], k)
        got = vals[off:off + k].astype(np.float32)
        off += k
        bad_vals += int(not np.array_equal(got, top))
        floor = top[-1]
        if tps[s] < 1.0 and k > 1:
            floor = nucleus_floor(top, temperature, tps[s]) - slack
        bad_toks += int(float(logits[s, toks[s]]) < floor)
    return bad_vals, bad_toks


def run(ctx: Ctx) -> Outcome:
    import jax
    import jax.numpy as jnp
    import jax.profiler as jprof

    from repro.obs import trace as obs_trace

    from bench.weights import seed_key

    mix = ctx.traffic
    vocab = ctx.config["model"]["vocab_size"]
    rows, ks = mix["rows"], tuple(mix["k"])
    tps = tuple(float(p) for p in mix["top_p"])
    temp = float(mix["temperature"])
    assert len(ks) == rows == len(tps)
    topp_rows = tuple(p < 1.0 and k > 1 for k, p in zip(ks, tps))
    n_inputs = mix["inputs"]

    gen = jax.jit(lambda key: (jax.random.normal(key, (n_inputs, rows, vocab))
                               * mix["logit_scale"]).astype(jnp.bfloat16))
    logits_all = gen(seed_key(ctx.seed, salt=2))
    inputs = [logits_all[i] for i in range(n_inputs)]
    base_key = seed_key(ctx.seed, salt=3)
    temps = jnp.full((rows,), temp, jnp.float32)
    tp_arr = jnp.asarray(np.asarray(tps, np.float32))
    call = _call_fn(rows, vocab, ks, topp_rows)
    if ctx.fault is not None:
        call = ctx.fault("sampler.call", call)
    keys = jax.jit(lambda k, i: jax.random.fold_in(k, i))

    for i in range(2):  # warm: compile, then one steady call
        np.asarray(call(inputs[i % n_inputs], keys(base_key, i), temps, tp_arr)[1])

    obs_trace.set_enabled(ctx.trace)
    counter, gcw = CompileCounter(), GcWatch()
    settle()
    setup_s = (now_ns() - ctx.t_start_ns) / 1e9
    seconds_ns = int(ctx.seconds * 1e9)
    t0 = now_ns()
    slice_ = TraceSlice(ctx.trace, t0 + seconds_ns // 3, t0 + seconds_ns // 3 + 2 * 10**9)
    t_end = t0 + seconds_ns
    outs = []
    i = 0
    counter.armed = gcw.armed = True
    while True:
        t = now_ns()
        if t >= t_end:
            break
        slice_.poll(t)
        with jprof.TraceAnnotation("bench.call", call=i):
            vals, toks = call(inputs[i % n_inputs], keys(base_key, i), temps, tp_arr)
            toks = np.asarray(toks)
        outs.append((i, vals, toks))
        i += 1
    t1 = now_ns()
    counter.armed = False
    gcw.close()
    gc.unfreeze()
    window_s = (t1 - t0) / 1e9
    trace = slice_.result()
    mem = memory_peak_bytes(jax.local_devices()[:1])

    calls = len(outs)
    elems = rows * vocab * calls
    log(f"[sampler] {calls} calls of {rows}x{vocab} in {window_s:.3f} s "
        f"({window_s / max(calls, 1) * 1e3:.4f} ms per call); {counter.count} programs "
        f"obtained in the window; collector in the window: {gcw.summary()}")

    # correctness on a sample of calls drawn from the seed, last one included
    rng = np.random.default_rng([ctx.seed, 7])
    n_check = min(mix["check_calls"], calls)
    pick = set(rng.choice(calls - 1, size=max(n_check - 1, 0), replace=False).tolist()) if calls > 1 else set()
    pick.add(calls - 1)
    bad_vals = bad_toks = 0
    host_inputs = {}
    for j in sorted(pick):
        idx, vals, toks = outs[j]
        src = idx % n_inputs
        if src not in host_inputs:
            host_inputs[src] = np.asarray(inputs[src].astype(jnp.float32))
        bv, bt = compare(host_inputs[src], np.asarray(vals.astype(jnp.float32)),
                         toks, ks, tps, temp)
        bad_vals += bv
        bad_toks += bt
    checks = [Check("topk_rows_differing", bad_vals, mix["check"]["topk_rows_differing"]),
              Check("tokens_outside_candidates", bad_toks,
                    mix["check"]["tokens_outside_candidates"]),
              Check("programs_obtained_in_window", counter.count, 0)]
    log(f"[sampler] checked {len(pick)} calls ({len(pick) * rows} rows)")

    record = {
        "window_s": window_s,
        "calls": calls,
        "trace": trace,
        "least_time_s_per_call": work.topk_bytes(rows, vocab, ks) / ctx.peaks["hbm_bytes_per_s"],
        "peaks": ctx.peaks,
    }
    return Outcome(attempted=calls, failed=0, setup_s=setup_s,
                   end_to_end={"melem_per_s": elems / window_s / 1e6},
                   record=record, checks=checks, memory_peak_bytes=mem)
