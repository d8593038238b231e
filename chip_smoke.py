#!/usr/bin/env python3
"""Bring-up check on a TPU: the LOMS kernels and the qwen3-8b serving path.

    python chip_smoke.py              # one chip: phases `library`, `serve`
    python chip_smoke.py --chips 4    # only the chip-spanning path (4 chips)

One process drives the main path once through the public entry points
and fails loudly:

* ``library`` — ``repro.merge`` / ``repro.merge_k`` / ``repro.sort`` at
  the paper's sizes (2x32, 3x7, a 1024-wide sort) over batches a caller
  would send, and ``repro.topk`` at a router shape (4096x128, k 8) and a
  vocabulary shape (8x151,936, k 64). Outputs must equal ``np.sort`` /
  ``np.argsort`` exactly (rows hold distinct values, so indices are
  unique too).
* ``serve`` — qwen3-8b at its published widths, depth cut to fit one
  chip, random weights from ``--seed``; about 8 requests of mixed
  sampling through the ``ScheduledEngine`` that ``repro.launch.serve
  --engine`` builds. Every request must end DONE; the top-k values of
  decode logits rows must equal ``jax.lax.top_k`` exactly. Whether the
  greedy streams match a solo ``generate()`` bit for bit is printed, not
  enforced (DESIGN §14 claims it; the chip decides).
* ``--chips 4`` — ``repro.sort`` over a 4-chip mesh (the PSRS sample
  sort) and ``sample_topk`` over a TP-sharded 151,936 vocabulary, each
  against the single-device result, bit for bit.

Every phase prints the ``plan()`` decision of each op it runs and fails
unless it names the Pallas (or fused) route; every phase fails if the
resilience ladder fell back or forced a rung, or the scheduler retried or
failed a launch. Without a TPU, or run away from the repository's
``src/``, the script exits non-zero before printing a result. The last
line of a passing run is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

#: failure counters no healthy run may increment
BAD_COUNTERS = ("resilience.fallbacks", "resilience.forced",
                "sched.retries", "sched.failed")


class SmokeFailure(RuntimeError):
    pass


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# shared checks: plan decisions and failure counters
# ---------------------------------------------------------------------------


def _decisions():
    from repro.obs import metrics as obs_metrics

    return {tuple(sorted(s["labels"].items())): s["value"]
            for s in obs_metrics.counter("plan.decisions").series()}


def _new_decisions(before):
    after = _decisions()
    return [dict(k) for k, v in after.items() if v > before.get(k, 0)]


def _kernel_route(dec: dict) -> bool:
    return dec["backend"] == "pallas" or "pallas" in dec["detail"]


def check_routes(name: str, before) -> None:
    """Print the plan decisions taken since ``before``; each must name
    the Pallas / fused route."""
    decs = _new_decisions(before)
    _check(bool(decs), f"{name}: no plan() decision was recorded")
    for d in decs:
        log(f"  plan {name}: op={d['op']} backend={d['backend']} "
            f"detail={d['detail']} network={d['network']} "
            f"source={d['source']} payload={d['payload']}")
        _check(_kernel_route(d),
               f"{name}: plan chose {d['backend']}/{d['detail']}, not the "
               "Pallas route")


def check_counters(phase: str) -> None:
    from repro.obs import metrics as obs_metrics

    for name in BAD_COUNTERS:
        c = obs_metrics.counter(name)
        log(f"  counter {name} = {c.total():g}")
        for series in c.series():
            log(f"    {series['labels']}: {series['value']:g}")
        _check(c.total() == 0, f"{phase}: {name} counted {c.total():g}")


def lowers_to_kernel(fn, *args) -> bool:
    """Whether ``fn`` lowers to a Mosaic kernel call on this backend."""
    import jax

    return "tpu_custom_call" in jax.jit(fn).lower(*args).as_text()


def _distinct_rows(rng, rows: int, n: int, scale: float = 1 / 128):
    """(rows, n) float32 rows, each a random permutation of ``n`` distinct
    exactly-representable values."""
    import numpy as np

    base = np.arange(n, dtype=np.float32) * np.float32(scale) - np.float32(n * scale / 2)
    return rng.permuted(np.broadcast_to(base, (rows, n)), axis=1).astype(np.float32)


# ---------------------------------------------------------------------------
# phase: library
# ---------------------------------------------------------------------------


def run_op(name: str, fn, args, ref):
    """Run one public op, check its route, and compare with ``ref``
    (a list of arrays) exactly."""
    import jax
    import numpy as np

    before = _decisions()
    _check(lowers_to_kernel(fn, *args),
           f"{name}: the op does not lower to a Pallas kernel")
    t0 = time.perf_counter()
    out = fn(*args)
    out = jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    outs = out if isinstance(out, (tuple, list)) else (out,)
    check_routes(name, before)
    for i, (o, r) in enumerate(zip(outs, ref)):
        o = np.asarray(o)
        _check(o.shape == r.shape and o.dtype == r.dtype,
               f"{name}: output {i} is {o.dtype}{o.shape}, want "
               f"{r.dtype}{r.shape}")
        bad = int((o != r).sum())
        _check(bad == 0, f"{name}: output {i} differs from numpy in {bad} "
               "entries")
    log(f"  {name}: exact vs numpy ({dt:.3f} s wall, first call includes "
        "compilation)")


def phase_library(seed: int, *, merge_rows: int = 32_768,
                  kway_rows: int = 30_000, sort_rows: int = 256,
                  router_tokens: int = 4096, vocab_rows: int = 8,
                  vocab: int = 151_936) -> None:
    import jax.numpy as jnp
    import numpy as np

    import repro

    rng = np.random.default_rng(seed)
    log("[library] merge 2x32 (the paper's UP-32/DN-32 device), "
        f"{merge_rows} rows")
    ab = np.sort(_distinct_rows(rng, merge_rows, 64), -1)
    perm = rng.permuted(np.broadcast_to(np.arange(64), (merge_rows, 64)), axis=1)
    ab = np.take_along_axis(ab, perm, -1)
    a = np.sort(ab[:, :32], -1)
    b = np.sort(ab[:, 32:], -1)
    ref = np.sort(np.concatenate([a, b], -1), -1)
    run_op("merge 2x32", lambda a, b: repro.merge(a, b),
           (jnp.asarray(a), jnp.asarray(b)), [ref])
    # raw floats (no key transform) take the byte-wise one-hot MXU permute
    run_op("merge 2x32 unsafe (MXU permute)",
           lambda a, b: repro.merge(a, b, nan_policy="unsafe"),
           (jnp.asarray(a), jnp.asarray(b)), [ref])

    log(f"[library] merge_k 3x7 (the paper's 3-way device), {kway_rows} rows")
    x = _distinct_rows(rng, kway_rows, 21)
    lists = [np.sort(x[:, 7 * j:7 * j + 7], -1) for j in range(3)]
    run_op("merge_k 3x7", lambda *ls: repro.merge_k(list(ls)),
           tuple(jnp.asarray(l) for l in lists),
           [np.sort(np.concatenate(lists, -1), -1)])

    log(f"[library] sort 1024 wide, {sort_rows} rows (values + argsort)")
    x = _distinct_rows(rng, sort_rows, 1024)
    pos = np.broadcast_to(np.arange(1024, dtype=np.int32), x.shape)
    run_op("sort 1024", lambda x: repro.sort(x), (jnp.asarray(x),),
           [np.sort(x, -1)])
    order = np.argsort(x, -1, kind="stable").astype(np.int32)
    run_op("sort 1024 + payload",
           lambda x, p: repro.sort(x, payload=p),
           (jnp.asarray(x), jnp.asarray(pos)),
           [np.sort(x, -1), order])

    for tag, rows, n, k in (("router", router_tokens, 128, 8),
                            ("vocab", vocab_rows, vocab, 64)):
        log(f"[library] topk {tag} {rows}x{n}, k {k}")
        x = _distinct_rows(rng, rows, n)
        idx = np.argsort(-x, -1, kind="stable")[:, :k].astype(np.int32)
        run_op(f"topk {tag} {rows}x{n} k{k}",
               lambda x, k=k: repro.topk(x, k), (jnp.asarray(x),),
               [np.take_along_axis(x, idx, -1), idx])
    check_counters("library")


# ---------------------------------------------------------------------------
# phase: serve
# ---------------------------------------------------------------------------

#: (prompt length, SamplingParams kwargs): greedy, k=1, k=50 and top-p
SERVE_MIX = (
    (64, dict(k=64, temperature=0.0)),
    (96, dict(k=1)),
    (128, dict(k=50)),
    (160, dict(k=50, top_p=0.9)),
    (192, dict(k=64, temperature=0.0)),
    (224, dict(k=50, temperature=0.8)),
    (256, dict(k=1)),
    (80, dict(k=50, top_p=0.8)),
)


def serve_config(arch: str, layers: int):
    from repro.configs import get_config

    full = get_config(arch)
    return dataclasses.replace(full, n_layers=min(layers, full.n_layers))


def param_count(params) -> int:
    import jax

    return sum(int(x.size) for x in jax.tree.leaves(params))


#: qwen3-8b layers kept on one chip: the f32 weights (2.0 B parameters,
#: 8.1 GB) and a step's temporaries fit the 16 GB of one v5e
SERVE_LAYERS = 4


def phase_serve(seed: int, *, arch: str = "qwen3-8b",
                layers: int = SERVE_LAYERS,
                mix=SERVE_MIX, new_tokens: int = 16, slots: int = 4,
                page_size: int = 16, topk_rows: int = 4) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import repro
    from repro.api import segment_topk
    from repro.launch.serve import build_engine, unfinished
    from repro.models import decode_step, init_cache, model_init, prefill
    from repro.serving.engine import ServeConfig, generate
    from repro.serving.scheduler import SamplingParams

    full_layers = serve_config(arch, 10**6).n_layers
    cfg = serve_config(arch, layers)
    log(f"[serve] {cfg.name}: d_model {cfg.d_model}, heads "
        f"{cfg.n_heads}/{cfg.n_kv_heads} (d_head {cfg.head_dim}), d_ff "
        f"{cfg.d_ff}, vocab {cfg.vocab_size}, qk_norm {cfg.qk_norm}; depth "
        f"cut {full_layers} -> {cfg.n_layers} layers to fit one chip")
    params, _ = model_init(jax.random.PRNGKey(seed), cfg)
    n = param_count(params)
    nbytes = sum(int(x.size) * x.dtype.itemsize for x in jax.tree.leaves(params))
    log(f"[serve] random weights (seed {seed}): {n / 1e9:.3f} B parameters, "
        f"{nbytes / 1e9:.2f} GB as stored "
        f"({jax.tree.leaves(params)[0].dtype}), compute in {cfg.dtype}")

    rng = np.random.default_rng(seed)
    max_prompt = max(p for p, _ in mix)
    eng = build_engine(params, cfg, slots=slots, page_size=page_size,
                       prompt_len=max_prompt, new_tokens=new_tokens)
    prompts, specs = [], []
    before = _decisions()
    for r, (plen, kw) in enumerate(mix):
        prompts.append(rng.integers(0, cfg.vocab_size, plen).astype(np.int32))
        specs.append(SamplingParams(max_new_tokens=new_tokens, seed=r, **kw))
    rids = [eng.submit(p, sp, arrival=r)
            for r, (p, sp) in enumerate(zip(prompts, specs))]
    t0 = time.perf_counter()
    out = eng.run()
    dt = time.perf_counter() - t0
    log(f"[serve] engine drained {len(out)} requests in {eng.t} ticks, "
        f"{dt:.1f} s wall including compilation ({slots} slots, page "
        f"{page_size})")
    for rid, (plen, kw) in zip(rids, mix):
        req = eng.requests[rid]
        log(f"  request {rid}: prompt {plen}, {kw} -> {req.state.value}, "
            f"{len(req.tokens)} tokens")
    bad = unfinished(eng)
    _check(not bad, f"serve: requests did not finish: {bad}")
    _check(all(eng.requests[r].state.value == "done" for r in rids),
           "serve: not every request ended DONE")
    check_routes("serve engine", before)

    # greedy streams vs a solo generate() with the slot's cache capacity
    greedy = [i for i, sp in enumerate(specs) if sp.greedy][:2]
    same = []
    for i in greedy:
        sp = specs[i]
        sc = ServeConfig(max_new_tokens=sp.max_new_tokens, top_k=sp.k,
                         top_p=sp.top_p, temperature=sp.temperature,
                         seed=sp.seed, cache_len=eng.sc.slot_capacity)
        solo = generate(params, {"tokens": jnp.asarray(prompts[i][None])},
                        cfg, sc)["tokens"][0]
        same.append(bool(np.array_equal(np.asarray(out[rids[i]]), solo)))
    log(f"[serve] greedy tokens match a solo generate() bit for bit: "
        f"{sum(same)}/{len(same)} requests {same}")

    # decode logits rows: the kernels' top-k values vs jax.lax.top_k
    plen = min(p for p, _ in mix)
    toks = jnp.asarray(np.stack([p[:plen] for p in prompts[:topk_rows]]))
    cache = init_cache(cfg, topk_rows, plen + 1)
    logits, cache = jax.jit(lambda p, t, c: prefill(p, {"tokens": t}, c,
                                                    cfg=cfg))(params, toks, cache)
    nxt = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
    pos = jnp.full((topk_rows, 1), plen, jnp.int32)
    logits, _ = jax.jit(lambda p, t, c, q: decode_step(p, t, c, cfg=cfg,
                                                       positions=q))(
        params, nxt, cache, pos)
    logits = jax.block_until_ready(logits)
    v = cfg.vocab_size
    for k in (50, 64):
        before = _decisions()
        got, _ = repro.topk(logits, k)
        ref, _ = jax.lax.top_k(logits, k)
        check_routes(f"decode topk k{k}", before)
        _check(bool(jnp.array_equal(got, ref)),
               f"serve: topk k{k} values differ from jax.lax.top_k")
        before = _decisions()
        offs = tuple(range(0, (topk_rows + 1) * v, v))
        sv, _, _ = segment_topk(logits.reshape(-1), offs, k)
        check_routes(f"decode segment_topk k{k}", before)
        _check(bool(jnp.array_equal(sv.reshape(topk_rows, k), ref)),
               f"serve: segment_topk k{k} values differ from jax.lax.top_k")
        log(f"  decode logits {topk_rows}x{v} ({logits.dtype}), k {k}: "
            "repro.topk and segment_topk values equal jax.lax.top_k exactly")
    check_counters("serve")


# ---------------------------------------------------------------------------
# phase: four chips
# ---------------------------------------------------------------------------


def phase_chips4(seed: int, *, rows: int = 4, width: int = 1 << 16,
                 vocab: int = 151_936, vocab_rows: int = 8) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import repro
    from repro.parallel.sharding import make_parallelism
    from repro.serving.sample import sample_topk

    devs = jax.devices()[:4]
    mesh = jax.make_mesh((1, 4), ("data", "model"), devices=devs)
    par = make_parallelism(mesh)
    log(f"[chips4] mesh {dict(mesh.shape)} over {[d.id for d in devs]}")
    rng = np.random.default_rng(seed)

    x = jnp.asarray(_distinct_rows(rng, rows, width))
    before = _decisions()
    d = jax.block_until_ready(repro.sort(x, par=par))
    decs = _new_decisions(before)
    for dec in decs:
        log(f"  plan sort {rows}x{width} par: op={dec['op']} "
            f"backend={dec['backend']} detail={dec['detail']}")
    _check(any(dec["backend"] == "sharded" for dec in decs),
           "chips4: repro.sort with par did not take the sharded route")
    spread = sorted(dev.id for dev in d.sharding.device_set)
    log(f"  sharded sort output lives on devices {spread}")
    _check(len(spread) == 4, "chips4: the sharded sort did not span 4 chips")
    s = jax.block_until_ready(repro.sort(x))
    _check(bool(jnp.array_equal(d, s)),
           "chips4: sharded sort differs from the single-device sort")
    _check(bool(np.array_equal(np.asarray(d), np.sort(np.asarray(x), -1))),
           "chips4: sharded sort differs from np.sort")
    log(f"  sort {rows}x{width} f32 over 4 chips == single device == np.sort")

    logits = jnp.asarray(_distinct_rows(rng, vocab_rows, vocab))
    key = jax.random.PRNGKey(seed)
    for k in (50, (50, 1, 64, 8, 50, 1, 64, 8)[:vocab_rows]):
        before = _decisions()
        td = jax.block_until_ready(sample_topk(key, logits, k=k, par=par))
        decs = _new_decisions(before)
        ts = jax.block_until_ready(sample_topk(key, logits, k=k))
        for dec in decs:
            log(f"  plan sample_topk k={k} par: op={dec['op']} "
                f"backend={dec['backend']} detail={dec['detail']}")
        _check(any(dec["detail"] == "tree_topk" for dec in decs),
               "chips4: sample_topk with par did not take the tree top-k")
        _check(bool(jnp.array_equal(td, ts)),
               f"chips4: sample_topk k={k} differs from the single device")
        log(f"  sample_topk {vocab_rows}x{vocab}, k={k}: 4 chips == single "
            "device")
    vals_d, idx_d = repro.topk(logits, 64, par=par)
    vals_s, idx_s = repro.topk(logits, 64)
    _check(len(vals_d.sharding.device_set) == 4,
           "chips4: the tree top-k did not span 4 chips")
    _check(bool(jnp.array_equal(vals_d, vals_s) and jnp.array_equal(idx_d, idx_s)),
           "chips4: sharded topk differs from the single device")
    log("  topk k64 values and indices: 4 chips == single device")
    check_counters("chips4")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the chip-spanning path")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no repro package under {src}: run from the "
              "repository", file=sys.stderr)
        return 2
    sys.path.insert(0, src)

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: JAX finds no TPU (platform "
              f"{devs[0].platform!r}); nothing was run", file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX finds {len(devs)}", file=sys.stderr)
        return 1

    from repro.compile_cache import enable_compile_cache
    from repro.obs import trace as obs_trace

    cache_dir = enable_compile_cache()
    obs_trace.set_enabled(True)  # the counters the checks read
    log(f"[chip_smoke] {devs[0].platform} {devs[0].device_kind} x{len(devs)}, "
        f"jax {jax.__version__}, compile cache {cache_dir}")
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            phase_chips4(args.seed)
        else:
            phase_library(args.seed)
            phase_serve(args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    log(f"[chip_smoke] all phases passed in {time.perf_counter() - t0:.1f} s")
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
