"""Fused vs unfused pipeline benchmark (+ the BENCH_sort.json trajectory).

For each benchmarked (op, shape, dtype) this measures the **fused**
single-launch pallas path (in-kernel key transform, VMEM payload lanes)
against the **unfused** pre-fusion pipeline (XLA-level encode/decode,
executor payload carry) and reports two numbers per variant:

* ``xla_ops`` — the count of XLA-level jaxpr equations, descending into
  pjit/custom_vjp sub-jaxprs but *not* into Pallas kernel bodies. This is
  the deterministic proxy the fused pipeline optimizes: every eliminated
  eqn is a launch / HBM round-trip that no longer exists. CI gates on
  bit-equality and this proxy — never on wall time.
* ``wall_us`` — median wall time. Meaningful on TPU; on CPU hosts the
  kernels run in interpret mode (emulated per-op), so wall time is
  recorded for the trajectory but is **not** a pass/fail signal.

``python -m benchmarks.fused_pipeline --check`` runs the perf-smoke gate:
every fused result must be bit-identical to the ``jnp.sort``/``lax.top_k``
reference (NaN-position aware) and must not use more XLA-level ops than
the unfused pipeline. Exits non-zero on any mismatch.

``benchmarks.run`` calls :func:`collect_rows` and writes the repo-root
``BENCH_sort.json`` so perf regressions stay visible across PRs.
"""
from __future__ import annotations

import sys

import jax
import jax.numpy as jnp
import numpy as np

from .common import emit, timeit_stats

#: benchmarked shapes: (op, batch, lengths, payload?)
CASES = [
    ("sort", 8, (128,), True),
    ("sort", 8, (512,), True),
    ("sort", 4, (1024,), False),
    ("sort", 16, (1007,), True),  # non-pow2: in-kernel pad + compact
    ("merge", 8, (256, 256), True),
    ("merge", 8, (512, 256), False),
    ("merge_k", 8, (64, 96, 32), True),
    ("topk", 8, (256,), False),
    ("topk", 8, (4096,), False),
]
TOPK_K = 16


def count_xla_ops(fn, *args) -> int:
    """XLA-level eqn count: recurse into pjit / custom_vjp call jaxprs but
    stop at pallas_call (kernel internals are on-chip, not HBM traffic)."""
    def walk(jaxpr) -> int:
        n = 0
        for eqn in jaxpr.eqns:
            n += 1
            if eqn.primitive.name == "pallas_call":
                continue
            for v in eqn.params.values():
                if hasattr(v, "jaxpr"):
                    n += walk(v.jaxpr)
                elif isinstance(v, (list, tuple)):
                    for vi in v:
                        if hasattr(vi, "jaxpr"):
                            n += walk(vi.jaxpr)
        return n

    return walk(jax.make_jaxpr(fn)(*args).jaxpr)


def _inputs(rng, op, batch, lens, payload):
    if op == "sort":
        x = jnp.asarray(rng.normal(size=(batch, lens[0])).astype(np.float32))
        args = [x]
    elif op == "topk":
        args = [jnp.asarray(rng.normal(size=(batch, lens[0])).astype(np.float32))]
    else:
        args = [jnp.sort(jnp.asarray(
            rng.normal(size=(batch, n)).astype(np.float32)), -1) for n in lens]
    pay = None
    if payload:
        total = lens[0] if op in ("sort", "topk") else None
        if op == "sort":
            pay = jnp.asarray(rng.integers(0, total, (batch, total)), jnp.int32)
        else:
            pay = [jnp.asarray(rng.integers(0, 99, a.shape), jnp.int32)
                   for a in args]
    return args, pay


def _call(op, args, pay, backend):
    import repro

    if op == "sort":
        if pay is None:
            return repro.sort(args[0], backend=backend)
        return repro.sort(args[0], payload=pay, backend=backend)
    if op == "merge":
        if pay is None:
            return repro.merge(args[0], args[1], backend=backend)
        return repro.merge(args[0], args[1], payload=tuple(pay),
                           backend=backend)
    if op == "merge_k":
        if pay is None:
            return repro.merge_k(args, backend=backend)
        return repro.merge_k(args, payload=list(pay), backend=backend)
    assert op == "topk"
    return repro.topk(args[0], TOPK_K, backend=backend)


def _reference(op, args, pay):
    cat = jnp.concatenate(args, -1) if len(args) > 1 else args[0]
    if op == "topk":
        v, i = jax.lax.top_k(cat, TOPK_K)
        return v
    return jnp.sort(cat, -1)


def _flat_vals(res, op, pay):
    if op == "topk":
        return res[0]
    return res[0] if pay is not None else res


def collect_rows(iters: int = 3):
    """Measure every case fused and unfused; returns (rows, failures)."""
    from repro.api import fused as fused_mod

    from repro.streaming.planner import plan_op

    rng = np.random.default_rng(0)
    rows, failures = [], []
    plan_ops = {"sort": "sort", "merge": "merge2", "merge_k": "kway",
                "topk": "topk"}
    for op, batch, lens, payload in CASES:
        args, pay = _inputs(rng, op, batch, lens, payload)
        shape = f"{batch}x" + "+".join(str(n) for n in lens)
        # the comparator-network family the planner (tournament winner on
        # a tuned cache, LOMS heuristic otherwise) assigns this size class
        network = plan_op(plan_ops[op], lens, batch=batch,
                          dtype=jnp.float32,
                          k=TOPK_K if op == "topk" else None).network

        fused_fn = jax.jit(lambda *a, _op=op, _p=pay: _call(_op, list(a), _p,
                                                            "pallas"))
        prev = fused_mod.set_fused_enabled(False)
        try:
            unfused_fn = jax.jit(lambda *a, _op=op, _p=pay: _call(
                _op, list(a), _p, "pallas"))
            unfused_fn(*args)  # trace (and compile) while fusion is off
            unfused_ops = count_xla_ops(unfused_fn, *args)
        finally:
            fused_mod.set_fused_enabled(prev)
        fused_ops = count_xla_ops(fused_fn, *args)

        ref = _reference(op, args, pay)
        got = _flat_vals(fused_fn(*args), op, pay)
        ok = np.array_equal(np.asarray(got), np.asarray(ref), equal_nan=True)
        if not ok:
            failures.append(f"{op}[{shape}]: fused != reference")
        if fused_ops > unfused_ops:
            failures.append(
                f"{op}[{shape}]: fused xla_ops {fused_ops} > unfused "
                f"{unfused_ops}")
        st_fused = timeit_stats(fused_fn, *args, iters=iters)
        st_unfused = timeit_stats(unfused_fn, *args, iters=iters)
        for backend, ops, st in (("pallas-fused", fused_ops, st_fused),
                                 ("unfused", unfused_ops, st_unfused)):
            rows.append({
                "op": op,
                "shape": shape,
                "dtype": "float32",
                "payload": payload,
                "backend": backend,
                "wall_us": round(st.p50_us, 1),
                **st.to_row(),
                "xla_ops": ops,
                "network": network,
                "platform": jax.default_backend(),
            })
        emit(f"fused_{op}_{shape}", st_fused.p50_us,
             f"xla_ops {fused_ops} vs unfused {unfused_ops} "
             f"({st_unfused.p50_us:.0f}us)", stats=st_fused)
    return rows, failures


def run():
    rows, failures = collect_rows()
    for f in failures:
        print(f"FUSED-CHECK-FAIL {f}", file=sys.stderr)
    return rows, failures


def main(check: bool = False) -> int:
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    rows, failures = run()
    if check and failures:
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main(check="--check" in sys.argv))
