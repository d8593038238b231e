"""Padded-dense vs segmented benchmark across raggedness ratios.

The segmented subsystem's claim is that bucketed size classes beat the
pad-everything-to-the-max fallback as raggedness grows. For each length
distribution this measures three realizations of the same per-segment
sort / top-k problem:

* ``padded-dense`` — every segment padded to the max length, one dense
  ``jnp.sort`` over the (S, max_len) matrix (the pre-PR 5 fallback);
* ``segmented`` — the bucketed class kernels (``backend="segmented"``);
* ``seg-reference`` — the per-segment XLA reference (the escape hatch).

Two deterministic proxies ride along with wall time:

* ``padded_slots`` — total network lanes processed: ``sum(n_c * W_c)``
  over the size classes vs ``S * ceil_pow2(max_len)`` for the dense pad.
  This is the comparator-count-shaped quantity the bucketing optimizes;
  it is exact at trace time and platform-independent.
* ``xla_ops`` — jaxpr equation count (HBM-level launches), as in
  benchmarks.fused_pipeline.

``python -m benchmarks.segmented --check`` runs the perf-smoke gate:
segmented results must be bit-identical to the per-segment reference on
every case, and ``padded_slots`` must never exceed the padded-dense
count. Wall time is recorded, never gated.
"""
from __future__ import annotations

import sys

import jax
import jax.numpy as jnp
import numpy as np

from .common import emit, timeit_stats
from .fused_pipeline import count_xla_ops

#: (name, segment length distribution) — lengths chosen so total work is
#: comparable while the max/mean ratio (raggedness) grows
CASES = [
    ("uniform", [64] * 48),
    ("mild", [32, 48, 64, 96] * 12),
    ("heavy", [8] * 24 + [16] * 12 + [64] * 8 + [256] * 4),
    ("extreme", [1] * 20 + [4] * 16 + [16] * 8 + [1024]),
]
TOPK_K = 8


def _padded_slots_segmented(lengths) -> int:
    from repro.kernels.common import ceil_pow2
    from repro.segmented import bucket_segments, max_class_width

    classes, spill = bucket_segments(np.asarray(lengths),
                                     max_class_width(jnp.float32))
    slots = sum(c.n * c.width for c in classes)
    slots += sum(c.n * ceil_pow2(c.width) for c in spill)
    return slots


def _padded_slots_dense(lengths) -> int:
    from repro.kernels.common import ceil_pow2

    return len(lengths) * ceil_pow2(max(lengths))


def _ref_sort(x, offs):
    parts = [np.sort(np.asarray(x[a:b])) for a, b in zip(offs, offs[1:])]
    return np.concatenate(parts) if parts else np.asarray(x[:0])


def _padded_dense_sort(x, offs, max_len):
    """The pre-segmented fallback: scatter into (S, max_len) with +inf
    pads, one dense sort, gather the live prefixes back. The same index
    map serves both directions — lane j of row r is CSR slot offs[r]+j
    going in, and (because +inf pads sort to the tail) coming out."""
    s = len(offs) - 1
    gmap = np.full((s, max_len), offs[-1], np.int64)
    for r, (a, b) in enumerate(zip(offs, offs[1:])):
        gmap[r, :b - a] = np.arange(a, b)
    ext = jnp.concatenate([x, jnp.full((1,), np.inf, x.dtype)])
    dense = jnp.sort(ext[jnp.asarray(gmap)], axis=-1)
    out = jnp.zeros((offs[-1] + 1,), x.dtype)
    return out.at[jnp.asarray(gmap).reshape(-1)].set(
        dense.reshape(-1))[:offs[-1]]


def collect_rows(iters: int = 3):
    import repro

    rng = np.random.default_rng(0)
    rows, failures = [], []
    for name, lengths in CASES:
        offs = tuple(np.concatenate([[0], np.cumsum(lengths)]).tolist())
        n = offs[-1]
        max_len = max(lengths)
        x = jnp.asarray(rng.normal(size=(n,)).astype(np.float32))
        shape = f"S{len(lengths)}xN{n}xmax{max_len}"
        # comparator-network family the planner assigns the dominant size
        # class (tournament winner on a tuned cache, LOMS heuristic
        # otherwise) — the per-class stamp the decision audit reads
        from repro.segmented.core import max_class_width
        from repro.streaming.planner import plan_op

        top_w = min(1 << (max(max_len - 1, 1)).bit_length(),
                    max_class_width(jnp.float32))
        network = plan_op("segmented", (top_w,), batch=len(lengths),
                          dtype=jnp.float32).network
        ref = _ref_sort(x, offs)

        from repro.segmented.core import segment_sort_impl

        seg_fn = jax.jit(lambda v, _o=offs: repro.segment_sort(
            v, _o, backend="segmented"))
        # pinned to the per-segment XLA reference on every platform (auto
        # routing would silently measure the kernels again on TPU)
        ref_fn = jax.jit(lambda v, _o=offs: segment_sort_impl(
            v, _o, use_kernel=False)[0])
        dense_fn = jax.jit(lambda v, _o=offs, _m=max_len:
                           _padded_dense_sort(v, _o, _m))

        got = np.asarray(seg_fn(x))
        if not np.array_equal(got, ref, equal_nan=True):
            failures.append(f"sort[{name}]: segmented != per-segment ref")
        if not np.array_equal(np.asarray(dense_fn(x)), ref, equal_nan=True):
            failures.append(f"sort[{name}]: padded-dense harness broken")

        slots_seg = _padded_slots_segmented(lengths)
        slots_dense = _padded_slots_dense(lengths)
        if slots_seg > slots_dense:
            failures.append(
                f"sort[{name}]: segmented padded_slots {slots_seg} > "
                f"dense {slots_dense}")

        variants = (("segmented", seg_fn, slots_seg),
                    ("seg-reference", ref_fn, slots_seg),
                    ("padded-dense", dense_fn, slots_dense))
        for backend, fn, slots in variants:
            st = timeit_stats(fn, x, iters=iters)
            rows.append({
                "op": "segment_sort",
                "shape": shape,
                "dtype": "float32",
                "payload": False,
                "backend": backend,
                "wall_us": round(st.p50_us, 1),
                **st.to_row(),
                "xla_ops": count_xla_ops(fn, x),
                "padded_slots": slots,
                "raggedness": round(max_len * len(lengths) / n, 2),
                "network": network,
                "platform": jax.default_backend(),
            })
        emit(f"segmented_sort_{name}", rows[-3]["wall_us"],
             f"slots {slots_seg} vs dense {slots_dense} "
             f"(x{slots_dense / max(slots_seg, 1):.1f} saved)")

        # mixed-k top-k: the continuous-batching shape
        ks = tuple(min(TOPK_K, ln) if ln else 0 for ln in lengths)
        topk_fn = jax.jit(lambda v, _o=offs, _k=ks: repro.segment_topk(
            v, _o, _k, backend="segmented")[0])
        vals = np.asarray(topk_fn(x))
        ref_parts = [np.sort(np.asarray(x[a:b]))[::-1][:k]
                     for (a, b), k in zip(zip(offs, offs[1:]), ks)]
        ref_topk = (np.concatenate(ref_parts) if ref_parts
                    else np.zeros((0,), np.float32))
        if not np.array_equal(vals, ref_topk, equal_nan=True):
            failures.append(f"topk[{name}]: segmented != per-segment ref")
        st = timeit_stats(topk_fn, x, iters=iters)
        rows.append({
            "op": "segment_topk",
            "shape": shape,
            "dtype": "float32",
            "payload": False,
            "backend": "segmented",
            "wall_us": round(st.p50_us, 1),
            **st.to_row(),
            "xla_ops": count_xla_ops(topk_fn, x),
            "padded_slots": slots_seg,
            "raggedness": round(max_len * len(lengths) / n, 2),
            "network": network,
            "platform": jax.default_backend(),
        })
    return rows, failures


def run():
    rows, failures = collect_rows()
    for f in failures:
        print(f"SEGMENTED-CHECK-FAIL {f}", file=sys.stderr)
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
