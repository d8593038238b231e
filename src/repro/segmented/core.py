"""Segmented (CSR ragged) sort / merge / top-k over size-class buckets.

The execution pipeline for one segmented call (DESIGN.md §12):

1. ``bucketing`` groups the static segments into pow2 size classes.
2. Per class, the member segments form a dense ``(n_segments, width)``
   tile: static slices of the flat input where every row is full (the
   spill classes, vocab rows), else a numpy gather map (invalid lanes
   point at one shared pad slot). **One** Pallas launch
   (`kernels.segmented`) sorts/merges every row — key encode, descending
   flip, validity compaction and the raw-value/payload gather all inside
   the kernel.
3. A numpy scatter map writes each row's valid prefix back to the flat
   CSR output; invalid lanes route to a trash slot that is sliced away.

Segments whose class exceeds the VMEM tile budget spill: equal-length
spill groups batch together, values-only spills chunk-sort in one class
launch and then reduce with the grid-resident FLiMS carry merge
(``streaming.grid_merge``), and permutation-carrying spills take the
batched XLA path (stable argsort of the total-order keys).

Values are always *gathered from the raw input at the permutation* (or
produced by monotone key decode on the values-only spill path), so the
output is bit-identical to a per-segment ``jnp.sort`` for every input —
the paper's "any mixture of input list sizes" property as a first-class
workload instead of a pad-to-max fallback.

Tile knobs (``block_batch``) come from ``streaming.planner.plan_segmented``
through the autotune cache; the escape hatch (``REPRO_DISABLE_SEGMENTED``
/ ``set_segmented_enabled``) and non-TPU auto routing fall back to
:mod:`repro.segmented.reference`.
"""
from __future__ import annotations

import os
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.kernels.common import (
    encode_key_values,
    key_transformable,
    np_fill,
    sentinel_min,
)
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.kernels.segmented import (
    flip_keys,
    key_sentinel,
    segment_class_merge_pallas,
    segment_class_sort_pallas,
)

from .bucketing import (
    SizeClass,
    bucket_merge_pairs,
    bucket_segments,
    gather_map,
    normalize_offsets,
    scatter_map,
    segment_lengths,
)
from .reference import ref_segment_merge, ref_segment_sort, ref_segment_topk

_ENABLED = True

#: hard cap on the dense class width; the planner's VMEM fit can only
#: shrink it further
MAX_CLASS_WIDTH = 2048


def segmented_enabled() -> bool:
    """Whether the bucketed kernel path may be auto-selected (the
    ``REPRO_DISABLE_FUSED``-style escape hatch for this subsystem)."""
    return _ENABLED and os.environ.get("REPRO_DISABLE_SEGMENTED") != "1"


def set_segmented_enabled(enabled: bool) -> bool:
    """Toggle the bucketed kernel path (returns the previous value)."""
    global _ENABLED
    prev = _ENABLED
    _ENABLED = bool(enabled)
    return prev


def max_class_width(dtype) -> int:
    """Largest pow2 class width whose sort working set fits the VMEM
    budget at a 1-row tile — the bucketed-kernel vs spill cutover."""
    from repro.streaming.planner import sort_fits_vmem

    w = MAX_CLASS_WIDTH
    while w > 2 and not sort_fits_vmem(w, block_batch=1, dtype=dtype):
        w //= 2
    return w


def _class_plan(widths: Tuple[int, ...], n_segs: int, dtype):
    from repro.streaming.planner import plan_op

    return plan_op("segmented", widths, batch=n_segs, dtype=dtype)


def _record_bucketing(op: str, classes, spill, offs) -> None:
    """Bucketing telemetry for one segmented call (trace-time: these fire
    once per compilation, the deterministic count). ``classes``/``spill``
    hold :class:`SizeClass` entries for sort/topk and ``(ca, cb)`` pairs
    for merge, whose ``offs`` is the pair ``(offs_a, offs_b)``.

    The padded-slot waste fraction is the segmented analog of the paper's
    resource column: the share of class-kernel lanes that carry sentinel
    padding rather than data. ``rows_sliced`` / ``rows_gathered`` count
    the value-tile rows :func:`_class_rows` slices or gathers (a merge
    pair counts its a and b tiles)."""
    if not obs_trace.enabled():
        return

    def slots(group) -> int:
        if isinstance(group, tuple):  # merge pair
            ca, cb = group
            return ca.n * (ca.width + cb.width)
        return group.n * group.width

    def valid(group) -> int:
        if isinstance(group, tuple):
            return sum(group[0].lens) + sum(group[1].lens)
        return sum(group.lens)

    class_slots = sum(slots(g) for g in classes)
    class_valid = sum(valid(g) for g in classes)
    spill_segs = sum((g[0].n if isinstance(g, tuple) else g.n)
                     for g in spill)
    obs_metrics.counter("segmented.class_launches").inc(len(classes), op=op)
    obs_metrics.counter("segmented.spill_groups").inc(len(spill), op=op)
    obs_metrics.counter("segmented.spill_segments").inc(spill_segs, op=op)
    obs_metrics.counter("segmented.padded_slots").inc(
        class_slots - class_valid, op=op)
    obs_metrics.counter("segmented.valid_slots").inc(class_valid, op=op)
    rows = {"rows_sliced": 0, "rows_gathered": 0}
    for g in (*classes, *spill):
        for o, c in (zip(offs, g) if isinstance(g, tuple) else [(offs, g)]):
            way = "rows_gathered" if _row_runs(o, c) is None else "rows_sliced"
            rows[way] += c.n
    for way, count in rows.items():
        obs_metrics.counter(f"segmented.{way}").inc(count, op=op)
    if class_slots:
        obs_metrics.histogram("segmented.padded_waste_frac").observe(
            (class_slots - class_valid) / class_slots, op=op)


def _flatten_leaves(payload, n: int):
    """Payload pytree -> flat (N[, F]) lanes + a rebuild closure."""
    leaves, treedef = jax.tree.flatten(payload)
    lanes, trails = [], []
    for leaf in leaves:
        assert leaf.ndim >= 1 and leaf.shape[0] == n, (leaf.shape, n)
        trail = leaf.shape[1:]
        lanes.append(leaf.reshape(n, -1) if trail else leaf)
        trails.append(trail)

    def rebuild(outs, m: int):
        return jax.tree.unflatten(
            treedef, [o.reshape((m,) + t) for o, t in zip(outs, trails)])

    return lanes, rebuild


def _ext(x: jnp.ndarray) -> jnp.ndarray:
    """Append one zero pad slot so gather maps have a safe sentinel row."""
    return jnp.concatenate([x, jnp.zeros((1,) + x.shape[1:], x.dtype)], 0)


def _take(ext: jnp.ndarray, gmap: np.ndarray) -> jnp.ndarray:
    return ext[jnp.asarray(gmap)]


def _row_runs(offs, cls: SizeClass) -> Optional[List[Tuple[int, int]]]:
    """``[(start, rows), ...]``: the class's rows as maximal runs that lie
    back to back in the flat input, or None when any row is padded."""
    w = cls.width
    if any(ln != w for ln in cls.lens):
        return None
    runs: List[Tuple[int, int]] = []
    for sid in cls.seg_ids:
        o = int(offs[sid])
        if runs and runs[-1][0] + runs[-1][1] * w == o:
            runs[-1] = (runs[-1][0], runs[-1][1] + 1)
        else:
            runs.append((o, 1))
    return runs


def _class_rows(x: jnp.ndarray, offs, cls: SizeClass, n: int) -> jnp.ndarray:
    """The class's dense ``(cls.n, width[, F])`` tile of flat ``x``.

    Unpadded rows are static slices of ``x``, one per run of back-to-back
    rows, so full rows laid out contiguously (every vocab sampling call)
    are a reshape, not an element gather. A padded class gathers from
    ``x`` plus its pad slot through :func:`gather_map`."""
    runs = _row_runs(offs, cls)
    if runs is None:
        return _take(_ext(x), gather_map(offs, cls, n))
    w, trail = cls.width, x.shape[1:]
    parts = [lax.slice_in_dim(x, o, o + r * w).reshape((r, w) + trail)
             for o, r in runs]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, 0)


def _scatter(out: jnp.ndarray, smap: np.ndarray, dense: jnp.ndarray):
    """Write the class tile into the flat output; trash lanes collide on
    the last slot, which the caller slices away."""
    idx = jnp.asarray(smap).reshape(-1)
    flat = dense.reshape((-1,) + dense.shape[2:])
    return out.at[idx].set(flat)


def _take_perm(dense_lane: jnp.ndarray, perm: jnp.ndarray) -> jnp.ndarray:
    """``take_along_axis`` over a dense ``(S, L[, F])`` lane with the
    permutation broadcast across trailing feature dims (the XLA-level
    sibling of the in-kernel ``gather_lanes``)."""
    idx = perm
    if dense_lane.ndim > idx.ndim:
        idx = idx.reshape(idx.shape + (1,) * (dense_lane.ndim - idx.ndim))
    return jnp.take_along_axis(dense_lane, idx, axis=1)


def _lens_col(cls: SizeClass) -> jnp.ndarray:
    return jnp.asarray(np.asarray(cls.lens, np.int32)[:, None])


def _keys_for(x: jnp.ndarray, nan_policy: str, descending: bool):
    """XLA-level key build for the spill paths (mirrors the in-kernel
    transform): total-order encode for floats under ``"last"``, exact
    bit-flip for descending. Returns (keys, undo) with ``undo`` mapping
    sorted keys back to values (monotone, bijective)."""
    encode = nan_policy == "last" and key_transformable(x.dtype)
    keys = encode_key_values(x) if encode else x
    if descending:
        keys = flip_keys(keys)

    def undo(k):
        v = flip_keys(k) if descending else k
        if encode:
            from repro.kernels.common import decode_key_values

            v = decode_key_values(v, x.dtype)
        return v

    return keys, undo


def _use_mxu(plan, encode: bool, dtype) -> bool:
    # encoded keys are ints: exact scatter permute only; the raw-float
    # unsafe path may ride the one-hot MXU device
    return bool(plan.use_mxu and not encode
                and jnp.issubdtype(jnp.dtype(dtype), jnp.floating))


# ---------------------------------------------------------------------------
# segment_sort
# ---------------------------------------------------------------------------


def _spill_sort_values(dense: jnp.ndarray, *, descending: bool,
                       nan_policy: str, tile: int, interpret) -> jnp.ndarray:
    """Values-only sort of equal-length long rows: chunk-sort every tile in
    one class launch, then reduce each row's sorted runs with the
    grid-resident FLiMS carry merge (one read/write per element)."""
    from repro.resilience.failpoints import failpoint
    from repro.streaming.grid_merge import grid_chunked_merge2

    failpoint("segmented.spill.values")
    s, ln = dense.shape
    keys, undo = _keys_for(dense, nan_policy, descending)
    c = -(-ln // tile)
    pad = c * tile - ln
    if pad:
        keys = jnp.pad(keys, [(0, 0), (0, pad)],
                       constant_values=np_fill(
                           key_sentinel(keys.dtype), keys.dtype))
    chunks = keys.reshape(s * c, tile)
    lens = jnp.full((s * c, 1), tile, jnp.int32)
    chunk_plan = _class_plan((tile,), s * c, keys.dtype)
    sorted_chunks, _, _ = segment_class_sort_pallas(
        chunks, lens, (), encode=False, flip=False, want_perm=False,
        network=chunk_plan.network, block_batch=chunk_plan.block_batch,
        use_mxu=False, interpret=interpret,
    )
    runs: List[jnp.ndarray] = list(
        jnp.moveaxis(sorted_chunks.reshape(s, c, tile), 1, 0))
    while len(runs) > 1:
        nxt = []
        for i in range(0, len(runs) - 1, 2):
            nxt.append(grid_chunked_merge2(runs[i], runs[i + 1], tile=tile,
                                           use_mxu=False,
                                           interpret=interpret))
        if len(runs) % 2:
            nxt.append(runs[-1])
        runs = nxt
    return undo(runs[0][:, :ln])


def _spill_sort_perm(dense: jnp.ndarray, *, descending: bool,
                     nan_policy: str):
    """Permutation-carrying spill rows: batched XLA stable argsort of the
    total-order keys (documented non-kernel path)."""
    from repro.resilience.failpoints import failpoint

    failpoint("segmented.spill.perm")
    keys, _ = _keys_for(dense, nan_policy, descending)
    order = jnp.argsort(keys, axis=-1, stable=True).astype(jnp.int32)
    return jnp.take_along_axis(dense, order, axis=-1), order


def segment_sort_impl(
    values: jnp.ndarray,
    offsets,
    *,
    descending: bool = False,
    payload=None,
    nan_policy: str = "last",
    use_kernel: bool = True,
    want_perm: bool = False,
    interpret: Optional[bool] = None,
):
    """Sort each CSR segment independently. Returns
    ``(values, perm | None, payload_tree | None)``."""
    offs = normalize_offsets(offsets)
    n = offs[-1]
    values = jnp.asarray(values)
    assert values.ndim == 1 and values.shape[0] == n, (values.shape, n)
    lanes, rebuild = ([], None)
    if payload is not None:
        lanes, rebuild = _flatten_leaves(payload, n)
    need_perm = want_perm or payload is not None

    if not use_kernel:
        out, perm, pouts = ref_segment_sort(
            values, offs, descending=descending, nan_policy=nan_policy,
            payload_lanes=lanes, want_perm=need_perm)
        ptree = None if payload is None else rebuild(pouts, n)
        return out, (perm if want_perm else None), ptree

    lengths = segment_lengths(offs)
    mw = max_class_width(values.dtype)
    classes, spill = bucket_segments(lengths, mw)
    _record_bucketing("segment_sort", classes, spill, offs)
    encode = nan_policy == "last" and key_transformable(values.dtype)
    out_v = jnp.zeros((n + 1,), values.dtype)
    out_p = jnp.zeros((n + 1,), jnp.int32) if need_perm else None
    out_l = [jnp.zeros((n + 1,) + l.shape[1:], l.dtype) for l in lanes]

    for cls in classes:
        dense = _class_rows(values, offs, cls, n)
        p_dense = [_class_rows(l, offs, cls, n) for l in lanes]
        if cls.width == 1:
            # singleton class: nothing to sort, no network, no launch
            res_v, res_l = dense, p_dense
            res_perm = jnp.zeros((cls.n, 1), jnp.int32)
        else:
            plan = _class_plan((cls.width,), cls.n, values.dtype)
            res_v, res_perm, res_l = segment_class_sort_pallas(
                dense, _lens_col(cls), tuple(p_dense), encode=encode,
                flip=descending, want_perm=need_perm,
                network=plan.network, block_batch=plan.block_batch,
                use_mxu=_use_mxu(plan, encode, values.dtype),
                interpret=interpret,
            )
        smap = scatter_map(offs, cls, cls.width)
        out_v = _scatter(out_v, smap, res_v)
        if need_perm:
            out_p = _scatter(out_p, smap, res_perm)
        out_l = [_scatter(o, smap, r) for o, r in zip(out_l, res_l)]

    for cls in spill:  # equal exact-length groups past the class budget
        dense = _class_rows(values, offs, cls, n)
        smap = scatter_map(offs, cls, cls.width)
        if need_perm:
            res_v, res_perm = _spill_sort_perm(
                dense, descending=descending, nan_policy=nan_policy)
            out_p = _scatter(out_p, smap, res_perm)
            for o_i, l in enumerate(lanes):
                out_l[o_i] = _scatter(
                    out_l[o_i], smap,
                    _take_perm(_class_rows(l, offs, cls, n), res_perm))
        else:
            res_v = _spill_sort_values(
                dense, descending=descending, nan_policy=nan_policy,
                tile=min(512, mw), interpret=interpret)
        out_v = _scatter(out_v, smap, res_v)

    ptree = None if payload is None else rebuild([o[:n] for o in out_l], n)
    return out_v[:n], (out_p[:n] if want_perm else None), ptree


# ---------------------------------------------------------------------------
# segment_merge
# ---------------------------------------------------------------------------


def segment_merge_impl(
    a: jnp.ndarray,
    b: jnp.ndarray,
    offsets_a,
    offsets_b,
    *,
    descending: bool = False,
    payload=None,  # (tree_a, tree_b) riding the merge permutation
    nan_policy: str = "last",
    use_kernel: bool = True,
    want_perm: bool = False,
    interpret: Optional[bool] = None,
):
    """Merge per-segment sorted runs ``a[s]`` and ``b[s]``. Returns
    ``(values, perm | None, payload_tree | None, out_offsets)`` — the
    output CSR segment ``s`` is the sorted union of the two runs, and
    ``perm`` holds concatenated-segment positions (a first, then b)."""
    offs_a = normalize_offsets(offsets_a)
    offs_b = normalize_offsets(offsets_b)
    assert len(offs_a) == len(offs_b), (len(offs_a), len(offs_b))
    na, nb = offs_a[-1], offs_b[-1]
    a = jnp.asarray(a)
    b = jnp.asarray(b)
    assert a.shape == (na,) and b.shape == (nb,), (a.shape, b.shape, na, nb)
    out_offs = tuple(x + y for x, y in zip(offs_a, offs_b))
    total = na + nb

    lanes, rebuild = ([], None)
    if payload is not None:
        # per-list payload trees concatenate per segment into the merged
        # CSR layout the permutation indexes
        tree_a, tree_b = payload
        la, rebuild = _flatten_leaves(tree_a, na)
        lb, _ = _flatten_leaves(tree_b, nb)
        lanes = [_cat_csr(pa, pb, offs_a, offs_b) for pa, pb in zip(la, lb)]
    need_perm = want_perm or payload is not None

    if not use_kernel:
        out, perm, pouts, _ = ref_segment_merge(
            a, b, offs_a, offs_b, descending=descending,
            nan_policy=nan_policy, payload_lanes=lanes, want_perm=need_perm)
        ptree = None if payload is None else rebuild(pouts, total)
        return out, (perm if want_perm else None), ptree, out_offs

    lens_a = segment_lengths(offs_a)
    lens_b = segment_lengths(offs_b)
    mw = max_class_width(a.dtype)
    classes, spill = bucket_merge_pairs(lens_a, lens_b, mw)
    _record_bucketing("segment_merge", classes, spill, (offs_a, offs_b))
    encode = nan_policy == "last" and key_transformable(a.dtype)
    lext = [_ext(l) for l in lanes]
    out_v = jnp.zeros((total + 1,), a.dtype)
    out_p = jnp.zeros((total + 1,), jnp.int32) if need_perm else None
    out_l = [jnp.zeros((total + 1,) + l.shape[1:], l.dtype) for l in lanes]

    def lane_gmap(ca: SizeClass, cb: SizeClass) -> np.ndarray:
        """Dense-coordinate gather map for the merged-CSR payload lanes:
        a lanes fill [0, Wa), b lanes [Wa, Wa+Wb)."""
        ga = np.full((ca.n, ca.width), total, np.int32)
        gb = np.full((cb.n, cb.width), total, np.int32)
        lane = np.arange(max(ca.width, cb.width))
        for r, sid in enumerate(ca.seg_ids):
            o0 = out_offs[sid]
            ga[r, :ca.lens[r]] = o0 + lane[:ca.lens[r]]
            gb[r, :cb.lens[r]] = o0 + ca.lens[r] + lane[:cb.lens[r]]
        return np.concatenate([ga, gb], axis=1)

    for ca, cb in classes:
        dense_a = _class_rows(a, offs_a, ca, na)
        dense_b = _class_rows(b, offs_b, cb, nb)
        p_dense = [_take(lx, lane_gmap(ca, cb)) for lx in lext]
        plan = _class_plan((ca.width, cb.width), ca.n, a.dtype)
        res_v, res_perm, res_l = segment_class_merge_pallas(
            dense_a, dense_b, _lens_col(ca), _lens_col(cb), tuple(p_dense),
            encode=encode, flip=descending, want_perm=need_perm,
            network=plan.network, block_batch=plan.block_batch,
            use_mxu=_use_mxu(plan, encode, a.dtype),
            n_cols=plan.n_cols if plan.network == "loms" else None,
            interpret=interpret,
        )
        out_cls = SizeClass(width=ca.width + cb.width, seg_ids=ca.seg_ids,
                            lens=tuple(x + y for x, y in
                                       zip(ca.lens, cb.lens)))
        smap = scatter_map(out_offs, out_cls, out_cls.width)
        out_v = _scatter(out_v, smap, res_v)
        if need_perm:
            out_p = _scatter(out_p, smap, res_perm)
        out_l = [_scatter(o, smap, r) for o, r in zip(out_l, res_l)]

    for ca, cb in spill:  # exact-length groups past the class budget
        dense_a = _class_rows(a, offs_a, ca, na)
        dense_b = _class_rows(b, offs_b, cb, nb)
        ln = ca.width + cb.width
        out_cls = SizeClass(width=ln, seg_ids=ca.seg_ids,
                            lens=(ln,) * ca.n)
        smap = scatter_map(out_offs, out_cls, ln)
        if need_perm:
            cat = jnp.concatenate([dense_a, dense_b], axis=1)
            res_v, res_perm = _spill_sort_perm(
                cat, descending=descending, nan_policy=nan_policy)
            out_p = _scatter(out_p, smap, res_perm)
            for o_i, lx in enumerate(lext):
                out_l[o_i] = _scatter(
                    out_l[o_i], smap,
                    _take_perm(_take(lx, lane_gmap(ca, cb)), res_perm))
        else:
            from repro.streaming.grid_merge import grid_chunked_merge2
            from repro.streaming.planner import plan_chunked

            ka, undo = _keys_for(dense_a, nan_policy, descending)
            kb, _ = _keys_for(dense_b, nan_policy, descending)
            tile = plan_chunked(ca.width, cb.width, batch=ca.n,
                                dtype=ka.dtype).tile
            res_v = undo(grid_chunked_merge2(ka, kb, tile=tile,
                                             use_mxu=False,
                                             interpret=interpret))
        out_v = _scatter(out_v, smap, res_v)

    ptree = None if payload is None else rebuild([o[:total] for o in out_l],
                                                 total)
    return out_v[:total], (out_p[:total] if want_perm else None), ptree, out_offs


def _cat_csr(lane_a: jnp.ndarray, lane_b: jnp.ndarray,
             offs_a: Tuple[int, ...], offs_b: Tuple[int, ...]) -> jnp.ndarray:
    """Interleave two CSR lanes into the merged layout (per segment: a's
    entries then b's) with one static gather."""
    na, nb = offs_a[-1], offs_b[-1]
    idx = np.empty(na + nb, np.int64)
    pos = 0
    for s in range(len(offs_a) - 1):
        la = offs_a[s + 1] - offs_a[s]
        lb = offs_b[s + 1] - offs_b[s]
        idx[pos:pos + la] = np.arange(offs_a[s], offs_a[s + 1])
        idx[pos + la:pos + la + lb] = na + np.arange(offs_b[s], offs_b[s + 1])
        pos += la + lb
    cat = jnp.concatenate([lane_a, lane_b], axis=0)
    return cat[jnp.asarray(idx)]


# ---------------------------------------------------------------------------
# segment_topk / segment_argmax
# ---------------------------------------------------------------------------


def _normalize_ks(k, n_segs: int) -> Tuple[int, ...]:
    if isinstance(k, (int, np.integer)):
        ks = (int(k),) * n_segs
    else:
        ks = tuple(int(x) for x in k)
        assert len(ks) == n_segs, (len(ks), n_segs)
    assert all(x >= 0 for x in ks), ks
    return ks


def segment_topk_impl(
    values: jnp.ndarray,
    offsets,
    k,
    *,
    descending: bool = True,
    payload=None,
    nan_policy: str = "last",
    use_kernel: bool = True,
    interpret: Optional[bool] = None,
):
    """Per-segment top-k (largest first by default; ``descending=False``
    selects the smallest ascending). ``k`` may be one int or one per
    segment — a size-class bucket runs **one** launch with the class's
    max k and each segment keeps its own prefix. Returns
    ``(values, idx, payload_tree | None, out_offsets)`` in CSR layout
    with ``min(k_s, len_s)`` entries per segment; ``idx`` holds
    within-segment input positions."""
    offs = normalize_offsets(offsets)
    n = offs[-1]
    values = jnp.asarray(values)
    assert values.ndim == 1 and values.shape[0] == n, (values.shape, n)
    lengths = segment_lengths(offs)
    ks = _normalize_ks(k, len(offs) - 1)
    counts = [min(k_s, int(ln)) for k_s, ln in zip(ks, lengths)]
    # int dtype even with zero segments (np.cumsum([]) is float64)
    out_offs = tuple(np.concatenate(
        [[0], np.cumsum(counts, dtype=np.int64)]).astype(int).tolist())
    total = out_offs[-1]

    lanes, rebuild = ([], None)
    if payload is not None:
        lanes, rebuild = _flatten_leaves(payload, n)

    if not use_kernel:
        out, idx, pouts, ref_offs = ref_segment_topk(
            values, offs, ks, descending=descending, nan_policy=nan_policy,
            payload_lanes=lanes)
        assert ref_offs == out_offs
        ptree = None if payload is None else rebuild(pouts, total)
        return out, idx, ptree, out_offs

    mw = max_class_width(values.dtype)
    classes, spill = bucket_segments(lengths, mw)
    _record_bucketing("segment_topk", classes, spill, offs)
    encode = nan_policy == "last" and key_transformable(values.dtype)
    out_v = jnp.zeros((total + 1,), values.dtype)
    out_i = jnp.zeros((total + 1,), jnp.int32)
    out_l = [jnp.zeros((total + 1,) + l.shape[1:], l.dtype) for l in lanes]

    def cls_counts(cls: SizeClass):
        return [counts[sid] for sid in cls.seg_ids]

    for cls in classes:
        cnts = cls_counts(cls)
        k_out = max(max(cnts), 1)
        dense = _class_rows(values, offs, cls, n)
        p_dense = [_class_rows(l, offs, cls, n) for l in lanes]
        if cls.width == 1:
            res_v = dense[:, :1]
            res_perm = jnp.zeros((cls.n, 1), jnp.int32)
            res_l = [p[:, :1] for p in p_dense]
        else:
            plan = _class_plan((cls.width,), cls.n, values.dtype)
            res_v, res_perm, res_l = segment_class_sort_pallas(
                dense, _lens_col(cls), tuple(p_dense), k_out=k_out,
                encode=encode, flip=descending, want_perm=True,
                network=plan.network, block_batch=plan.block_batch,
                use_mxu=_use_mxu(plan, encode, values.dtype),
                interpret=interpret,
            )
        smap = scatter_map(out_offs, cls, k_out, counts=cnts, trash=total)
        out_v = _scatter(out_v, smap, res_v)
        out_i = _scatter(out_i, smap, res_perm)
        out_l = [_scatter(o, smap, r) for o, r in zip(out_l, res_l)]

    for cls in spill:  # equal-length vocab-scale rows: batched unified topk
        from repro.resilience.failpoints import failpoint

        failpoint("segmented.spill.topk")
        cnts = cls_counts(cls)
        k_out = max(max(cnts), 1)
        dense = _class_rows(values, offs, cls, n)
        if descending:
            from repro.api.ops import topk as unified_topk

            # stable=True upholds the segment_topk contract that idx are
            # genuine within-segment positions: the dense topk's -1
            # pad-aliasing sentinel (a real value tying the dtype minimum,
            # e.g. masked -inf logits) orders after every real index under
            # stable ties, and k_out <= len means real candidates always
            # fill the prefix — so -1 can never surface here
            res_v, res_perm = unified_topk(dense, k_out, stable=True,
                                           nan_policy=nan_policy)
        else:
            keys, _ = _keys_for(dense, nan_policy, False)
            order = jnp.argsort(keys, axis=-1,
                                stable=True)[:, :k_out].astype(jnp.int32)
            res_v = jnp.take_along_axis(dense, order, axis=1)
            res_perm = order
        smap = scatter_map(out_offs, cls, k_out, counts=cnts, trash=total)
        out_v = _scatter(out_v, smap, res_v)
        out_i = _scatter(out_i, smap, res_perm)
        for o_i, l in enumerate(lanes):
            out_l[o_i] = _scatter(
                out_l[o_i], smap,
                _take_perm(_class_rows(l, offs, cls, n), res_perm))

    ptree = None if payload is None else rebuild([o[:total] for o in out_l],
                                                 total)
    return out_v[:total], out_i[:total], ptree, out_offs


def segment_argmax_impl(
    values: jnp.ndarray,
    offsets,
    *,
    nan_policy: str = "last",
    use_kernel: bool = True,
    interpret: Optional[bool] = None,
):
    """Per-segment argmax: ``(vals (S,), idx (S,))``; an empty segment
    yields the dtype minimum and index ``-1``."""
    offs = normalize_offsets(offsets)
    n_segs = len(offs) - 1
    vals, idx, _, out_offs = segment_topk_impl(
        values, offs, 1, descending=True, nan_policy=nan_policy,
        use_kernel=use_kernel, interpret=interpret)
    has = np.diff(np.asarray(out_offs)) > 0  # static per-segment hit mask
    src = np.minimum(np.asarray(out_offs[:-1]), max(out_offs[-1] - 1, 0))
    gathered_v = vals[jnp.asarray(src)] if out_offs[-1] else jnp.zeros(
        (n_segs,), values.dtype)
    gathered_i = idx[jnp.asarray(src)] if out_offs[-1] else jnp.zeros(
        (n_segs,), jnp.int32)
    fill_v = np_fill(sentinel_min(values.dtype), values.dtype)
    has_j = jnp.asarray(has)
    out_v = jnp.where(has_j, gathered_v, fill_v)
    out_i = jnp.where(has_j, gathered_i, jnp.int32(-1))
    return out_v, out_i
