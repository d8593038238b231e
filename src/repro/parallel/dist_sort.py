"""Distributed sample-sort: ``repro.sort`` / ``repro.merge_k`` over a mesh axis.

The paper's central claim is that LOMS devices merge *any mixture of input
list sizes* in a fixed small number of stages — exactly the primitive a
multi-device sort needs after an all-to-all partition. This module builds
PSRS (parallel sorting by regular sampling) out of LOMS devices, under
``shard_map`` over one mesh axis of ``P`` devices:

1. **local sort** — each device runs the LOMS merge-tree schedule on its
   contiguous slice of the input (for ``merge_k``: a k-way LOMS merge of
   its slices of the pre-sorted input lists — a contiguous slice of a
   sorted list is itself sorted, so the merge devices apply directly);
2. **splitters** — P regular samples per device, all-gathered and sorted
   (a P²-input LOMS sort computed replicated), every P-th picked as one of
   the P-1 splitters;
3. **partition** — per-row bucket boundaries by binary search over the
   sorted local run (``side='right'``: a global equal-value class never
   straddles a bucket), one ``lax.all_to_all`` moving bucket ``j`` to
   device ``j`` as capacity-padded blocks with explicit per-block valid
   counts riding along;
4. **merge** — each device k-way merges the P received runs: the LOMS
   k-way device while the comparison cloud fits the VMEM budget, the
   streaming ``chunked_merge_k`` pipeline (FLiMS refill rule) past it,
   and a log-depth tree of binary-search rank-merges for payload-carrying
   oversized partitions;
5. **rebalance** — bucket sizes are data-dependent, so a second
   ``all_to_all`` redistributes by *global rank* back onto the even output
   sharding. Validity masks are derived from the all-gathered bucket
   sizes, never from sentinel values.

Exactness: the partition capacity is the full local length, so no bucket
can overflow regardless of splitter quality (splitters only affect load
balance, never correctness), and sentinel-padded slots are tracked by
masks / ``-1`` positions end to end — a genuine dtype-max value ties the
pad but is never displaced by it (:func:`~repro.kernels.common.stable_compact`
resolves such ties by validity). The result is bit-identical to the
single-device backends for any input, including the int32 position
payload the unified API threads for ``stable=`` / ``payload=`` calls.
Float inputs arrive from the ops layer as total-order integer keys
(:mod:`repro.api.keys`), so the splitter searches never see NaN/±inf.

The data-dependent scatter/gather of phases 3 and 5 means the *schedule*
of the distributed path is not oblivious (unlike everything below it);
the per-device compute — every compare-exchange — still is.
"""
from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.kernels.common import np_fill, sentinel_max, stable_compact
from repro.obs import metrics as obs_metrics

#: below this total length the partition + two exchanges dominate the
#: device-parallel merge win; plan() keeps single-device backends.
DIST_MIN_TOTAL = 8192


# ---------------------------------------------------------------------------
# per-device building blocks (plain jnp; run inside the shard_map body)
# ---------------------------------------------------------------------------


def _fits_kway_budget(total: int) -> bool:
    from repro.streaming.planner import kway_fits_vmem

    return kway_fits_vmem(total)


def _merge2_ranked(
    av: jnp.ndarray, ap: Optional[jnp.ndarray],
    bv: jnp.ndarray, bp: Optional[jnp.ndarray],
) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """Stable 2-run merge by binary-search ranks (first run wins ties).

    O(n log n) with no comparator cloud — the payload-carrying analog of
    the streaming fallback for runs far past the VMEM budget."""
    m, n = av.shape[-1], bv.shape[-1]
    ra = jnp.arange(m, dtype=jnp.int32) + jax.vmap(
        lambda hay, q: jnp.searchsorted(hay, q, side="left"))(bv, av).astype(jnp.int32)
    rb = jnp.arange(n, dtype=jnp.int32) + jax.vmap(
        lambda hay, q: jnp.searchsorted(hay, q, side="right"))(av, bv).astype(jnp.int32)
    vals = jnp.concatenate([av, bv], axis=-1)
    rank = jnp.concatenate([ra, rb], axis=-1)
    out_v = jnp.put_along_axis(jnp.zeros_like(vals), rank, vals, axis=-1,
                               inplace=False)
    if ap is None:
        return out_v, None
    pos = jnp.concatenate([ap, bp], axis=-1)
    out_p = jnp.put_along_axis(jnp.zeros_like(pos), rank, pos, axis=-1,
                               inplace=False)
    return out_v, out_p


def _merge_sorted_runs(
    runs: List[jnp.ndarray], pos_runs: Optional[List[jnp.ndarray]]
) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """k-way merge of sorted (B, n_i) runs with the VMEM-budget ladder.

    Inside the budget: a binary tree of 2-way LOMS devices (valid for any
    length mixture — the flat k-way setup array rejects some ragged
    mixes). Past it: the streaming ``chunked_merge_k`` pipeline on TPU
    (value-only), the log-depth binary-search rank-merge tree everywhere
    interpret mode would make the tiled kernels crawl, and always for
    payload-carrying runs (streaming cannot thread positions)."""
    if len(runs) == 1:
        return runs[0], (None if pos_runs is None else pos_runs[0])
    total = sum(r.shape[-1] for r in runs)
    if _fits_kway_budget(total):
        from repro.api import schedules

        if pos_runs is None:
            return schedules.merge_k(runs, kind="tree"), None
        return schedules.merge_k(runs, kind="tree", payload=pos_runs)
    if pos_runs is None and jax.default_backend() == "tpu":
        from repro.streaming import chunked_merge_k

        return chunked_merge_k(runs), None
    items = list(runs)
    pls = list(pos_runs) if pos_runs is not None else [None] * len(runs)
    while len(items) > 1:
        nxt, npl = [], []
        for i in range(0, len(items) - 1, 2):
            v, p = _merge2_ranked(items[i], pls[i], items[i + 1], pls[i + 1])
            nxt.append(v)
            npl.append(p)
        if len(items) % 2:
            nxt.append(items[-1])
            npl.append(pls[-1])
        items, pls = nxt, npl
    return items[0], pls[0]


def _splitters(xs: jnp.ndarray, axis_name: str, p: int) -> jnp.ndarray:
    """Regular-sampling splitters, replicated per device: (B, P-1)."""
    from repro.api import schedules

    n_local = xs.shape[-1]
    samp_idx = np.arange(p, dtype=np.int32) * n_local // p
    samp = xs[:, samp_idx]  # (B, P) regular samples of the sorted run
    gathered = jax.lax.all_gather(samp, axis_name, axis=1, tiled=True)
    ssort = schedules.sort(gathered)  # P^2-input LOMS sort, replicated
    return ssort[:, p - 1 :: p][:, : p - 1]


def _partition(
    xs: jnp.ndarray, ps: Optional[jnp.ndarray], split: jnp.ndarray, fill
):
    """Scatter each row of the sorted run into P capacity-C send blocks.

    Capacity is the full local length, so overflow is impossible; unused
    slots carry the +sentinel (runs stay sorted) and position ``-1``."""
    b, n_local = xs.shape
    p = split.shape[-1] + 1
    # first index > split_j: equal values all stay left of the boundary,
    # so an equal-value class lands in one bucket on every device
    sb = jax.vmap(lambda row, s: jnp.searchsorted(row, s, side="right"))(
        xs, split).astype(jnp.int32)
    bounds = jnp.concatenate(
        [jnp.zeros((b, 1), jnp.int32), sb, jnp.full((b, 1), n_local, jnp.int32)],
        axis=1)  # (B, P+1)
    lane = jnp.arange(n_local, dtype=jnp.int32)
    bucket = jax.vmap(lambda s: jnp.searchsorted(s, lane, side="right"))(
        sb).astype(jnp.int32)  # (B, n_local) destination bucket per element
    start = jnp.take_along_axis(bounds, bucket, axis=1)
    dest = bucket * n_local + (lane[None, :] - start)
    rows = jnp.arange(b, dtype=jnp.int32)[:, None]
    send = jnp.full((b, p * n_local), fill, xs.dtype).at[rows, dest].set(xs)
    cnt = bounds[:, 1:] - bounds[:, :-1]  # (B, P) per-bucket valid counts
    psend = None
    if ps is not None:
        psend = jnp.full((b, p * n_local), -1, jnp.int32).at[rows, dest].set(ps)
        psend = psend.reshape(b, p, n_local)
    return send.reshape(b, p, n_local), cnt, psend


def _a2a(x: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """Send split-axis slice j to device j; received slices stack there."""
    # per-device payload bytes of this exchange, recorded at trace time
    # (one count per compilation — the interconnect-traffic figure the
    # DIST_MIN_TOTAL cutover is meant to amortize)
    obs_metrics.counter("dist_sort.all_to_all_bytes").inc(
        int(np.prod(x.shape)) * jnp.dtype(x.dtype).itemsize)
    return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=1)


def _rebalance(
    vals: jnp.ndarray, pos: Optional[jnp.ndarray], v_count: jnp.ndarray,
    axis_name: str, p: int, n_local: int, fill,
):
    """Redistribute merged buckets by global rank onto even output shards.

    Element i of this device's bucket has global rank ``off_me + i``; it
    belongs to output device ``rank // n_local`` at offset
    ``rank % n_local``. Receive-side validity comes from the all-gathered
    bucket sizes — disjoint rank intervals that exactly tile the segment —
    never from comparing against sentinel values."""
    b, l = vals.shape
    me = jax.lax.axis_index(axis_name)
    v_all = jax.lax.all_gather(v_count, axis_name, axis=1,
                               tiled=False).astype(jnp.int32)  # (B, P)
    off = jnp.cumsum(v_all, axis=1) - v_all  # (B, P) bucket start ranks
    my_off = jnp.take(off, me, axis=1)  # (B,)
    lane = jnp.arange(l, dtype=jnp.int32)
    rank = my_off[:, None] + lane[None, :]
    valid = lane[None, :] < v_count[:, None]
    dest = jnp.clip(rank // n_local, 0, p - 1) * n_local + rank % n_local
    slot = jnp.where(valid, dest, p * n_local)  # invalid -> trash slot
    rows = jnp.arange(b, dtype=jnp.int32)[:, None]
    send = jnp.full((b, p * n_local + 1), fill, vals.dtype).at[rows, slot].set(vals)
    recv = _a2a(send[:, :-1].reshape(b, p, n_local), axis_name)
    precv = None
    if pos is not None:
        psend = jnp.full((b, p * n_local + 1), -1, jnp.int32).at[rows, slot].set(pos)
        precv = _a2a(psend[:, :-1].reshape(b, p, n_local), axis_name)
    out = jnp.full((b, n_local), fill, vals.dtype)
    pout = None if pos is None else jnp.full((b, n_local), -1, jnp.int32)
    q = jnp.arange(n_local, dtype=jnp.int32)[None, :]
    my_lo = me * n_local
    for i in range(p):  # P is static: unrolled masked selects
        lo = off[:, i][:, None] - my_lo
        hi = lo + v_all[:, i][:, None]
        m = (q >= lo) & (q < hi)
        out = jnp.where(m, recv[:, i, :], out)
        if pos is not None:
            pout = jnp.where(m, precv[:, i, :], pout)
    return out, pout


def _psrs_tail(
    xs: jnp.ndarray, ps: Optional[jnp.ndarray], *, axis_name: str, p: int, fill
):
    """Phases 2-5 on an already locally sorted (B, n_local) run."""
    n_local = xs.shape[-1]
    split = _splitters(xs, axis_name, p)
    send, cnt, psend = _partition(xs, ps, split, fill)
    recv = _a2a(send, axis_name)  # (B, P, C): run i from device i
    rcnt = _a2a(cnt, axis_name)  # (B, P): its valid length
    precv = None if psend is None else _a2a(psend, axis_name)
    runs = [recv[:, i, :] for i in range(p)]
    pruns = None if precv is None else [precv[:, i, :] for i in range(p)]
    merged, pmerged = _merge_sorted_runs(runs, pruns)
    if pmerged is not None:
        # pads tie genuine dtype-max values; validity (pos >= 0), not the
        # value, decides the live prefix
        merged, pmerged = stable_compact(pmerged >= 0, merged, pmerged)
    v_count = rcnt.sum(axis=1).astype(jnp.int32)
    return _rebalance(merged, pmerged, v_count, axis_name, p, n_local, fill)


# ---------------------------------------------------------------------------
# public entry points (full logical arrays in, full logical arrays out)
# ---------------------------------------------------------------------------
#
# The pipelines are jitted at module level with the mesh/axis as static
# arguments: the shard_map bodies are thousands of small compare-exchange
# ops, so eager per-device dispatch would dominate, and a per-call jax.jit
# wrapper would recompile on every invocation.


def _fill_for(dtype):
    return np_fill(sentinel_max(dtype), dtype)


@functools.partial(jax.jit, static_argnames=("mesh", "axis_name", "with_pos"))
def _sample_sort_jit(x, pos, *, mesh, axis_name, with_pos):
    from repro.api import schedules

    p = int(mesh.shape[axis_name])
    fill = _fill_for(x.dtype)
    spec = P(None, axis_name)

    if not with_pos:
        def body(xl):
            out, _ = _psrs_tail(schedules.sort(xl), None,
                                axis_name=axis_name, p=p, fill=fill)
            return out

        return jax.shard_map(body, mesh=mesh, in_specs=spec, out_specs=spec,
                             check_vma=False)(x)

    def body(xl, pl):
        xs, psl = schedules.sort(xl, payload=pl)
        return _psrs_tail(xs, psl, axis_name=axis_name, p=p, fill=fill)

    return jax.shard_map(body, mesh=mesh, in_specs=(spec, spec),
                         out_specs=(spec, spec), check_vma=False)(x, pos)


def sample_sort(
    x: jnp.ndarray, *, mesh, axis_name: str, pos: Optional[jnp.ndarray] = None
) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """Ascending sort of canonical (B, N) over ``mesh[axis_name]``.

    ``N`` must divide evenly over the axis (the planner only offers this
    backend when it does). ``pos`` is the int32 position payload of the
    registry convention; returns ``(sorted, pos_out | None)``."""
    p = int(mesh.shape[axis_name])
    n = x.shape[-1]
    assert n % p == 0 and n >= p, (n, p)
    if pos is None:
        out = _sample_sort_jit(x, jnp.zeros((), jnp.int32), mesh=mesh,
                               axis_name=axis_name, with_pos=False)
        return out, None
    return _sample_sort_jit(x, pos, mesh=mesh, axis_name=axis_name,
                            with_pos=True)


@functools.partial(jax.jit,
                   static_argnames=("mesh", "axis_name", "k", "with_pos"))
def _sample_merge_jit(*arrs, mesh, axis_name, k, with_pos):

    p = int(mesh.shape[axis_name])
    fill = _fill_for(arrs[0].dtype)
    spec = P(None, axis_name)

    if not with_pos:
        def body(*locs):
            merged, _ = _merge_sorted_runs(list(locs), None)
            out, _ = _psrs_tail(merged, None, axis_name=axis_name, p=p,
                                fill=fill)
            return out

        return jax.shard_map(body, mesh=mesh,
                             in_specs=tuple(spec for _ in arrs),
                             out_specs=spec, check_vma=False)(*arrs)

    def body(*args):
        merged, pmerged = _merge_sorted_runs(list(args[:k]), list(args[k:]))
        return _psrs_tail(merged, pmerged, axis_name=axis_name, p=p, fill=fill)

    return jax.shard_map(body, mesh=mesh, in_specs=tuple(spec for _ in arrs),
                         out_specs=(spec, spec), check_vma=False)(*arrs)


def sample_merge_k(
    lists: Sequence[jnp.ndarray], *, mesh, axis_name: str,
    pos: Optional[Sequence[jnp.ndarray]] = None,
) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """k-way merge of sorted (B, n_i) lists over ``mesh[axis_name]``.

    Each list shards evenly; a device's slice of a sorted list is sorted,
    so phase 1 is a local k-way LOMS merge instead of a full sort — the
    paper's merge-any-mixture primitive doing the work a sort would."""
    lists = list(lists)
    p = int(mesh.shape[axis_name])
    lens = [int(l.shape[-1]) for l in lists]
    assert all(ln % p == 0 and ln >= p for ln in lens), (lens, p)
    k = len(lists)
    if pos is None:
        out = _sample_merge_jit(*lists, mesh=mesh, axis_name=axis_name, k=k,
                                with_pos=False)
        return out, None
    return _sample_merge_jit(*lists, *list(pos), mesh=mesh,
                             axis_name=axis_name, k=k, with_pos=True)
