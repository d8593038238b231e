"""Device-tree sharded top-k: log-depth LOMS merge reduction over a mesh axis.

Serving-scale decode needs top-k over a vocab that is sharded across the
tensor-parallel axis. Each device computes a local blockwise LOMS top-k of
its vocab slice (global indices restored from the shard offset), then the
per-shard (value, index) candidate lists reduce across the axis through a
log-depth tree of truncated UP-k/DN-k merges — the paper's 2-stage merge
device reading only its upper rows, exactly as in ``kernels/topk.py`` but
with the tree edges mapped onto inter-device links:

* power-of-two axis: a butterfly exchange (``lax.ppermute`` partners at
  XOR distance 1, 2, 4, ...) — k values per link per step, every shard
  finishes with the replicated global top-k;
* any other axis size: one ``lax.all_gather`` of the k-candidate lists
  followed by the same log-depth merge tree computed redundantly per shard.

Everything inside the ``shard_map`` body is plain XLA: the same
comparison clouds and tie rules the Pallas kernels use, with an XLA
scatter as the permute, so it traces under manual sharding on any backend
and moves values exactly.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels.common import sentinel_min


def _permute(rank, *arrays):
    """``out[..., rank[i]] = a[..., i]`` for each array: an XLA scatter,
    exact for every dtype (this module never runs inside a kernel)."""
    return tuple(jnp.put_along_axis(jnp.zeros_like(a), rank, a, axis=-1,
                                    inplace=False) for a in arrays)


def _iota(n: int):
    return jnp.arange(n, dtype=jnp.int32)


def _sort_desc(x, idx):
    """Descending N-sorter on the last axis, ties by the higher position
    first — the order of the Pallas kernels (the reverse of a stable
    ascending sort)."""
    n = x.shape[-1]
    q, p = _iota(n)[:, None], _iota(n)[None, :]
    xq, xp = x[..., :, None], x[..., None, :]
    rank = ((xq > xp) | ((xq == xp) & (q > p))).sum(-2).astype(jnp.int32)
    return _permute(rank, x, idx)


def _merge_desc(av, ai, bv, bi, keep: int):
    """Merge two descending (value, index) lists, keep the top ``keep``;
    ``b`` wins ties (the kernels' truncated UP-k/DN-k merge)."""
    m, n = av.shape[-1], bv.shape[-1]
    ra = _iota(m) + (bv[..., None, :] >= av[..., :, None]).sum(-1)
    rb = _iota(n) + (av[..., :, None] > bv[..., None, :]).sum(-2)
    rank = jnp.concatenate([ra, rb], axis=-1).astype(jnp.int32)
    mv, mi = _permute(rank, jnp.concatenate([av, bv], axis=-1),
                      jnp.concatenate([ai, bi], axis=-1))
    return mv[..., :keep], mi[..., :keep]


def _tree_reduce_desc(vs, is_, k: int):
    """Reduce a (..., S, k) stack of descending lists to (..., k)."""
    neg = sentinel_min(vs.dtype)
    while vs.shape[-2] > 1:
        if vs.shape[-2] % 2:
            pad = [(0, 0)] * (vs.ndim - 2) + [(0, 1), (0, 0)]
            vs = jnp.pad(vs, pad, constant_values=neg)
            is_ = jnp.pad(is_, pad, constant_values=-1)  # never alias slot 0
        vs, is_ = _merge_desc(vs[..., 0::2, :], is_[..., 0::2, :],
                              vs[..., 1::2, :], is_[..., 1::2, :], k)
    return vs[..., 0, :], is_[..., 0, :]


def local_topk_desc(
    x: jnp.ndarray,
    k: int,
    *,
    block: int = 128,
    offset=0,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Blockwise descending top-k of (B, E) with global indices ``+offset``.

    The in-kernel algorithm of ``router_topk_pallas`` as plain jnp: N-sorter
    per block, then a log-depth tree of truncated LOMS merges. Safe inside
    shard_map/vmap (no pallas_call)."""
    bsz, e = x.shape
    neg = sentinel_min(x.dtype)
    nblk = -(-e // block)
    ep = nblk * block
    if ep != e:
        x = jnp.pad(x, [(0, 0), (0, ep - e)], constant_values=neg)
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    idx = jnp.where(lane < e, lane + jnp.asarray(offset, jnp.int32), -1)
    xb = x.reshape(bsz, nblk, block)
    ib = idx.reshape(bsz, nblk, block)
    vs, is_ = _sort_desc(xb, ib)
    kk = min(k, block)
    vs, is_ = _tree_reduce_desc(vs[..., :kk], is_[..., :kk], k)
    if vs.shape[-1] < k:  # degenerate: fewer candidates than k on this shard
        pad = [(0, 0)] * (vs.ndim - 1) + [(0, k - vs.shape[-1])]
        vs = jnp.pad(vs, pad, constant_values=neg)
        is_ = jnp.pad(is_, pad, constant_values=-1)
    return vs, is_


def _butterfly_topk(vals, idxs, k: int, axis: str, size: int):
    """XOR-partner butterfly: after log2(size) exchange+merge steps every
    shard holds the identical global top-k."""
    step = 1
    while step < size:
        perm = [(i, i ^ step) for i in range(size)]
        ov = jax.lax.ppermute(vals, axis, perm)
        oi = jax.lax.ppermute(idxs, axis, perm)
        vals, idxs = _merge_desc(vals, idxs, ov, oi, k)
        step *= 2
    return vals, idxs


def tree_topk(
    x: jnp.ndarray,
    k: int,
    *,
    mesh: Optional[Mesh] = None,
    axis: Optional[str] = None,
    block: int = 128,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Descending top-k (values, int32 indices) over the last axis of (B, E).

    With ``mesh``/``axis`` given and the axis larger than 1, E is treated as
    sharded over that axis and reduced by the device-tree; otherwise this is
    the single-device log-tree (same merge network, local edges)."""
    assert x.ndim == 2, x.shape
    bsz, e = x.shape
    if mesh is None or axis is None or mesh.shape[axis] == 1:
        vs, is_ = local_topk_desc(x, k, block=block)
        return vs, is_
    size = int(mesh.shape[axis])
    assert e % size == 0, (e, size)
    shard = e // size
    pow2 = size & (size - 1) == 0

    def body(xs):  # xs: (B, E/size) local shard
        me = jax.lax.axis_index(axis)
        off = (me * shard).astype(jnp.int32)
        vs, is_ = local_topk_desc(xs, k, block=min(block, shard), offset=off)
        if pow2:
            return _butterfly_topk(vs, is_, k, axis, size)
        allv = jax.lax.all_gather(vs, axis, axis=1)  # (B, S, k)
        alli = jax.lax.all_gather(is_, axis, axis=1)
        return _tree_reduce_desc(allv, alli, k)


    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=P(None, axis),
        out_specs=(P(None, None), P(None, None)),
        check_vma=False,
    )
    return fn(x)


def tree_topk_for(par, x: jnp.ndarray, k: int, **kw):
    """Top-k routed by a :class:`repro.parallel.sharding.Parallelism`: the
    device-tree over the TP axis when the vocab divides it, else local."""
    from repro.parallel.sharding import vocab_topk_axis

    axis = vocab_topk_axis(par, x.shape[-1]) if par is not None else None
    if axis is None:
        return tree_topk(x, k, **kw)
    return tree_topk(x, k, mesh=par.mesh, axis=axis, **kw)
