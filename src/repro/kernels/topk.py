"""Pallas TPU kernels: blockwise top-k via truncated LOMS merges.

This is the framework's hot sorting path (MoE router top-k over experts,
decode-time top-k over the vocab). Two kernels:

  * ``router_topk`` — E small (<= ~512): one kernel does local descending
    rank-sorts of E/bs blocks and the full LOMS merge tree in VMEM.
  * ``vocab_topk``  — E large (vocab ~152k): phase-1 kernel grids over
    (batch, vocab-block) producing per-block sorted top-k lists; then a
    log-depth sequence of phase-2 merge kernels, each merging pairs of
    sorted k-lists with a truncated UP-k/DN-k LOMS merge (top half kept —
    exactly the paper's 2-stage device, reading only the upper rows).

Values carry int32 payload indices throughout (compare on value, tie-break
on nothing — payloads ride the permutation). Sentinel slots — block padding
and odd-group merge pads — carry index -1, never an in-range position: a
pad ties with a real dtype-min element, and any non-negative index would
silently alias that element's slot (the repro.topk index contract).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .common import (
    _iota,
    decode_kernel_keys,
    encode_kernel_keys,
    for_row_slabs,
    group_window,
    pad_batch,
    permute_lanes,
    resolve_interpret,
    row_block_spec,
    row_slab,
    slab_lanes,
    run_rank,
    sentinel_min,
    tile_rows,
)

#: largest last-axis size the single-kernel router path handles; beyond it
#: the two-phase vocab kernel grids over (batch, vocab-block). The dispatch
#: layer (repro.api.dispatch) imports this so routing and realization agree.
ROUTER_TOPK_MAX = 512

_neg_inf = sentinel_min


def _block_topk(x, idx, block: int, kk: int, use_mxu: bool):
    """Descending top-``kk`` of every ``block``-lane block of (bt, W)
    lanes -> (bt, W/block * kk): block ``g``'s list lands at lanes
    [g*kk, (g+1)*kk). Ties rank the higher index first (the reverse of
    the stable ascending N-sorter)."""
    width = x.shape[-1]
    lane = _iota((1, width), 1)
    grp = lane // block
    rank = run_rank(x, grp, lane, 0, descending=True,
                    window=group_window(block, width))
    dest = jnp.where(rank < kk, grp * kk + rank, -1)
    return permute_lanes(dest, [x, idx], width // block * kk,
                         window=group_window(block, width, kk),
                         use_mxu=use_mxu)


def _merge_desc_pairs(v, i, kk: int, keep: int, use_mxu: bool):
    """Merge consecutive pairs of descending ``kk``-lists laid out along
    the lanes, keeping the top ``keep`` of each pair — the truncated
    UP-k/DN-k merge reading only its upper rows. The second list of a
    pair wins ties."""
    width = v.shape[-1]
    gw = 2 * kk
    lane = _iota((1, width), 1)
    t = lane % gw
    run = (t >= kk).astype(jnp.int32)
    grp = lane // gw
    rank = run_rank(v, grp, run, t - run * kk, descending=True,
                    window=group_window(gw, width))
    dest = jnp.where(rank < keep, grp * keep + rank, -1)
    return permute_lanes(dest, [v, i], width // gw * keep,
                         window=group_window(gw, width, keep),
                         use_mxu=use_mxu)


def _router_topk_kernel(x_ref, v_ref, i_ref, *, k, block, use_mxu, key_dtype):
    def body(x):
        if key_dtype is not None:  # fused nan_policy="last" encode on load
            x = encode_kernel_keys(x)
        bt, e = x.shape
        g = e // block
        kk = min(k, block)
        vs, is_ = _block_topk(x, _iota(x.shape, 1), block, kk, use_mxu)
        while g > 1:
            if g % 2:  # odd list count: one all-sentinel list, index -1
                vs = jnp.concatenate(
                    [vs, jnp.full((bt, kk), _neg_inf(vs.dtype), vs.dtype)], -1)
                is_ = jnp.concatenate(
                    [is_, jnp.full((bt, kk), -1, jnp.int32)], -1)
                g += 1
            keep = min(k, 2 * kk)
            vs, is_ = _merge_desc_pairs(vs, is_, kk, keep, use_mxu)
            g //= 2
            kk = keep
        vs = vs[:, :k]
        if key_dtype is not None:  # fused decode on store
            vs = decode_kernel_keys(vs, key_dtype)
        return vs, is_[:, :k]

    for_row_slabs([x_ref], [v_ref, i_ref], body,
                  row_slab(x_ref.shape[0], x_ref.shape[1]))


@functools.partial(jax.jit, static_argnames=(
    "k", "block", "block_batch", "use_mxu", "interpret", "key_dtype"))
def router_topk_pallas(
    x: jnp.ndarray, *, k: int, block: int = 32, block_batch: int = 8,
    use_mxu: bool = True, interpret: Optional[bool] = None,
    key_dtype: Optional[str] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Top-k over the last axis of (T, E) router logits; E % block == 0.
    ``interpret=None`` auto-resolves: compile on TPU, interpret elsewhere.
    ``key_dtype`` fuses the total-order float->int key transform into the
    kernel (encode on load, decode on store; pass ``use_mxu=False``).
    Ragged batches pad up to a ``block_batch`` multiple."""
    interpret = resolve_interpret(interpret)
    t, e = x.shape
    assert e % block == 0
    block_batch = tile_rows(block_batch, t)
    xp = slab_lanes([pad_batch(x, block_batch)], block_batch, e)[0]
    tp = xp.shape[0]
    v, i = pl.pallas_call(
        functools.partial(_router_topk_kernel, k=k, block=block,
                          use_mxu=use_mxu, key_dtype=key_dtype),
        grid=(tp // block_batch,),
        in_specs=[row_block_spec(block_batch, e)],
        out_specs=[row_block_spec(block_batch, k)] * 2,
        out_shape=[
            jax.ShapeDtypeStruct((tp, k), xp.dtype),
            jax.ShapeDtypeStruct((tp, k), jnp.int32),
        ],
        interpret=interpret,
    )(xp)
    return v[:t].astype(x.dtype), i[:t]


# ---------------------------------------------------------------------------
# large-axis (vocab) top-k: phase 1 block kernel + phase 2 merge-level kernel
# ---------------------------------------------------------------------------
# Lists live as (n_lists, B, k) arrays: a list's block is (B-tile, k), whose
# last two dims are the batch tile and the whole list — the TPU tiling
# accepts both for any k.


def _phase1_kernel(x_ref, v_ref, i_ref, *, k, block, v_real, use_mxu,
                   key_dtype, decode):
    j = pl.program_id(1)
    per = x_ref.shape[1] // block  # blocks in this lane tile

    def body(x):
        idx = _iota(x.shape, 1) + (j * x.shape[1]).astype(jnp.int32)
        if key_dtype is not None:  # fused nan_policy="last" encode on load
            x = encode_kernel_keys(x)
            # V-padding slots become the int-key -sentinel (below
            # key(-inf)), bit-identical to the unfused pipeline's padded
            # encoded array
            x = jnp.where(idx < v_real, x, _neg_inf(x.dtype))
        idx = jnp.where(idx < v_real, idx, -1)  # V-padding must not alias
        vs, is_ = _block_topk(x, idx, block, k, use_mxu)
        if decode:  # single-block vocab: this launch is also the last phase
            vs = decode_kernel_keys(vs, key_dtype)
        return vs, is_

    vs, is_ = body(x_ref[...])
    for b in range(per):
        v_ref[b] = vs[:, b * k:(b + 1) * k]
        i_ref[b] = is_[:, b * k:(b + 1) * k]


def _merge_level_kernel(v_ref, i_ref, vo_ref, io_ref, *, keep, use_mxu,
                        decode_dtype):
    v = jnp.concatenate([v_ref[0], v_ref[1]], axis=-1)  # two desc lists
    i = jnp.concatenate([i_ref[0], i_ref[1]], axis=-1)
    vo, io = _merge_desc_pairs(v, i, v_ref.shape[-1], keep, use_mxu)
    if decode_dtype is not None:  # last level: fused decode on store
        vo = decode_kernel_keys(vo, decode_dtype)
    vo_ref[...] = vo
    io_ref[...] = io


@functools.partial(jax.jit, static_argnames=(
    "k", "block", "block_batch", "use_mxu", "interpret", "key_dtype"))
def vocab_topk_pallas(
    x: jnp.ndarray, *, k: int, block: int = 128, block_batch: int = 8,
    use_mxu: bool = True, interpret: Optional[bool] = None,
    key_dtype: Optional[str] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Top-k over a large last axis (B, V). Pads V to a block multiple and
    the batch to a ``block_batch`` multiple. ``interpret=None``
    auto-resolves: compile on TPU, interpret elsewhere. ``key_dtype``
    fuses the total-order key transform into the phase kernels: phase 1
    encodes on load, the final merge level decodes on store — the
    intermediate k-lists stay int keys and never round-trip through an
    XLA encode/decode (pass ``use_mxu=False``). Phase 1 reads lane tiles
    of at least 128 (several blocks per tile when ``block`` < 128)."""
    interpret = resolve_interpret(interpret)
    bsz, v = x.shape
    nblk = -(-v // block)
    # pad to power-of-two block count for a regular merge tree
    nblk = 1 << (nblk - 1).bit_length()
    per = max(1, 128 // block) if 128 % block == 0 else 1
    per = min(per, nblk)
    vp = nblk * block
    block_batch = tile_rows(block_batch, bsz)
    x = pad_batch(x, block_batch)
    bp = x.shape[0]
    if vp != v:
        x = jnp.pad(x, [(0, 0), (0, vp - v)], constant_values=_neg_inf(x.dtype))
    kk = min(k, block)
    work_dtype = x.dtype
    if key_dtype is not None:  # encode_key_values widens sub-64-bit to i32
        work_dtype = jnp.int64 if jnp.dtype(key_dtype).itemsize == 8 else jnp.int32
    nb = bp // block_batch
    vs, is_ = pl.pallas_call(
        functools.partial(_phase1_kernel, k=kk, block=block, v_real=v,
                          use_mxu=use_mxu, key_dtype=key_dtype,
                          decode=(key_dtype is not None and nblk == 1)),
        grid=(nb, nblk // per),
        in_specs=[pl.BlockSpec((block_batch, per * block),
                               lambda i, j: (i, j))],
        out_specs=[pl.BlockSpec((per, block_batch, kk),
                                lambda i, j: (j, i, 0))] * 2,
        out_shape=[
            jax.ShapeDtypeStruct(
                (nblk, bp, kk), x.dtype if nblk == 1 else work_dtype),
            jax.ShapeDtypeStruct((nblk, bp, kk), jnp.int32),
        ],
        interpret=interpret,
    )(x)
    while vs.shape[0] > 1:
        g = vs.shape[0] // 2
        lk = vs.shape[-1]
        keep = min(k, 2 * lk)
        last = g == 1
        vs, is_ = pl.pallas_call(
            functools.partial(
                _merge_level_kernel, keep=keep, use_mxu=use_mxu,
                decode_dtype=key_dtype if (key_dtype is not None and last)
                else None),
            grid=(nb, g),
            in_specs=[pl.BlockSpec((2, block_batch, lk),
                                   lambda i, j: (j, i, 0))] * 2,
            out_specs=[pl.BlockSpec((None, block_batch, keep),
                                    lambda i, j: (j, i, 0))] * 2,
            out_shape=[
                jax.ShapeDtypeStruct(
                    (g, bp, keep),
                    x.dtype if (key_dtype is not None and last)
                    else vs.dtype),
                jax.ShapeDtypeStruct((g, bp, keep), jnp.int32),
            ],
            interpret=interpret,
        )(vs, is_)
    return vs[0, :bsz, :k], is_[0, :bsz, :k]
