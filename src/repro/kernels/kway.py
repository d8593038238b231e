"""Pallas TPU kernel: generic schedule-driven k-way LOMS merge.

Runs any :class:`repro.core.networks.Schedule` inside a Pallas kernel.
The schedule's wiring is static, but Pallas does not allow captured
constants, so it rides as one small int32 operand (a whole-array block,
fetched once): per stage, every working cell's group id, run, position
inside its run and slot inside its group. Each stage is then lane-flat
on the working vector:
  ranks of every cell inside its group (comparison cloud, VPU) -> slot
  lookup -> one one-hot permute.
The setup scatter and the output gather are one-hot permutes too, so no
gather, scatter or SMEM vector load reaches the TPU lowering. This is the
general path (3c_7r, mixed list sizes, medians); the 2-way fast path
lives in loms_merge.py.

Fused pipeline extensions (DESIGN.md §11): ``key_dtype`` applies the
total-order float->int key transform on load/store inside the kernel,
``payloads`` threads an int32 position lane through every stage permute
and gathers payload lanes in VMEM, ``descending`` reverses each list
segment on load and the output on store — so a NaN-policy payload k-way
merge is still one launch.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.networks import Schedule

from .common import (
    _iota,
    decode_kernel_keys,
    encode_kernel_keys,
    for_row_slabs,
    gather_lanes,
    narrow_back,
    pad_batch,
    pair_sum,
    permute_lanes,
    resolve_interpret,
    reverse_lanes,
    row_block_spec,
    row_slab,
    slab_lanes,
    run_rank,
    split_payload_lanes,
    tile_rows,
    unpack_fused_results,
)

#: wiring rows per stage: group id, run, position in run, slot in group
_STAGE_ROWS = 4


def _schedule_wiring(sched: Schedule, n_stages=None) -> np.ndarray:
    """The schedule's static wiring as one int32 matrix: row 0 the setup
    scatter, row 1 the output gather, then ``_STAGE_ROWS`` rows per stage
    over the working vector. A cell outside every group of a stage is its
    own group (unique negative id), so the stage leaves it in place."""
    size = sched.size
    stages = sched.stages if n_stages is None else sched.stages[:n_stages]
    width = max(size, sched.n_inputs, sched.n_outputs)
    rows = [np.asarray(sched.setup_scatter, np.int64),
            np.asarray(sched.output_gather, np.int64)]
    for st in stages:
        grp = -1 - np.arange(size)
        run = np.zeros(size, np.int64)
        local = np.zeros(size, np.int64)
        slot = np.zeros(size, np.int64)
        for gi, g in enumerate(st.groups):
            if g.n <= 1:
                continue
            runs = g.runs if g.runs is not None else (1,) * g.n
            offs = np.cumsum((0,) + tuple(runs))
            for o, cell in enumerate(g.idx):
                r = int(np.searchsorted(offs, o, side="right") - 1)
                grp[cell], run[cell] = gi, r
                local[cell], slot[cell] = o - offs[r], o
        rows += [grp, run, local, slot]
    out = np.full((len(rows), width), -1, np.int32)
    for i, r in enumerate(rows):
        out[i, :len(r)] = r
    return out


def _segment_reversal(lens: Tuple[int, ...]) -> jnp.ndarray:
    """(1, total) destinations reversing every list segment in place."""
    total = sum(lens)
    lane = _iota((1, total), 1)
    dest = lane
    off = 0
    for ln in lens:
        inside = (lane >= off) & (lane < off + ln)
        dest = jnp.where(inside, 2 * off + ln - 1 - lane, dest)
        off += ln
    return dest


def _kway_kernel(
    x_ref,
    w_ref,
    *refs,
    sched: Schedule,
    n_stages,
    use_mxu: bool,
    lens: Optional[Tuple[int, ...]],
    key_dtype: Optional[str],
    descending: bool,
    n_payload: int,
    want_perm: bool,
):
    p_refs = refs[:n_payload]
    out_refs = refs[n_payload:]
    n_in, n_out, size = sched.n_inputs, sched.n_outputs, sched.size
    n_st = len(sched.stages if n_stages is None else sched.stages[:n_stages])
    need_pos = n_payload > 0 or want_perm

    def row(r, width):
        return w_ref[r:r + 1, :width]

    def body(x, *leaves):
        bt = x.shape[0]
        arrays = [x] + ([_iota((bt, n_in), 1)] if need_pos else [])
        if descending:
            # reverse each list segment -> ascending problem whose position
            # lane still indexes the original (descending) concat
            arrays = permute_lanes(_segment_reversal(lens), arrays, n_in,
                                   use_mxu=use_mxu)
        if key_dtype is not None:  # fused nan_policy="last" encode on load
            arrays[0] = encode_kernel_keys(arrays[0])
        arrays = permute_lanes(row(0, n_in), arrays, size, use_mxu=use_mxu)
        for s in range(n_st):
            grp, run, local, slot = (row(2 + _STAGE_ROWS * s + j, size)
                                     for j in range(_STAGE_ROWS))
            rank = run_rank(arrays[0], grp, run, local)
            # the cell holding slot ``rank`` of the same group
            dest = pair_sum(
                lambda qs, ps, qi, pi: jnp.where(
                    (qs[0] == ps[0]) & (qs[1] == ps[1]), qi, 0),
                [grp, slot], [grp, rank], size)
            arrays = permute_lanes(dest, arrays, size, use_mxu=use_mxu)
        gather = row(1, n_out)
        out = gather_lanes(gather, arrays[0])
        perm = gather_lanes(gather, arrays[1]) if need_pos else None
        if key_dtype is not None:  # fused decode on store
            out = decode_kernel_keys(out, key_dtype)
        if descending:
            out, *rest = reverse_lanes([out] + ([perm] if need_pos else []),
                                       use_mxu=use_mxu)
            perm = rest[0] if need_pos else None
        outs = [out] + ([perm] if want_perm else [])
        return outs + [gather_lanes(perm, leaf) for leaf in leaves]

    for_row_slabs([x_ref, *p_refs], out_refs, body,
                  row_slab(x_ref.shape[0], size))


def kway_merge_pallas(
    x: jnp.ndarray,
    sched: Schedule,
    payloads: Sequence[jnp.ndarray] = (),
    *,
    n_stages: Optional[int] = None,
    block_batch: int = 8,
    use_mxu: bool = True,
    interpret: Optional[bool] = None,
    lens: Optional[Tuple[int, ...]] = None,
    key_dtype: Optional[str] = None,
    descending: bool = False,
    want_perm: bool = False,
):
    """Apply an oblivious schedule to (B, n_inputs) batched lists.

    Ragged batch sizes are padded up to a ``block_batch`` multiple and
    sliced back. ``interpret=None`` auto-resolves: compile on TPU,
    interpret elsewhere.

    Fused-pipeline extras (DESIGN.md §11): ``key_dtype`` (original float
    dtype name) fuses the total-order key encode/decode into the kernel —
    pass ``use_mxu=False`` with it; ``payloads`` is a sequence of
    (B, n_inputs[, F]) lanes riding the permutation in VMEM;
    ``descending`` (requires ``lens``, the per-list lengths) handles
    descending-sorted lists in-register; ``want_perm`` also returns the
    int32 permutation. Returns ``out`` alone in the classic call, else
    ``(out, perm | None, tuple(payload_outs))``.
    """
    interpret = resolve_interpret(interpret)
    bsz, n_in = x.shape
    assert n_in == sched.n_inputs
    payloads = tuple(payloads)
    for p in payloads:
        assert p.ndim in (2, 3) and p.shape[:2] == (bsz, n_in), (
            p.shape, (bsz, n_in))
    if descending:
        assert lens is not None and sum(lens) == n_in, (lens, n_in)
    lanes, rebuild = split_payload_lanes(payloads)
    block_batch = tile_rows(block_batch, bsz)
    x = pad_batch(x, block_batch)
    lanes = [pad_batch(p, block_batch) for p in lanes]
    dtypes = [x.dtype] + [p.dtype for p in lanes]
    x, *lanes = slab_lanes([x, *lanes], block_batch, sched.size)
    padded = x.shape[0]
    wiring = _schedule_wiring(sched, n_stages)
    n_out = sched.n_outputs
    kernel = functools.partial(
        _kway_kernel, sched=sched, n_stages=n_stages, use_mxu=use_mxu,
        lens=lens, key_dtype=key_dtype, descending=descending,
        n_payload=len(lanes), want_perm=want_perm,
    )
    out_specs = [row_block_spec(block_batch, n_out)]
    out_shapes = [jax.ShapeDtypeStruct((padded, n_out), x.dtype)]
    if want_perm:
        out_specs.append(row_block_spec(block_batch, n_out))
        out_shapes.append(jax.ShapeDtypeStruct((padded, n_out), jnp.int32))
    out_specs += [row_block_spec(block_batch, n_out) for _ in lanes]
    out_shapes += [jax.ShapeDtypeStruct((padded, n_out), p.dtype)
                   for p in lanes]
    results = pl.pallas_call(
        kernel,
        grid=(padded // block_batch,),
        in_specs=[
            row_block_spec(block_batch, n_in),
            # grid-constant wiring: a whole-array block, fetched once
            pl.BlockSpec(wiring.shape, lambda i: (0, 0)),
            *[row_block_spec(block_batch, n_in) for _ in lanes],
        ],
        out_specs=out_specs,
        out_shape=out_shapes,
        interpret=interpret,
    )(x, jnp.asarray(wiring), *lanes)
    results = narrow_back(results, dtypes, want_perm)
    res = unpack_fused_results(results, bsz, padded, len(lanes), want_perm)
    if not payloads and not want_perm:
        return res
    out, perm, pouts = res
    return out, perm, rebuild(pouts)
