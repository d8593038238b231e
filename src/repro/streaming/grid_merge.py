"""Grid-resident streaming 2-way merge: one launch, carry in VMEM scratch.

The original chunked merge (``chunked.py``) drives the FLiMS carry-buffer
loop from XLA: every tile step is its own ``pallas_call``, so the carry
buffer and the stream pointers round-trip through HBM between steps —
exactly the intermediate traffic the paper's devices exist to avoid.

This kernel keeps the whole pipeline resident for the duration of one
``pallas_call`` (DESIGN.md §11):

* grid = (batch, out_tiles); the TPU grid iterates the last dimension
  innermost, so each batch row runs its tile steps back to back;
* the carry tile lives in **VMEM scratch** and persists across grid
  steps (Pallas scratch is allocated once per launch, not per step);
* the stream pointers and last-loaded values live in **SMEM scratch**;
* the inputs stay in HBM and each refill is one async DMA of a single
  tile, chosen by the FLiMS rule (refill whichever stream's *last loaded*
  element is smaller — the bound that makes a fixed emission rate safe);
* only the emitted lower halves are written back, through the blocked
  output spec.

HBM traffic is therefore one read of each input element, one write of
each output element, and nothing else — the FLiMS property — instead of
one carry round-trip per tile. Values-only (the streaming backend's
contract); works for any dtype including the total-order int keys.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import pad_tail_sorted, resolve_interpret
from repro.networks import merge_program, merge_runs
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace


def _grid_merge2_kernel(
    a_hbm, b_hbm, o_ref, carry_ref, buf_ref, ptr_ref, last_ref, sem,
    *, t: int, la: int, lb: int, prog, use_mxu: bool,
):
    r = pl.program_id(0)
    i = pl.program_id(1)

    def load(src, start, slot):
        """DMA one tile of row ``r`` into buffer ``slot``."""
        if not isinstance(start, int):  # stream pointers step by whole tiles
            start = pl.multiple_of(start, t)
        cp = pltpu.make_async_copy(src.at[r, :, pl.ds(start, t)],
                                   buf_ref.at[slot], sem)
        cp.start()
        cp.wait()

    def tail(slot):
        # a vector load, then one static element: the TPU reads scalars
        # from VMEM only this way
        return buf_ref[slot][0, t - 1]

    @pl.when(i == 0)
    def _prologue():
        # load the first tile of each stream, emit the lower half
        load(a_hbm, 0, 0)
        load(b_hbm, 0, 1)
        merged = merge_runs(prog, buf_ref[0], buf_ref[1], use_mxu=use_mxu)
        o_ref[...] = merged[:, :t]
        carry_ref[...] = merged[:, t:]
        ptr_ref[0] = t
        ptr_ref[1] = t
        last_ref[0] = tail(0)
        last_ref[1] = tail(1)

    @pl.when(i > 0)
    def _step():
        pa = ptr_ref[0]
        pb = ptr_ref[1]
        last_a = last_ref[0]
        last_b = last_ref[1]
        sel_a = last_a <= last_b  # FLiMS rule: refill the lagging stream

        @pl.when(sel_a)
        def _():
            load(a_hbm, pa, 0)

        @pl.when(jnp.logical_not(sel_a))
        def _():
            load(b_hbm, pb, 0)

        new = tail(0)
        last_ref[0] = jnp.where(sel_a, new, last_a)
        last_ref[1] = jnp.where(sel_a, last_b, new)
        # pointers clamp at the all-sentinel drain tile, so an exhausted
        # stream reads sentinels forever
        ptr_ref[0] = jnp.where(sel_a, jnp.minimum(pa + t, la - t), pa)
        ptr_ref[1] = jnp.where(sel_a, pb, jnp.minimum(pb + t, lb - t))
        merged = merge_runs(prog, carry_ref[...], buf_ref[0],
                            use_mxu=use_mxu)
        o_ref[...] = merged[:, :t]
        carry_ref[...] = merged[:, t:]


@functools.partial(jax.jit, static_argnames=("tile", "network", "use_mxu",
                                             "interpret"))
def grid_chunked_merge2(
    a: jnp.ndarray,
    b: jnp.ndarray,
    *,
    tile: int = 512,
    network: str = "loms",
    use_mxu: bool = True,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Single-launch streaming merge of ascending (B, Na) and (B, Nb).

    Equivalent to ``sort(concat([a, b], -1))`` with an O(tile) on-chip
    working set per row; the carry buffer never leaves VMEM between tile
    steps. The emitted prefix is exact for any input length (drain tiles
    carry the finite dtype +sentinel; see chunked.py on aliasing).
    ``network`` names the registered family executing each tile merge —
    the program is built outside the kernel, a static trace-time
    constant. Sub-32-bit values merge widened to 32 bits (the SMEM
    scalars the refill rule reads are 32-bit), which is exact."""
    from repro.resilience.failpoints import failpoint

    # trace-time seam: fires when this signature (re)compiles, the same
    # scope as a genuine refill-pipeline lowering failure — already-cached
    # executables are past the point this layer can observe
    failpoint("grid_merge.refill")
    interpret = resolve_interpret(interpret)
    dtype = a.dtype
    if jnp.dtype(dtype).itemsize < 4:
        wide = jnp.float32 if jnp.issubdtype(dtype, jnp.floating) else jnp.int32
        a, b = a.astype(wide), b.astype(wide)
    bsz, na = a.shape
    nb = b.shape[-1]
    t = int(tile)
    total = na + nb
    out_tiles = -(-total // t)
    if obs_trace.enabled():
        # trace-time telemetry (this body runs once per compilation): the
        # prologue DMAs two tiles per row, every later grid step one —
        # the HBM-refill count the FLiMS carry pipeline is sized by
        obs_metrics.counter("grid_merge.launches").inc(tile=t)
        obs_metrics.counter("grid_merge.refill_tiles").inc(
            bsz * (out_tiles + 1), tile=t)
    # each stream gets one all-sentinel drain tile past its (padded) tail
    la = (-(-na // t) + 1) * t
    lb = (-(-nb // t) + 1) * t
    # rows as (1, L) slabs in HBM: a row's DMA is then a whole-dimension
    # slice, which the TPU tiling accepts at any row
    ap = pad_tail_sorted(a, la).reshape(bsz, 1, la)
    bp = pad_tail_sorted(b, lb).reshape(bsz, 1, lb)
    out = pl.pallas_call(
        functools.partial(_grid_merge2_kernel, t=t, la=la, lb=lb,
                          prog=merge_program(network, t, t),
                          use_mxu=use_mxu),
        grid=(bsz, out_tiles),
        # inputs stay in HBM: the kernel DMAs its own tiles
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.HBM),
            pl.BlockSpec(memory_space=pltpu.HBM),
        ],
        # one (1, t) output tile per step: the block's last two dims are
        # a whole dimension and a lane multiple, as the TPU tiling needs
        out_specs=pl.BlockSpec((None, None, 1, t), lambda r, i: (r, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, out_tiles, 1, t), a.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, t), a.dtype),     # carry (resident across steps)
            pltpu.VMEM((2, 1, t), a.dtype),  # refill buffers
            pltpu.SMEM((2,), jnp.int32),     # stream pointers
            pltpu.SMEM((2,), a.dtype),       # last-loaded values
            pltpu.SemaphoreType.DMA,
        ],
        interpret=interpret,
    )(ap, bp)
    return out.reshape(bsz, out_tiles * t)[:, :total].astype(dtype)
