"""Pallas TPU kernel: fused single-launch full sort (DESIGN.md §11).

The schedule executor realizes ``repro.sort`` as a 2-sorter stage plus a
LOMS 2-way merge tree — each level a separate XLA op over HBM-resident
data, with the NaN-policy key encode/decode and the payload gather as
further passes. This kernel runs the *whole* pipeline per batch tile in
one ``pallas_call``:

  load -> (encode total-order int keys) -> pad to a power of two with
  +sentinels -> trace-time-unrolled merge tree carrying an int32
  position lane -> slice the live prefix -> (decode) -> (reverse for
  descending) -> store values + gather payload lanes in VMEM.

The tree's level structure comes from the pluggable network layer
(``repro.networks``): ``network=`` names a registered family ("loms",
"s2ms", "periodic3", "bitonic") and the kernel executes whatever
merge-step program the registry hands back — the autotuner tournament
picks the family per size class.

Sentinel handling never relies on tie order: when a position lane is
carried, validity is decided by mask (``compact_lanes``); the bare
values-only call needs only multiset-sortedness, under which the first
``n`` output slots are exactly the sorted input for *any* family.

VMEM: every level ranks and permutes through (rows, S, 128) pairwise
tiles, S the level's group width (at least 128); the kernel walks row
slabs (``row_slab``) so the widest tile stays near 2^17 elements whatever
``block_batch`` is. ``streaming.planner`` (``plan_sort`` /
``sort_fits_vmem``) gates routing by width.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.networks import run_sort_program, sort_program

from .common import (
    _iota,
    ceil_pow2,
    compact_lanes,
    decode_kernel_keys,
    encode_kernel_keys,
    for_row_slabs,
    gather_lanes,
    narrow_back,
    np_fill,
    pad_batch,
    resolve_interpret,
    reverse_lanes,
    row_block_spec,
    row_slab,
    slab_lanes,
    sentinel_max,
    split_payload_lanes,
    tile_rows,
    unpack_fused_results,
)


def _sort_kernel(
    x_ref,
    *refs,
    n: int,
    network: str,
    use_mxu: bool,
    key_dtype: Optional[str],
    descending: bool,
    n_payload: int,
    want_perm: bool,
):
    p_refs = refs[:n_payload]
    out_refs = refs[n_payload:]
    npad = ceil_pow2(n)
    need_pos = n_payload > 0 or want_perm

    def body(x, *leaves):
        bt = x.shape[0]
        if key_dtype is not None:  # fused nan_policy="last" encode on load
            x = encode_kernel_keys(x)
        if npad != n:
            # np_fill: a bare python uint32-max overflows weak-int32
            # promotion
            fill = np_fill(sentinel_max(x.dtype), x.dtype)
            x = jnp.concatenate(
                [x, jnp.full((bt, npad - n), fill, x.dtype)], axis=-1)
        pos = _iota((bt, npad), 1) if need_pos else None
        # the unrolled merge tree comes from the network registry (shared
        # with the segmented class kernels, column-device cutover included)
        x, pos = run_sort_program(sort_program(network, npad), x, pos,
                                  use_mxu)
        if need_pos and npad != n:
            # the column devices make no cross-run tie-order promise, so a
            # tail pad that ties a genuine dtype-max value may land inside
            # the live prefix; validity is decided by the position lane,
            # never by value
            x, pos = compact_lanes(pos < n, [x, pos], use_mxu=use_mxu)
        out = x[:, :n]  # value-identical under pad/max aliasing (pads tie)
        perm = pos[:, :n] if need_pos else None
        if key_dtype is not None:  # fused decode on store
            out = decode_kernel_keys(out, key_dtype)
        if descending:
            out, *rest = reverse_lanes([out] + ([perm] if need_pos else []),
                                       use_mxu=use_mxu)
            perm = rest[0] if need_pos else None
        outs = [out] + ([perm] if want_perm else [])
        return outs + [gather_lanes(perm, leaf) for leaf in leaves]

    for_row_slabs([x_ref, *p_refs], out_refs, body,
                  row_slab(x_ref.shape[0], npad))


@functools.partial(
    jax.jit,
    static_argnames=(
        "network", "block_batch", "use_mxu", "interpret", "key_dtype",
        "descending", "want_perm",
    ),
)
def loms_sort_pallas(
    x: jnp.ndarray,
    payloads: Sequence[jnp.ndarray] = (),
    *,
    network: str = "loms",
    block_batch: int = 8,
    use_mxu: bool = True,
    interpret: Optional[bool] = None,
    key_dtype: Optional[str] = None,
    descending: bool = False,
    want_perm: bool = False,
):
    """Full sort of unsorted (B, n) rows in one fused kernel launch.

    ``network`` — registered family name executed by the merge tree.

    ``key_dtype`` — original float dtype name: the kernel encodes the
    total-order int keys on load and decodes on store (pass
    ``use_mxu=False``; int keys must take the exact scatter permute).
    ``payloads`` — (B, n[, F]) lanes permuted in VMEM and returned.
    ``descending`` — descending output, handled in-register. ``want_perm``
    — also return the int32 sort permutation (input positions).

    Returns ``out`` alone in the plain call, else
    ``(out, perm | None, tuple(payload_outs))``. Ragged batch sizes pad up
    to a ``block_batch`` multiple and slice back.
    """
    interpret = resolve_interpret(interpret)
    bsz, n = x.shape
    payloads = tuple(payloads)
    for p in payloads:
        assert p.ndim in (2, 3) and p.shape[:2] == (bsz, n), (p.shape, (bsz, n))
    lanes, rebuild = split_payload_lanes(payloads)
    block_batch = tile_rows(block_batch, bsz)
    x = pad_batch(x, block_batch)
    lanes = [pad_batch(p, block_batch) for p in lanes]
    dtypes = [x.dtype] + [p.dtype for p in lanes]
    x, *lanes = slab_lanes([x, *lanes], block_batch, ceil_pow2(n))
    padded = x.shape[0]
    spec = row_block_spec(block_batch, n)
    out_specs = [spec] * (1 + int(want_perm) + len(lanes))
    out_shapes = [jax.ShapeDtypeStruct((padded, n), x.dtype)]
    if want_perm:
        out_shapes.append(jax.ShapeDtypeStruct((padded, n), jnp.int32))
    out_shapes += [jax.ShapeDtypeStruct(p.shape, p.dtype) for p in lanes]
    results = pl.pallas_call(
        functools.partial(
            _sort_kernel, n=n, network=network, use_mxu=use_mxu,
            key_dtype=key_dtype, descending=descending,
            n_payload=len(lanes), want_perm=want_perm,
        ),
        grid=(padded // block_batch,),
        in_specs=[spec] * (1 + len(lanes)),
        out_specs=out_specs,
        out_shape=out_shapes,
        interpret=interpret,
    )(x, *lanes)
    results = narrow_back(results, dtypes, want_perm)
    res = unpack_fused_results(results, bsz, padded, len(lanes), want_perm)
    if not payloads and not want_perm:
        return res
    out, perm, pouts = res
    return out, perm, rebuild(pouts)
