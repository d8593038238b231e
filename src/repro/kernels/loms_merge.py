"""Pallas TPU kernel: batched 2-way List Offset merge (paper Section IV).

Layout strategy (hardware adaptation, DESIGN.md §2): the k-column setup
array for UP-m/DN-n with C columns assigns
    A_j  -> column j % C            (ascending stride-C slices of ``a``)
    B_j  -> column (n-1-j) % C      (ascending stride-C slices of ``b``)
The kernel never builds that array: column, row position and run are
lane arithmetic on ``concat(a, b)``, stage 1 ranks every element inside
its column (the S2MS comparison cloud on the VPU), stage 2 ranks it
inside its row, and one one-hot permute writes the row-major flatten.
Only static slices, compares, selects and sublane sums reach the TPU
lowering (``repro.kernels.common`` lane primitives).

Fused pipeline extensions (DESIGN.md §11): the kernel optionally
* encodes the total-order float->int key transform on load and decodes it
  on store (``key_dtype=``) so ``nan_policy="last"`` costs zero extra HBM
  passes,
* threads an int32 position lane through the same permutes and gathers
  payload lanes in VMEM (``payloads=``), so payload merges stop
  materializing an index array and gathering at the XLA level,
* handles ``descending=`` inputs by a lane-reversal permute on load/store.

Per-block VMEM: the (B, m+n) lanes plus one (rows, m+n, 128) pairwise
tile; the kernel walks row slabs (``row_slab``) so the tile stays small
whatever ``block_batch`` the planner picks.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.networks import merge_program, merge_runs

from .common import (
    _iota,
    decode_kernel_keys,
    encode_kernel_keys,
    for_row_slabs,
    gather_lanes,
    narrow_back,
    pad_batch,
    resolve_interpret,
    reverse_lanes,
    row_block_spec,
    row_slab,
    slab_lanes,
    split_payload_lanes,
    tile_rows,
    unpack_fused_results,
)


def _loms2_kernel(
    a_ref,
    b_ref,
    *refs,
    network: str,
    n_cols: int,
    use_mxu: bool,
    key_dtype: Optional[str],
    descending: bool,
    n_payload: int,
    want_perm: bool,
):
    p_refs = refs[:n_payload]
    out_refs = refs[n_payload:]
    m, n = a_ref.shape[-1], b_ref.shape[-1]
    # the merge structure comes from the network registry: the LOMS column
    # device, the S2MS cloud, or a pair network
    prog = merge_program(network, m, n,
                         n_cols if network == "loms" else None)
    need_pos = n_payload > 0 or want_perm

    def body(a, b, *leaves):
        bt = a.shape[0]
        # positions index the *original* orientation of concat(a, b), the
        # same convention the unfused executor's position payload uses
        pa = _iota((bt, m), 1)
        pb = _iota((bt, n), 1) + m
        if descending:  # reverse on load: the merge itself is ascending
            a, pa = reverse_lanes([a, pa], use_mxu=use_mxu)
            b, pb = reverse_lanes([b, pb], use_mxu=use_mxu)
        if key_dtype is not None:  # fused nan_policy="last" encode
            a = encode_kernel_keys(a)
            b = encode_kernel_keys(b)
        if need_pos:
            out, perm = merge_runs(prog, a, b, use_mxu=use_mxu,
                                   payload=(pa, pb))
        else:
            out, perm = merge_runs(prog, a, b, use_mxu=use_mxu), None
        if key_dtype is not None:  # fused decode on store
            out = decode_kernel_keys(out, key_dtype)
        if descending:
            out, *rest = reverse_lanes([out] + ([perm] if need_pos else []),
                                       use_mxu=use_mxu)
            perm = rest[0] if need_pos else None
        outs = [out] + ([perm] if want_perm else [])
        return outs + [gather_lanes(perm, leaf) for leaf in leaves]

    for_row_slabs([a_ref, b_ref, *p_refs], out_refs, body,
                  row_slab(a_ref.shape[0], m + n))


@functools.partial(
    jax.jit,
    static_argnames=(
        "network", "n_cols", "block_batch", "use_mxu", "interpret",
        "key_dtype", "descending", "want_perm",
    ),
)
def loms_merge2_pallas(
    a: jnp.ndarray,
    b: jnp.ndarray,
    payloads: Sequence[jnp.ndarray] = (),
    *,
    network: str = "loms",
    n_cols: int = 2,
    block_batch: int = 8,
    use_mxu: bool = True,
    interpret: Optional[bool] = None,
    key_dtype: Optional[str] = None,
    descending: bool = False,
    want_perm: bool = False,
):
    """Merge sorted ``a`` (B, m) and ``b`` (B, n) -> (B, m+n).

    ``network`` names a registered family (``repro.networks``); the
    default LOMS path requires n_cols | m and n_cols | n (the hole-free
    fast path; ragged sizes fall back to the schedule executor in
    ops.py), other families carry their own shape capability (e.g.
    bitonic needs a pow2 total). Ragged batch sizes are padded up to a
    ``block_batch`` multiple and sliced back. ``interpret=None``
    auto-resolves: compile on TPU, interpret elsewhere.

    Fused-pipeline extras (all handled inside the one kernel launch):
    ``key_dtype`` — name of the original float dtype; the kernel applies
    the total-order int-key encode on load and the inverse on store
    (callers pass int-unsafe ``use_mxu=False``). ``descending`` — inputs
    are descending-sorted; so is the output. ``payloads`` — sequence of
    (B, m+n[, F]) lanes, the per-list payloads already concatenated along
    the list axis; each rides the merge permutation in VMEM and is
    returned permuted. ``want_perm`` — also return the int32 permutation.

    Returns ``out`` alone in the classic call, else
    ``(out, perm | None, tuple(payload_outs))``.
    """
    interpret = resolve_interpret(interpret)
    (bsz, m), (_, n) = a.shape, b.shape
    if network == "loms":
        assert m % n_cols == 0 and n % n_cols == 0, (m, n, n_cols)
    payloads = tuple(payloads)
    for p in payloads:
        assert p.ndim in (2, 3) and p.shape[:2] == (bsz, m + n), (
            p.shape, (bsz, m + n))
    lanes, rebuild = split_payload_lanes(payloads)
    block_batch = tile_rows(block_batch, bsz)
    a, b = pad_batch(a, block_batch), pad_batch(b, block_batch)
    lanes = [pad_batch(p, block_batch) for p in lanes]
    dtypes = [a.dtype] + [p.dtype for p in lanes]
    a, b, *lanes = slab_lanes([a, b, *lanes], block_batch, m + n)
    padded = a.shape[0]
    grid = (padded // block_batch,)
    out_specs = [row_block_spec(block_batch, m + n)]
    out_shapes = [jax.ShapeDtypeStruct((padded, m + n), a.dtype)]
    if want_perm:
        out_specs.append(row_block_spec(block_batch, m + n))
        out_shapes.append(jax.ShapeDtypeStruct((padded, m + n), jnp.int32))
    out_specs += [row_block_spec(block_batch, m + n) for _ in lanes]
    out_shapes += [jax.ShapeDtypeStruct(p.shape, p.dtype) for p in lanes]
    results = pl.pallas_call(
        functools.partial(
            _loms2_kernel, network=network, n_cols=n_cols, use_mxu=use_mxu,
            key_dtype=key_dtype, descending=descending,
            n_payload=len(lanes), want_perm=want_perm,
        ),
        grid=grid,
        in_specs=[
            row_block_spec(block_batch, m),
            row_block_spec(block_batch, n),
            *[row_block_spec(block_batch, m + n) for _ in lanes],
        ],
        out_specs=out_specs,
        out_shape=out_shapes,
        interpret=interpret,
    )(a, b, *lanes)
    results = narrow_back(results, dtypes, want_perm)
    res = unpack_fused_results(results, bsz, padded, len(lanes), want_perm)
    if not payloads and not want_perm:
        return res
    out, perm, pouts = res
    return out, perm, rebuild(pouts)
