"""Pallas TPU kernels for the segmented (CSR ragged) subsystem.

One kernel launch per **size class**: the bucketer (repro.segmented) packs
every segment whose length rounds up to the same power of two ``W`` into
the rows of a dense ``(n_segments, W)`` tile, with a per-row valid length
riding as an int32 column. The kernel then runs the matching trace-time-
unrolled LOMS network once for the whole class:

  load -> (encode total-order int keys) -> (bit-flip for descending) ->
  overwrite the invalid tail lanes with the key-domain +sentinel ->
  unrolled LOMS merge tree (sort) or column S2MS merge (merge) carrying an
  int32 position lane -> mask-compact validity (``compact_lanes`` — a pad
  can never displace a real element, even when a genuine NaN key sits
  above the float sentinel) -> gather the *raw* input values and payload
  lanes at the permutation in VMEM -> store the (optionally truncated)
  prefix.

Because the output values are gathered from the raw input at the
permutation — never decoded from keys — they are bit-exact for every
input including NaN payload bits, and the same gather carries pytree
payload lanes (PR 4's position-lane device). Descending order is a key
bit-flip (``~k`` reverses any integer total order exactly; ``-x`` for the
raw-float unsafe path), so descending-sorted segment *inputs* become
ascending key runs for free — no index reversal anywhere.

``k_out`` truncates the stored prefix, which makes per-segment top-k the
same launch as the class sort with a narrower output block.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.networks import (merge_program, merge_runs, pick_merge_cols,
                            run_sort_program, sort_program)

from .common import (
    _iota,
    compact_lanes,
    encode_kernel_keys,
    for_row_slabs,
    gather_lanes,
    narrow_back,
    pad_batch,
    resolve_interpret,
    row_block_spec,
    row_slab,
    slab_lanes,
    split_payload_lanes,
    tile_rows,
    unpack_fused_results,
    widen,
)


def key_sentinel(dtype):
    """+sentinel in the *key* domain: the largest representable value, so
    masked lanes order after every valid key (NaN keys included — the
    total-order encode maps NaN below int-max)."""
    d = jnp.dtype(dtype)
    if jnp.issubdtype(d, jnp.floating):
        return jnp.asarray(jnp.finfo(d).max, d)
    return jnp.asarray(jnp.iinfo(d).max, d)


def flip_keys(k: jnp.ndarray) -> jnp.ndarray:
    """Exact order reversal: bitwise-not for any integer width (bijective,
    no int-min overflow the naive negation has), negation for raw floats
    (unsafe path — finite by contract)."""
    if jnp.issubdtype(k.dtype, jnp.floating):
        return -k
    return ~k


def _prep_keys(x, lens, *, encode: bool, flip: bool):
    """values -> masked network keys + the validity of each input lane."""
    keys = encode_kernel_keys(x) if encode else widen(x)
    if flip:
        keys = flip_keys(keys)
    lane = _iota(x.shape, 1)
    valid_in = lane < lens  # lens: (bt, 1) broadcasts over the lane axis
    return jnp.where(valid_in, keys, key_sentinel(keys.dtype)), lane


def _seg_sort_kernel(
    x_ref, len_ref, *refs,
    w: int, k_out: int, network: str, encode: bool, flip: bool,
    use_mxu: bool, n_payload: int, want_perm: bool,
):
    def body(x, lens, *p_ins):
        # x: (bt, w) raw, invalid tail lanes hold arbitrary fill; lens:
        # (bt, 1) per-segment valid lengths
        keys, lane = _prep_keys(x, lens, encode=encode, flip=flip)
        keys, pos = run_sort_program(sort_program(network, w), keys, lane,
                                     use_mxu)
        # validity by mask, never by value: a genuine NaN key sorts above
        # the float sentinel, so the compacted prefix — not the raw
        # network order — defines the live output
        pos = compact_lanes(pos < lens, [pos], use_mxu=use_mxu)[0]
        return _prefix_outs(pos, x, p_ins, k_out, want_perm)

    for_row_slabs([x_ref, len_ref, *refs[:n_payload]], refs[n_payload:],
                  body, row_slab(x_ref.shape[0], w))


def _prefix_outs(pos, x_vals, p_ins, k_out: int, want_perm: bool,
                 seg_pos=None):
    """Shared epilogue: the raw values and payload lanes gathered at the
    compacted permutation, truncated to the ``k_out`` prefix."""
    pos = pos[:, :k_out]
    outs = [gather_lanes(pos, x_vals)]
    if want_perm:
        outs.append(pos if seg_pos is None else seg_pos[:, :k_out])
    return outs + [gather_lanes(pos, p) for p in p_ins]


def _seg_merge_kernel(
    a_ref, b_ref, la_ref, lb_ref, *refs,
    wa: int, wb: int, k_out: int, network: str, n_cols: int, encode: bool,
    flip: bool, use_mxu: bool, n_payload: int, want_perm: bool,
):
    # dense-coordinate positions: [0, wa) = a lanes, [wa, wa+wb) = b lanes
    prog = merge_program(network, wa, wb,
                         n_cols if network == "loms" else None)

    def body(a, b, lens_a, lens_b, *p_ins):
        ka, lane_a = _prep_keys(a, lens_a, encode=encode, flip=flip)
        kb, lane_b = _prep_keys(b, lens_b, encode=encode, flip=flip)
        keys, pos = merge_runs(prog, ka, kb,
                               payload=(lane_a, wa + lane_b), use_mxu=use_mxu)
        in_a = pos < wa
        valid = (in_a & (pos < lens_a)) | (~in_a & (pos - wa < lens_b))
        pos = compact_lanes(valid, [pos], use_mxu=use_mxu)[0]
        # perm in *segment* coordinates: b elements continue at len_a
        seg_pos = jnp.where(pos < wa, pos, lens_a + (pos - wa))
        return _prefix_outs(pos, jnp.concatenate([a, b], axis=1), p_ins,
                            k_out, want_perm, seg_pos=seg_pos)

    for_row_slabs([a_ref, b_ref, la_ref, lb_ref, *refs[:n_payload]],
                  refs[n_payload:], body, row_slab(a_ref.shape[0], wa + wb))


def _n_lanes(payloads) -> int:
    return len(split_payload_lanes(payloads)[0])


def _class_call(kernel, inputs, payloads, *, width: int, k_out: int,
                block_batch: int, want_perm: bool, interpret, dtype):
    """Shared pallas_call wrapper: batch-pad, build specs, unpack.
    ``width`` is the row width the kernel walks."""
    interpret = resolve_interpret(interpret)
    bsz = inputs[0].shape[0]
    lanes, rebuild = split_payload_lanes(payloads)
    block_batch = tile_rows(block_batch, bsz)
    inputs = [pad_batch(v, block_batch) for v in inputs]
    lanes = [pad_batch(p, block_batch) for p in lanes]
    dtypes = [dtype] + [p.dtype for p in lanes]
    n_in = len(inputs)
    both = slab_lanes(inputs + lanes, block_batch, width)
    inputs, lanes = both[:n_in], both[n_in:]
    dtype = inputs[0].dtype
    padded = inputs[0].shape[0]
    in_specs = [row_block_spec(block_batch, v.shape[1])
                for v in inputs + lanes]
    out_specs = [row_block_spec(block_batch, k_out)] * (
        1 + int(want_perm) + len(lanes))
    out_shapes = [jax.ShapeDtypeStruct((padded, k_out), dtype)]
    if want_perm:
        out_shapes.append(jax.ShapeDtypeStruct((padded, k_out), jnp.int32))
    out_shapes += [jax.ShapeDtypeStruct((padded, k_out), p.dtype)
                   for p in lanes]
    results = pl.pallas_call(
        kernel,
        grid=(padded // block_batch,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shapes,
        interpret=interpret,
    )(*inputs, *lanes)
    results = narrow_back(results, dtypes, want_perm)
    res = unpack_fused_results(results, bsz, padded, len(lanes), want_perm)
    if not lanes and not want_perm:
        return res, None, ()  # shared epilogue returns the bare values
    out, perm, pouts = res
    return out, perm, rebuild(pouts)


@functools.partial(
    jax.jit,
    static_argnames=("k_out", "network", "encode", "flip", "want_perm",
                     "block_batch", "use_mxu", "interpret"),
)
def segment_class_sort_pallas(
    dense: jnp.ndarray,  # (S, W) raw segment rows, W a power of two
    lens: jnp.ndarray,  # (S, 1) int32 valid lengths (0 <= len <= W)
    payloads: Sequence[jnp.ndarray] = (),  # (S, W[, F]) dense lanes
    *,
    k_out: Optional[int] = None,  # truncate stored prefix (top-k); None = W
    network: str = "loms",  # registered network family for the merge tree
    encode: bool = True,  # fuse the total-order float key transform
    flip: bool = False,  # descending order (exact key bit-flip)
    want_perm: bool = False,
    block_batch: int = 8,
    use_mxu: bool = False,
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, Optional[jnp.ndarray], Tuple[jnp.ndarray, ...]]:
    """One size-class sort launch: every row sorted independently, valid
    prefix first. Returns ``(out, perm | None, payload_outs)`` — ``out``
    holds raw input values gathered at the sort permutation (bit-exact),
    ``perm`` the within-segment input positions; lanes past ``lens`` are
    unspecified (the CSR scatter never reads them)."""
    s, w = dense.shape
    assert w & (w - 1) == 0, f"class width {w} must be a power of two"
    k_out = w if k_out is None else int(k_out)
    assert 1 <= k_out <= w, (k_out, w)
    encode = encode and jnp.issubdtype(dense.dtype, jnp.floating)
    kernel = functools.partial(
        _seg_sort_kernel, w=w, k_out=k_out, network=network, encode=encode,
        flip=flip, use_mxu=use_mxu, n_payload=_n_lanes(payloads),
        want_perm=want_perm,
    )
    return _class_call(
        kernel, [dense, lens.astype(jnp.int32)], tuple(payloads), width=w,
        k_out=k_out, block_batch=block_batch,
        want_perm=want_perm, interpret=interpret, dtype=dense.dtype,
    )


@functools.partial(
    jax.jit,
    static_argnames=("k_out", "network", "encode", "flip", "want_perm",
                     "block_batch", "use_mxu", "n_cols", "interpret"),
)
def segment_class_merge_pallas(
    dense_a: jnp.ndarray,  # (S, Wa) sorted segment rows (pow2 width)
    dense_b: jnp.ndarray,  # (S, Wb)
    lens_a: jnp.ndarray,  # (S, 1) int32
    lens_b: jnp.ndarray,  # (S, 1) int32
    payloads: Sequence[jnp.ndarray] = (),  # (S, Wa+Wb[, F]) dense-coord lanes
    *,
    k_out: Optional[int] = None,
    network: str = "loms",
    encode: bool = True,
    flip: bool = False,
    want_perm: bool = False,
    block_batch: int = 8,
    use_mxu: bool = False,
    n_cols: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, Optional[jnp.ndarray], Tuple[jnp.ndarray, ...]]:
    """One size-class 2-way merge launch: row ``s`` merges the sorted runs
    ``a[s, :lens_a[s]]`` and ``b[s, :lens_b[s]]``. ``perm`` is in segment
    coordinates (b positions offset by the *valid* a length, matching the
    concatenated-segment payload convention of ``repro.merge``); payload
    lanes arrive in dense ``[a | b]`` coordinates of width ``Wa + Wb``."""
    s, wa = dense_a.shape
    wb = dense_b.shape[1]
    assert wa & (wa - 1) == 0 and wb & (wb - 1) == 0, (wa, wb)
    total = wa + wb
    k_out = total if k_out is None else int(k_out)
    assert 1 <= k_out <= total, (k_out, total)
    encode = encode and jnp.issubdtype(dense_a.dtype, jnp.floating)
    n_cols = pick_merge_cols(wa, wb) if n_cols is None else int(n_cols)
    kernel = functools.partial(
        _seg_merge_kernel, wa=wa, wb=wb, k_out=k_out, network=network,
        n_cols=n_cols, encode=encode, flip=flip, use_mxu=use_mxu,
        n_payload=_n_lanes(payloads),
        want_perm=want_perm,
    )
    return _class_call(
        kernel,
        [dense_a, dense_b, lens_a.astype(jnp.int32), lens_b.astype(jnp.int32)],
        tuple(payloads), width=total, k_out=k_out,
        block_batch=block_batch, want_perm=want_perm, interpret=interpret,
        dtype=dense_a.dtype,
    )
