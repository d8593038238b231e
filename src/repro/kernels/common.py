"""In-kernel primitives shared by the Pallas sorters.

Everything here is written for the TPU compute units:
  * comparison clouds -> dense boolean tiles on the VPU,
  * output routing (the FPGA MUXF tree) -> a one-hot select, on the VPU
    or as byte-wise one-hot matmuls on the MXU — exact either way,
  * fixed wiring -> lane arithmetic on iotas, unrolled at trace time.
No data-dependent control flow exists anywhere, mirroring the paper's
oblivious hardware, and every kernel-side primitive is one the TPU
lowering accepts (see the lane-primitive section).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl


def _iota(shape, dim, dtype=jnp.int32):
    """broadcasted_iota — the Pallas/Mosaic-safe way to make index ramps
    (captured numpy constants are not allowed inside kernel bodies)."""
    return jax.lax.broadcasted_iota(dtype, shape, dim)


# ---------------------------------------------------------------------------
# total-order float<->int keys (the nan_policy="last" transform)
# ---------------------------------------------------------------------------
# The math lives here — not in repro.api.keys, which re-exports it — so the
# kernel bodies can encode on load and decode on store without an
# api -> kernels -> api import cycle. Everything below is plain jnp and
# traces identically inside a Pallas kernel and at the XLA level.

#: float itemsize -> same-width signed integer type carrying the bit trick
#: (int64 keys require jax_enable_x64, but so does having f64 inputs)
KEY_ITYPE = {2: jnp.int16, 4: jnp.int32, 8: jnp.int64}


def key_transformable(dtype) -> bool:
    """Whether ``dtype`` is a float type the total-order key map covers."""
    d = jnp.dtype(dtype)
    return jnp.issubdtype(d, jnp.floating) and d.itemsize in KEY_ITYPE


def encode_key_values(x: jnp.ndarray) -> jnp.ndarray:
    """Float array -> integer keys with the same sort order, NaNs last.

    Bijective and strictly monotonic over every float (finite, ±0, ±inf);
    NaNs canonicalize to the positive quiet NaN, which maps above
    ``key(+inf)``. f32/bf16/f16 keys widen to int32 (the networks' native
    lane width); f64 keys stay int64. Kernel-safe: pure jnp, no captured
    numpy constants."""
    d = jnp.dtype(x.dtype)
    itype = KEY_ITYPE[d.itemsize]
    mask = itype(jnp.iinfo(itype).max)  # 0x7fff.. : flip all but the sign
    x = jnp.where(jnp.isnan(x), jnp.asarray(jnp.nan, d), x)  # canonical qNaN
    y = jax.lax.bitcast_convert_type(x, itype)
    k = jnp.where(y < 0, y ^ mask, y)
    return k if d.itemsize == 8 else k.astype(jnp.int32)


def decode_key_values(k: jnp.ndarray, dtype) -> jnp.ndarray:
    """Exact inverse of :func:`encode_key_values` (``dtype`` = the original
    float type); every NaN comes back as the canonical quiet NaN."""
    d = jnp.dtype(dtype)
    itype = KEY_ITYPE[d.itemsize]
    mask = itype(jnp.iinfo(itype).max)
    y = k.astype(itype)  # downcast first: the xor must run at key width
    y = jnp.where(y < 0, y ^ mask, y)
    return jax.lax.bitcast_convert_type(y, d)


def wide_dtype(dtype):
    """The 32-bit type kernels compare sub-32-bit values in (f32 for
    floats, int32 otherwise); wider types are their own. The widening is
    exact and order-preserving, and the TPU VPU has no 16-bit float
    compare."""
    d = jnp.dtype(dtype)
    if d.itemsize >= 4:
        return d
    return jnp.dtype(jnp.float32 if jnp.issubdtype(d, jnp.floating)
                     else jnp.int32)


def widen(x: jnp.ndarray) -> jnp.ndarray:
    return x if jnp.dtype(x.dtype).itemsize >= 4 else x.astype(wide_dtype(x.dtype))


def encode_kernel_keys(x: jnp.ndarray) -> jnp.ndarray:
    """:func:`encode_key_values` inside a kernel: a sub-32-bit float
    encodes from its exact f32 widening. The keys order exactly like the
    narrow encoding's; :func:`decode_kernel_keys` inverts them."""
    return encode_key_values(widen(x))


def decode_kernel_keys(k: jnp.ndarray, dtype) -> jnp.ndarray:
    """Inverse of :func:`encode_kernel_keys`, back to ``dtype``."""
    d = jnp.dtype(dtype)
    return decode_key_values(k, wide_dtype(d)).astype(d)


def ceil_pow2(n: int) -> int:
    """Smallest power of two >= ``n``, with the degenerate guard ``n <= 1
    -> 1``: a 0- or 1-element list needs no comparison network, and the
    naive ``1 << (n - 1).bit_length()`` would emit a phantom 2-wide device
    for ``n == 0`` (``(-1).bit_length() == 1``). Every trace-time pad-to-
    pow2 decision (the fused sort tree, the segmented size-class bucketer)
    must come through here so empty/singleton inputs can never size a
    0-width or oversized network."""
    n = int(n)
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``interpret=None`` -> auto: compile natively on TPU, run the kernel
    body as jnp (interpret mode) on every other platform. A trace-time
    Python decision — safe inside the jit wrappers because ``interpret``
    is always a static argument."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def sentinel_max(dtype):
    """Finite +sentinel: +/-inf would turn the one-hot MXU permute into
    0 * inf = NaN, so sentinels must stay finite."""
    d = jnp.dtype(dtype)
    if jnp.issubdtype(d, jnp.floating):
        return float(jnp.finfo(d).max)
    return int(jnp.iinfo(d).max)


def sentinel_min(dtype):
    d = jnp.dtype(dtype)
    if jnp.issubdtype(d, jnp.floating):
        return float(jnp.finfo(d).min)
    return int(jnp.iinfo(d).min)


def np_fill(value, dtype):
    """Pad value as a numpy scalar of ``dtype``: a bare python uint32-max
    passed to jnp.pad/jnp.full overflows JAX's weak-int32 promotion."""
    return np.asarray(value, jnp.dtype(dtype))


def use_mxu_for(dtype) -> bool:
    """Whether the planner sends values of ``dtype`` through the one-hot
    MXU permute (floats) or the VPU select (integers, including the
    total-order keys). Both are exact — the MXU permute moves bytes — so
    this is a routing default, not a correctness gate."""
    return bool(jnp.issubdtype(jnp.dtype(dtype), jnp.floating))


def tile_rows(block_batch: int, bsz: int) -> int:
    """The batch tile a kernel launches with. On the TPU a block's row
    count must be a multiple of 8 or the whole (padded) batch, so a batch
    that fits one tile runs as one tile and a smaller tile rounds up to 8
    rows; interpret mode keeps the planner's tile."""
    block_batch = max(int(block_batch), 1)
    if jax.default_backend() != "tpu":
        return block_batch
    if bsz <= block_batch:
        return max(bsz, 1)
    return -(-block_batch // 8) * 8


def pad_batch(x: jnp.ndarray, multiple: int) -> jnp.ndarray:
    """Pad the leading (batch) axis up to a multiple of ``multiple``.

    Pad rows are zeros — every kernel here treats batch rows independently,
    so their (garbage) outputs are sliced away by the caller."""
    pad = (-x.shape[0]) % multiple
    if pad == 0:
        return x
    return jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))


def pad_tail_sorted(x: jnp.ndarray, length: int, descending: bool = False) -> jnp.ndarray:
    """Pad the last (sorted) axis out to ``length`` while keeping each row
    sorted: +sentinel tail for ascending rows, -sentinel for descending.

    Sentinels are dtype extremes, so a genuine extreme value *ties* the
    padding (it can never be displaced past it — the padded row stays a
    sorted permutation of ``values + pads``). Value-only consumers are
    therefore exact under aliasing; anything that carries indices or
    payloads must track validity explicitly (an index ``-1`` per pad slot,
    or a length mask resolved with :func:`stable_compact`)."""
    pad = length - x.shape[-1]
    assert pad >= 0, (x.shape, length)
    if pad == 0:
        return x
    if x.shape[-1] == 0:
        # zero-width row (an empty segment): the "pad" is a pure fill —
        # jnp.pad handles it, but go through jnp.full so the sentinel dtype
        # cast is explicit and a (…, 0) int row cannot weak-promote
        fill = np_fill(sentinel_min(x.dtype) if descending else sentinel_max(x.dtype),
                       x.dtype)
        return jnp.full(x.shape[:-1] + (length,), fill, dtype=x.dtype)
    fill = np_fill(sentinel_min(x.dtype) if descending else sentinel_max(x.dtype),
                   x.dtype)
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)], constant_values=fill)


def stable_compact(valid: jnp.ndarray, *arrays: jnp.ndarray):
    """Stable valid-first compaction along the last axis.

    Permutes each array (same shapes as ``valid``) so the slots where
    ``valid`` is True come first, preserving relative order on both sides.
    This is the mask-based answer to sentinel aliasing: when a genuine
    extreme value ties a padding sentinel, the *mask* — not the value —
    decides what the live prefix contains, so a pad can never displace a
    real element's index or payload. On already value-sorted input whose
    invalid slots all hold the +sentinel, compaction keeps the valid
    prefix sorted (everything it moves past is a tied maximum)."""
    if valid.shape[-1] <= 1:
        # width-0/1 rows are compact by construction; the cumsum/put dance
        # below would still work for width 1 but traces three ops for a
        # no-op, and width 0 has nothing to permute at all
        return arrays if len(arrays) > 1 else arrays[0]
    v = valid.astype(jnp.int32)
    n_valid = v.sum(axis=-1, keepdims=True)
    dest = jnp.where(
        valid,
        jnp.cumsum(v, axis=-1) - 1,
        n_valid + jnp.cumsum(1 - v, axis=-1) - 1,
    )
    outs = tuple(
        jnp.put_along_axis(jnp.zeros_like(a), dest, a, axis=-1, inplace=False)
        for a in arrays
    )
    return outs if len(outs) > 1 else outs[0]


# ---------------------------------------------------------------------------
# lane primitives: every in-kernel comparison, rank and permutation
# ---------------------------------------------------------------------------
# Everything below works on (rows, lanes) arrays and lowers on the TPU
# (Mosaic) as well as in interpret mode: static lane slices, a column
# broadcast ``a[:, :, None]``, elementwise compares/selects and sums over
# the sublane axis. No gather, scatter, reversal, strided view or lane-
# splitting reshape — the TPU lowering refuses all five. A data-dependent
# permutation is a one-hot select over a (rows, S, T) tile: S source lanes
# down the sublanes, T = ``PAIR_TILE`` output lanes along the lanes.

#: output lanes per pairwise tile (one vreg row)
PAIR_TILE = 128

#: elements of one (rows, S, T) pairwise tile a kernel aims for; kernels
#: loop over row slabs (:func:`row_slab`) so a tile stays near this size
_TILE_ELEMS = 1 << 17


def to_bits(x: jnp.ndarray) -> jnp.ndarray:
    """Raw bits of ``x`` as int32 (int64 for 8-byte types): selects and
    sums over bits are exact for every value, NaN payloads and -0.0
    included."""
    d = jnp.dtype(x.dtype)
    if d == jnp.bool_:
        return x.astype(jnp.int32)
    if d.itemsize == 8:
        return x if d == jnp.int64 else jax.lax.bitcast_convert_type(x, jnp.int64)
    if d == jnp.int32:
        return x
    if d.itemsize == 4:
        return jax.lax.bitcast_convert_type(x, jnp.int32)
    if jnp.issubdtype(d, jnp.signedinteger):
        return x.astype(jnp.int32)
    itype = {2: jnp.int16, 1: jnp.int8}[d.itemsize]
    return jax.lax.bitcast_convert_type(x, itype).astype(jnp.int32)


def from_bits(b: jnp.ndarray, dtype) -> jnp.ndarray:
    """Exact inverse of :func:`to_bits`."""
    d = jnp.dtype(dtype)
    if d == jnp.bool_:
        return b != 0
    if d.itemsize in (4, 8):
        return b if b.dtype == d else jax.lax.bitcast_convert_type(b, d)
    if jnp.issubdtype(d, jnp.signedinteger):
        return b.astype(d)
    itype = {2: jnp.int16, 1: jnp.int8}[d.itemsize]
    return jax.lax.bitcast_convert_type(b.astype(itype), d)


def _rows2(a: jnp.ndarray) -> jnp.ndarray:
    return a if a.ndim == 2 else a.reshape(1, a.shape[-1])


def pair_sum(fn, q_ops, p_ops, n_out: int, *, window=None):
    """For every output lane p: ``sum over source lanes q of fn(q, p)``.

    ``q_ops`` are (rows | 1, Lq) source operands; inside ``fn`` each is a
    (rows, S, 1) column. ``p_ops`` are (rows | 1, n_out) output-side
    operands, a (rows, 1, T) row each. ``fn(qs, ps, qi, pi)`` also gets
    the source and output lane numbers (``qi`` (1, S, 1), ``pi``
    (1, 1, T)) and returns one integer array or a tuple of them; each sums
    over the source axis into a (rows, n_out) result. ``window(c0, c1)
    -> (q0, q1)`` (static) bounds the source lanes output lanes [c0, c1)
    can see; default all of them. Output lanes are tiled by
    :data:`PAIR_TILE`, so one tile is (rows, S, PAIR_TILE)."""
    q_ops = [_rows2(a) for a in q_ops]
    p_ops = [_rows2(a) for a in p_ops]
    lq = q_ops[0].shape[-1]
    tiles = []
    for c0 in range(0, n_out, PAIR_TILE):
        c1 = min(n_out, c0 + PAIR_TILE)
        q0, q1 = (0, lq) if window is None else window(c0, c1)
        qs = [a[:, q0:q1][:, :, None] for a in q_ops]
        ps = [a[:, c0:c1][:, None, :] for a in p_ops]
        qi = _iota((1, q1 - q0, 1), 1) + q0
        pi = _iota((1, 1, c1 - c0), 2) + c0
        terms = fn(qs, ps, qi, pi)
        single = not isinstance(terms, tuple)
        terms = (terms,) if single else terms
        tiles.append(tuple(jnp.sum(t, axis=1) for t in terms))
    outs = tuple(
        parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=-1)
        for parts in zip(*tiles))
    return outs[0] if single else outs


def group_window(gw: int, n_src: int, gw_out: Optional[int] = None):
    """Window for :func:`pair_sum` when lanes form consecutive groups of
    ``gw`` source lanes and ``gw_out`` output lanes (default ``gw``): an
    output tile sees the source lanes of every group it touches."""
    gw_out = gw if gw_out is None else gw_out

    def window(c0, c1):
        g0, g1 = c0 // gw_out, -(-c1 // gw_out)
        return g0 * gw, min(n_src, g1 * gw)

    return window


def _mxu_select(dest, bits, c0, c1, q0, q1):
    """One output tile of the one-hot permute on the MXU. The bits ride as
    bytes, one batched one-hot matmul per byte: a byte and a one-hot
    entry are exact even as bf16 MXU inputs and a single product per
    output lands in the f32 accumulator, so the result is exact for every
    value at any matmul precision."""
    rows = bits.shape[0]
    oh = (jnp.broadcast_to(dest[:, q0:q1], (rows, q1 - q0))[:, None, :]
          == _iota((1, c1 - c0, 1), 1) + c0).astype(jnp.float32)
    src = bits[:, q0:q1]
    acc = jnp.zeros((rows, c1 - c0), bits.dtype)
    for s in range(bits.dtype.itemsize):
        byte = ((src >> (8 * s)) & 255).astype(jnp.float32)[:, None, :]
        r = jnp.einsum("bqk,bjk->bqj", byte, oh,
                       preferred_element_type=jnp.float32)
        acc = acc | (r[:, 0, :].astype(bits.dtype) << (8 * s))
    return acc


def permute_lanes(dest: jnp.ndarray, arrays, n_out: int, *, window=None,
                  use_mxu: bool = False):
    """``out[:, dest[:, q]] = a[:, q]`` for every array ``a`` (a lane
    permutation, or an injective partial map: a source whose ``dest`` is
    outside ``[0, n_out)`` is dropped, an output lane no source maps to
    is 0). Exact for every dtype — the values move as raw bits.
    ``use_mxu`` moves them through one-hot matmuls instead of VPU
    selects; both give the same bits."""
    dest = _rows2(dest)
    bits = [to_bits(a) for a in arrays]
    if use_mxu:
        lq = bits[0].shape[-1]
        outs = []
        for b in bits:
            parts = []
            for c0 in range(0, n_out, PAIR_TILE):
                c1 = min(n_out, c0 + PAIR_TILE)
                q0, q1 = (0, lq) if window is None else window(c0, c1)
                parts.append(_mxu_select(dest, b, c0, c1, q0, q1))
            outs.append(parts[0] if len(parts) == 1
                        else jnp.concatenate(parts, axis=-1))
    else:
        def fn(qs, ps, qi, pi):
            hit = qs[0] == pi
            return tuple(jnp.where(hit, b, 0) for b in qs[1:])

        outs = pair_sum(fn, [dest, *bits], [], n_out, window=window)
    return [from_bits(o, a.dtype) for o, a in zip(outs, arrays)]


def gather_lanes(perm: jnp.ndarray, leaf: jnp.ndarray) -> jnp.ndarray:
    """In-kernel payload gather ``leaf[:, perm]`` along the lane axis.

    ``perm`` is (bt, L_out) int32 input positions, ``leaf`` (bt, L). Runs
    inside the kernel body so payload permutes never leave VMEM. Negative
    positions (top-k pad sentinels) read lane 0 — their slots are
    sentinels anyway."""
    idx = jnp.where(perm < 0, 0, perm)
    out = pair_sum(lambda qs, ps, qi, pi: jnp.where(qi == ps[0], qs[0], 0),
                   [to_bits(leaf)], [idx], idx.shape[-1])
    return from_bits(out, leaf.dtype)


def run_rank(keys, grp, run, local, *, descending: bool = False,
             window=None):
    """Rank of every lane inside its group of sorted runs.

    ``rank(p) = local(p) + #{q in p's group, other run, before p}`` where
    q is before p when its key is smaller — or equal and its run comes
    first. That is the stable merge of the group's runs (the S2MS
    comparison cloud); with one-lane runs it is the stable N-sorter
    (ties by run number). ``descending`` ranks largest first, ties by the
    *later* run first (the exact reverse of the ascending order). ``grp``
    None means one group; ``grp``/``run``/``local`` may be (1, L) static
    rows or (rows, L) data."""
    def fn(qs, ps, qi, pi):
        kq, kp = qs[0], ps[0]
        rq, rp = qs[1], ps[1]
        # boolean algebra, not a select between masks: the TPU lowering
        # cannot select between i1 vectors
        if descending:
            before = (kq > kp) | ((kq == kp) & (rq > rp))
        else:
            before = (kq < kp) | ((kq == kp) & (rq < rp))
        hit = before & (rq != rp)
        if grp is not None:
            hit = hit & (qs[2] == ps[2])
        return hit.astype(jnp.int32)

    ops = [widen(keys), run] + ([] if grp is None else [grp])
    return local + pair_sum(fn, ops, ops, keys.shape[-1], window=window)


def compact_lanes(valid: jnp.ndarray, arrays, *, use_mxu: bool = False):
    """Kernel-safe :func:`stable_compact`: lanes where ``valid`` holds move
    to the front, the rest behind them, both keeping their order."""
    v = valid.astype(jnp.int32)
    before = pair_sum(lambda qs, ps, qi, pi: jnp.where(qi < pi, qs[0], 0),
                      [v], [], v.shape[-1])
    lane = _iota(v.shape, 1)
    n_valid = jnp.sum(v, axis=-1, keepdims=True)
    dest = jnp.where(valid, before, n_valid + lane - before)
    return permute_lanes(dest, arrays, v.shape[-1], use_mxu=use_mxu)


def reverse_lanes(arrays, *, use_mxu: bool = False):
    """Reverse the lane order of (rows, L) arrays (``a[:, ::-1]``), as a
    permutation: the TPU lowering has no reversal."""
    n = arrays[0].shape[-1]
    dest = (n - 1) - _iota((1, n), 1)
    return permute_lanes(dest, arrays, n, use_mxu=use_mxu)


def row_slab(bt: int, width: int) -> int:
    """Rows a kernel processes at once so that a (rows, width, PAIR_TILE)
    pairwise tile stays near ``_TILE_ELEMS`` elements: the whole block,
    whole 8-row tiles, or single rows (a power of two dividing ``bt``) —
    the TPU loads a dynamic row offset as one row or whole 8-row tiles,
    never 2 or 4 rows."""
    rows = max(1, _TILE_ELEMS // (max(width, 1) * PAIR_TILE))
    rows = min(1 << (rows.bit_length() - 1), bt)
    while bt % rows:
        rows //= 2
    if 1 < rows < min(bt, 8):
        rows = 1
    return max(rows, 1)


def slab_lanes(arrays, bt: int, width: int):
    """The lanes a kernel that walks ``width``-wide rows in ``bt``-row
    blocks is handed: 16- and 8-bit arrays travel as their exact 32-bit
    widening when it walks single rows, because the TPU loads a dynamic
    single row only of a 32-bit block (narrow rows are packed in pairs
    and quads). The caller casts the outputs back."""
    if row_slab(bt, width) > 1 or all(
            jnp.dtype(a.dtype).itemsize >= 4 for a in arrays):
        return list(arrays)
    return [widen(a) for a in arrays]


def narrow_back(results, dtypes, want_perm: bool):
    """Undo :func:`slab_lanes` on a fused kernel's results
    ``[out, perm?, *payload outs]`` (``dtypes``: the values' then each
    payload lane's original dtype)."""
    results = list(results) if isinstance(results, (list, tuple)) else [results]
    perm = results[1:2] if want_perm else []
    rest = results[1 + int(want_perm):]
    return ([results[0].astype(dtypes[0])] + perm
            + [r.astype(d) for r, d in zip(rest, dtypes[1:])])


def for_row_slabs(in_refs, out_refs, body, rows: int) -> None:
    """Run ``body(*arrays) -> outs`` over ``rows``-row slabs of a block:
    one straight pass when the slab is the whole block, else a
    ``fori_loop`` with dynamic sublane slices, so the kernel's code and
    working set stay those of one slab."""
    bt = in_refs[0].shape[0]
    if rows >= bt:
        outs = body(*(r[...] for r in in_refs))
        for o_ref, o in zip(out_refs, outs):
            o_ref[...] = o.astype(o_ref.dtype)
        return

    def step(s, carry):
        r0 = pl.multiple_of(s * rows, rows)
        outs = body(*(r[pl.ds(r0, rows), :] for r in in_refs))
        for o_ref, o in zip(out_refs, outs):
            # widened lanes (slab_lanes) store the exact widening
            o_ref[pl.ds(r0, rows), :] = o.astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, bt // rows, step, 0)


def split_payload_lanes(payloads):
    """(B, L[, F]) payloads -> (B, L) lanes for the kernels (which move 2-D
    lanes only) and ``rebuild(outs)`` that restores each payload's
    trailing feature axis on the permuted lanes."""
    lanes, shapes = [], []
    for p in payloads:
        if p.ndim == 2:
            lanes.append(p)
            shapes.append(None)
        else:
            assert p.ndim == 3, p.shape
            lanes += [p[:, :, f] for f in range(p.shape[2])]
            shapes.append(p.shape[2])

    def rebuild(outs):
        outs, res, i = list(outs), [], 0
        for f in shapes:
            if f is None:
                res.append(outs[i])
                i += 1
            else:
                res.append(jnp.stack(outs[i:i + f], axis=-1))
                i += f
        return tuple(res)

    return lanes, rebuild


def row_block_spec(block_batch: int, width: int) -> pl.BlockSpec:
    """BlockSpec for a (B, width) lane: grid dim 0 tiles the batch, the
    lane axis rides whole (a full dimension, so any width tiles)."""
    return pl.BlockSpec((block_batch, width), lambda i: (i, 0))


def unpack_fused_results(results, bsz: int, padded: int, n_payload: int,
                         want_perm: bool):
    """Shared epilogue of the fused kernel wrappers: slice off batch
    padding and split (out, perm?, payload outs). Returns the bare ``out``
    for the classic values-only call, else ``(out, perm|None, pouts)``."""
    if not isinstance(results, (list, tuple)):
        results = [results]
    results = [r[:bsz] if padded != bsz else r for r in results]
    out = results[0]
    if n_payload == 0 and not want_perm:
        return out
    perm = results[1] if want_perm else None
    return out, perm, tuple(results[1 + (1 if want_perm else 0):])
