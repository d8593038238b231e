"""Segmented (CSR ragged) subsystem tests — PR 5.

Covers the acceptance criteria:

* ``repro.segment_sort`` / ``segment_merge`` / ``segment_topk`` /
  ``segment_argmax`` bit-identical to a per-segment ``jnp.sort`` / top-k
  reference across ragged offset patterns (empty / length-1 / prime /
  all-equal segments, NaN & ±inf keys, descending, pytree payloads), on
  both the auto route and the forced kernel path;
* each size-class bucket lowers to exactly one ``pallas_call``
  (jaxpr-verified), singleton classes to none;
* the escape hatch (``set_segmented_enabled``) reverts auto dispatch to
  the per-segment XLA reference;
* the ``kernels/common.py`` guards: ``ceil_pow2`` degenerate inputs,
  zero-width ``stable_compact`` / ``pad_tail_sorted``;
* the MoE ragged-capacity dispatch and the mixed-k serving sampler route
  through the segmented backend and stay consistent with their dense
  equivalents.
"""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import repro
from repro.segmented import set_segmented_enabled

RNG = np.random.default_rng(7)

#: ragged offset patterns: empty, length-1, prime, all-equal, mixed
OFFSET_CASES = [
    (0,),  # no segments at all
    (0, 0),  # one empty segment
    (0, 1),  # one singleton
    (0, 5),  # one tiny segment
    (0, 0, 1, 1, 2),  # empties interleaved with singletons
    (0, 7, 14, 21),  # all-equal prime lengths
    (0, 3, 3, 4, 17, 17, 64, 111),  # the kitchen sink
    (0, 13, 26, 39, 52),  # all-equal, non-pow2
    (0, 1, 2, 3, 4, 5),  # all singletons
]


def _ref_sort(x, offs, descending=False):
    parts = []
    for a, b in zip(offs, offs[1:]):
        s = np.sort(np.asarray(x[a:b]))
        parts.append(s[::-1] if descending else s)
    return np.concatenate(parts) if parts else np.asarray(x[:0])


def _collect_prims(jaxpr, names):
    for eqn in jaxpr.eqns:
        names.append(eqn.primitive.name)
        if eqn.primitive.name == "pallas_call":
            continue
        for v in eqn.params.values():
            if hasattr(v, "jaxpr"):
                _collect_prims(v.jaxpr, names)
            elif isinstance(v, (list, tuple)):
                for vi in v:
                    if hasattr(vi, "jaxpr"):
                        _collect_prims(vi.jaxpr, names)
    return names


def _n_pallas(fn, *args):
    return _collect_prims(jax.make_jaxpr(fn)(*args).jaxpr, []).count(
        "pallas_call")


# ---------------------------------------------------------------------------
# common.py guards (satellite)
# ---------------------------------------------------------------------------


def test_ceil_pow2_degenerate_guard():
    from repro.kernels.common import ceil_pow2

    assert ceil_pow2(0) == 1  # never a 0-width (or phantom 2-wide) network
    assert ceil_pow2(1) == 1
    assert [ceil_pow2(n) for n in (2, 3, 4, 5, 8, 9)] == [2, 4, 4, 8, 8, 16]


def test_stable_compact_zero_width_and_singleton():
    from repro.kernels.common import stable_compact

    empty = jnp.zeros((3, 0), jnp.float32)
    assert stable_compact(jnp.zeros((3, 0), bool), empty).shape == (3, 0)
    one = jnp.ones((2, 1), jnp.float32)
    out = stable_compact(jnp.ones((2, 1), bool), one)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(one))


def test_pad_tail_sorted_zero_width():
    from repro.kernels.common import pad_tail_sorted, sentinel_max, sentinel_min

    empty = jnp.zeros((2, 0), jnp.float32)
    up = pad_tail_sorted(empty, 4)
    assert up.shape == (2, 4)
    assert float(up[0, 0]) == sentinel_max(jnp.float32)
    down = pad_tail_sorted(jnp.zeros((2, 0), jnp.int32), 3, descending=True)
    assert int(down[0, 0]) == sentinel_min(jnp.int32)


def test_bucketer_drops_empties_and_rejects_traced_offsets():
    from repro.segmented import bucket_segments, normalize_offsets

    classes, spill = bucket_segments(np.array([0, 1, 0, 3, 8, 9]), 64)
    assert not spill
    widths = {c.width: c.seg_ids for c in classes}
    assert widths == {1: (1,), 4: (3,), 8: (4,), 16: (5,)}
    with pytest.raises(TypeError, match="static"):
        jax.jit(lambda o: normalize_offsets(o))(jnp.arange(3))
    # concrete (non-traced) arrays of any flavor are fine
    assert normalize_offsets(jnp.asarray([0, 3, 7])) == (0, 3, 7)
    assert normalize_offsets(np.asarray([0, 3, 7])) == (0, 3, 7)
    with pytest.raises(ValueError, match="non-decreasing"):
        normalize_offsets((0, 5, 3))


# ---------------------------------------------------------------------------
# bit-equality vs the per-segment reference (deterministic sweep)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("offs", OFFSET_CASES)
@pytest.mark.parametrize("backend", ["auto", "segmented"])
@pytest.mark.parametrize("descending", [False, True])
def test_segment_sort_matches_reference(offs, backend, descending):
    x = jnp.asarray(RNG.normal(size=(offs[-1],)).astype(np.float32))
    out = repro.segment_sort(x, offs, backend=backend, descending=descending)
    np.testing.assert_array_equal(
        np.asarray(out), _ref_sort(x, offs, descending))


@pytest.mark.parametrize("backend", ["auto", "segmented"])
def test_segment_sort_nan_inf(backend):
    offs = (0, 4, 4, 9, 40)
    x = RNG.normal(size=(offs[-1],)).astype(np.float32)
    x[1] = np.nan
    x[5] = np.inf
    x[6] = -np.inf
    x[20] = np.nan
    out = repro.segment_sort(jnp.asarray(x), offs, backend=backend)
    np.testing.assert_array_equal(
        np.asarray(out), _ref_sort(x, offs), err_msg="NaNs must sort last")
    outd = repro.segment_sort(jnp.asarray(x), offs, backend=backend,
                              descending=True)
    np.testing.assert_array_equal(np.asarray(outd), _ref_sort(x, offs, True))


@pytest.mark.parametrize("dtype", [jnp.int32, jnp.uint32])
@pytest.mark.parametrize("backend", ["auto", "segmented"])
def test_segment_sort_int_dtypes(dtype, backend):
    offs = (0, 3, 3, 20, 51)
    hi = np.iinfo(np.dtype(dtype)).max
    x = jnp.asarray(
        RNG.integers(0, hi, (offs[-1],), dtype=np.dtype(dtype).name))
    out = repro.segment_sort(x, offs, backend=backend)
    np.testing.assert_array_equal(np.asarray(out), _ref_sort(x, offs))


@pytest.mark.parametrize("backend", ["auto", "segmented"])
def test_segment_sort_payload_pytree(backend):
    offs = (0, 2, 2, 9, 41, 42)
    n = offs[-1]
    x = jnp.asarray(RNG.permutation(n).astype(np.int32))  # unique keys
    pay = {"emb": jnp.asarray(RNG.normal(size=(n, 3)).astype(np.float32)),
           "pos": jnp.arange(n, dtype=jnp.int32)}
    out, tree = repro.segment_sort(x, offs, backend=backend, payload=pay)
    for a, b in zip(offs, offs[1:]):
        order = np.argsort(np.asarray(x[a:b]), kind="stable")
        np.testing.assert_array_equal(np.asarray(out[a:b]),
                                      np.asarray(x[a:b])[order])
        np.testing.assert_array_equal(np.asarray(tree["emb"][a:b]),
                                      np.asarray(pay["emb"][a:b])[order])
        np.testing.assert_array_equal(np.asarray(tree["pos"][a:b]),
                                      np.asarray(pay["pos"][a:b])[order])


@pytest.mark.parametrize("backend", ["auto", "segmented"])
def test_segment_merge_ragged_pairs(backend):
    offs_a = (0, 0, 3, 10, 14, 30)
    offs_b = (0, 2, 2, 9, 30, 41)
    a = RNG.normal(size=(offs_a[-1],)).astype(np.float32)
    b = RNG.normal(size=(offs_b[-1],)).astype(np.float32)
    for o0, o1 in zip(offs_a, offs_a[1:]):
        a[o0:o1] = np.sort(a[o0:o1])
    for o0, o1 in zip(offs_b, offs_b[1:]):
        b[o0:o1] = np.sort(b[o0:o1])
    out, oo = repro.segment_merge(jnp.asarray(a), jnp.asarray(b),
                                  offs_a, offs_b, backend=backend)
    assert oo == tuple(x + y for x, y in zip(offs_a, offs_b))
    for s in range(len(offs_a) - 1):
        ref = np.sort(np.concatenate([a[offs_a[s]:offs_a[s + 1]],
                                      b[offs_b[s]:offs_b[s + 1]]]))
        np.testing.assert_array_equal(np.asarray(out[oo[s]:oo[s + 1]]), ref)


@pytest.mark.parametrize("backend", ["auto", "segmented"])
def test_segment_merge_descending_with_payload(backend):
    offs_a = (0, 4, 9)
    offs_b = (0, 6, 7)
    a = np.sort(RNG.normal(size=(9,)).astype(np.float32))[::-1].copy()
    a[:4] = np.sort(a[:4])[::-1]
    a[4:] = np.sort(a[4:])[::-1]
    b = RNG.normal(size=(7,)).astype(np.float32)
    b[:6] = np.sort(b[:6])[::-1]
    pa = jnp.arange(9, dtype=jnp.int32)
    pb = jnp.arange(7, dtype=jnp.int32) + 100
    out, tree, oo = repro.segment_merge(
        jnp.asarray(a), jnp.asarray(b), offs_a, offs_b, backend=backend,
        descending=True, payload=(pa, pb))
    for s in range(2):
        seg = np.concatenate([a[offs_a[s]:offs_a[s + 1]],
                              b[offs_b[s]:offs_b[s + 1]]])
        np.testing.assert_array_equal(np.asarray(out[oo[s]:oo[s + 1]]),
                                      np.sort(seg)[::-1])
    # payload consistency: each slot's tag resolves to its own value
    for j in range(oo[-1]):
        s = max(i for i in range(2) if oo[i] <= j)
        tag = int(tree[j])
        src = (a[offs_a[s]:offs_a[s + 1]] if tag < 100
               else b[offs_b[s]:offs_b[s + 1]])
        base = offs_a[s] if tag < 100 else offs_b[s] + 100
        assert np.float32(src[tag - base]) == np.asarray(out[j])


@pytest.mark.parametrize("backend", ["auto", "segmented"])
@pytest.mark.parametrize("descending", [True, False])
def test_segment_topk_mixed_k(backend, descending):
    offs = (0, 0, 1, 8, 15, 47, 111)
    ks = (3, 2, 5, 1, 8, 64)
    x = RNG.normal(size=(offs[-1],)).astype(np.float32)
    vals, idx, oo = repro.segment_topk(
        jnp.asarray(x), offs, ks, backend=backend, descending=descending)
    for s, (o0, o1) in enumerate(zip(offs, offs[1:])):
        cnt = min(ks[s], o1 - o0)
        assert oo[s + 1] - oo[s] == cnt
        srt = np.sort(x[o0:o1])
        ref = (srt[::-1] if descending else srt)[:cnt]
        got = np.asarray(vals[oo[s]:oo[s + 1]])
        np.testing.assert_array_equal(got, ref)
        # idx are within-segment positions that reproduce the values
        np.testing.assert_array_equal(
            x[o0:o1][np.asarray(idx[oo[s]:oo[s + 1]])], got)


@pytest.mark.parametrize("backend", ["auto", "segmented"])
def test_segment_argmax(backend):
    offs = (0, 0, 1, 8, 15, 47)
    x = RNG.normal(size=(offs[-1],)).astype(np.float32)
    v, i = repro.segment_argmax(jnp.asarray(x), offs, backend=backend)
    for s, (o0, o1) in enumerate(zip(offs, offs[1:])):
        if o1 == o0:
            assert int(i[s]) == -1
        else:
            assert int(i[s]) == int(np.argmax(x[o0:o1]))
            assert np.float32(np.max(x[o0:o1])) == np.asarray(v[s])


def test_segment_sort_spill_long_segments():
    from repro.segmented import max_class_width

    mw = max_class_width(jnp.float32)
    ln = 2 * mw + 37
    offs = (0, 5, 5 + ln, 5 + 2 * ln, 5 + 2 * ln + 9)
    x = RNG.normal(size=(offs[-1],)).astype(np.float32)
    out = repro.segment_sort(jnp.asarray(x), offs, backend="segmented")
    np.testing.assert_array_equal(np.asarray(out), _ref_sort(x, offs))
    # perm-carrying spill takes the batched XLA path but stays exact
    out2, perm = repro.segment_sort(jnp.asarray(x), offs,
                                    backend="segmented",
                                    payload=jnp.arange(offs[-1],
                                                       dtype=jnp.int32))
    np.testing.assert_array_equal(np.asarray(out2), _ref_sort(x, offs))


def test_segment_sort_spill_with_feature_dim_payload():
    # regression: the spill paths' take_along_axis must broadcast the
    # permutation over trailing feature dims ((N, F) leaves crashed)
    from repro.segmented import max_class_width

    mw = max_class_width(jnp.float32)
    offs = (0, 3, 3 + mw + 17)
    n = offs[-1]
    x = jnp.asarray(RNG.permutation(n).astype(np.float32))  # unique keys
    pay = {"emb": jnp.asarray(RNG.normal(size=(n, 3)).astype(np.float32)),
           "pos": jnp.arange(n, dtype=jnp.int32)}
    out, tree = repro.segment_sort(x, offs, backend="segmented", payload=pay)
    for a, b in zip(offs, offs[1:]):
        order = np.argsort(np.asarray(x[a:b]), kind="stable")
        np.testing.assert_array_equal(np.asarray(out[a:b]),
                                      np.asarray(x[a:b])[order])
        np.testing.assert_array_equal(np.asarray(tree["emb"][a:b]),
                                      np.asarray(pay["emb"][a:b])[order])
    # merge and topk spill loops share the broadcast helper
    ln = mw + 9
    a_v = jnp.asarray(np.sort(RNG.normal(size=(ln,)).astype(np.float32)))
    b_v = jnp.asarray(np.sort(RNG.normal(size=(ln,)).astype(np.float32)))
    pa = jnp.asarray(RNG.normal(size=(ln, 2)).astype(np.float32))
    pb = jnp.asarray(RNG.normal(size=(ln, 2)).astype(np.float32))
    out_m, tree_m, oo = repro.segment_merge(
        a_v, b_v, (0, ln), (0, ln), backend="segmented", payload=(pa, pb))
    assert tree_m.shape == (2 * ln, 2)
    vals, idx, ptree, oo2 = repro.segment_topk(
        x, offs, 5, backend="segmented",
        payload=jnp.asarray(RNG.normal(size=(n, 4)).astype(np.float32)))
    assert ptree.shape == (oo2[-1], 4)


@pytest.mark.parametrize("descending", [False, True])
def test_tie_convention_matches_between_kernel_and_reference(descending):
    # regression: descending used to mean reverse-of-stable-ascending in
    # the reference but stable-sort-of-flipped-keys in the kernels, so
    # perm/idx diverged on ties by platform. Both now use the flipped-key
    # stable convention. (Scope: stable sub-paths — widths below the
    # column-device cutover. Wider classes make no tie-order promise,
    # like the dense API without stable=True; values stay bit-identical.)
    x = jnp.asarray(np.array([1, 1, 1, 2, 2, 0, 0, 3], np.float32))
    offs = (0, 8)
    pay = jnp.arange(8, dtype=jnp.int32)
    out_k, perm_k = repro.segment_sort(x, offs, backend="segmented",
                                       descending=descending, payload=pay)
    prev = set_segmented_enabled(False)
    try:
        out_r, perm_r = repro.segment_sort(x, offs, descending=descending,
                                           payload=pay)
    finally:
        set_segmented_enabled(prev)
    np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_r))
    np.testing.assert_array_equal(np.asarray(perm_k), np.asarray(perm_r))
    vk, ik, _ = repro.segment_topk(x, offs, 3, backend="segmented",
                                   descending=descending)
    prev = set_segmented_enabled(False)
    try:
        vr, ir, _ = repro.segment_topk(x, offs, 3, descending=descending)
    finally:
        set_segmented_enabled(prev)
    np.testing.assert_array_equal(np.asarray(vk), np.asarray(vr))
    np.testing.assert_array_equal(np.asarray(ik), np.asarray(ir))


# ---------------------------------------------------------------------------
# one pallas_call per size-class bucket (jaxpr-verified acceptance)
# ---------------------------------------------------------------------------


def test_each_size_class_is_single_pallas_call():
    # classes: width 4 (two members), 16, 32; plus one singleton (no call)
    offs = (0, 3, 6, 20, 52, 53)
    x = jnp.asarray(RNG.normal(size=(offs[-1],)).astype(np.float32))
    n = _n_pallas(
        lambda v: repro.segment_sort(v, offs, backend="segmented"), x)
    assert n == 3, n


def test_singleton_class_emits_no_network():
    offs = (0, 1, 2, 3)  # all length-1: pure layout, zero launches
    x = jnp.asarray(RNG.normal(size=(3,)).astype(np.float32))
    n = _n_pallas(
        lambda v: repro.segment_sort(v, offs, backend="segmented"), x)
    assert n == 0, n
    np.testing.assert_array_equal(
        np.asarray(repro.segment_sort(x, offs, backend="segmented")),
        np.asarray(x))


def test_mixed_k_topk_equal_vocab_is_one_launch():
    # the continuous-batching case: equal segment lengths, ragged k ->
    # a single size class -> one launch for the whole batch
    b, v = 4, 64
    offs = tuple(range(0, (b + 1) * v, v))
    x = jnp.asarray(RNG.normal(size=(b * v,)).astype(np.float32))
    n = _n_pallas(
        lambda t: repro.segment_topk(t, offs, (1, 8, 3, 64),
                                     backend="segmented"), x)
    assert n == 1, n


def test_reference_route_has_no_pallas_calls():
    offs = (0, 3, 6, 20)
    x = jnp.asarray(RNG.normal(size=(20,)).astype(np.float32))
    prev = set_segmented_enabled(False)
    try:
        dec = repro.plan(repro.SortSpec(
            op="sort", lengths=(20,), batch=3, device="tpu",
            segment_offsets=((0, 3, 6, 20),)))
        assert (dec.backend, dec.detail) == ("segmented", "reference")
        n = _n_pallas(lambda t: repro.segment_sort(t, offs), x)
        assert n == 0, n
    finally:
        set_segmented_enabled(prev)


def test_plan_routes_segmented_specs():
    spec = repro.SortSpec(op="sort", lengths=(20,), batch=3, device="tpu",
                          segment_offsets=((0, 3, 6, 20),))
    dec = repro.plan(spec)
    assert (dec.backend, dec.detail) == ("segmented", "bucketed_pallas")
    cpu = repro.plan(dataclasses.replace(spec, device="cpu"))
    assert (cpu.backend, cpu.detail) == ("segmented", "reference")
    # dense backends refuse segmented specs loudly
    with pytest.raises(ValueError):
        repro.plan(dataclasses.replace(spec, backend="schedule"))
    # and the decision table carries segmented rows
    rows = repro.decision_table(device="tpu")
    seg_rows = [r for r in rows if r["segments"]]
    assert seg_rows and all(r["backend"] == "segmented" for r in seg_rows)


# ---------------------------------------------------------------------------
# class tiles: full rows are sliced, padded rows gathered
# ---------------------------------------------------------------------------


def _csr(lengths):
    return tuple(np.concatenate([[0], np.cumsum(lengths)]).astype(int)
                 .tolist())


#: (lengths, ks): adjacent unpadded spill rows; unpadded spill rows with
#: short segments between them; a padded pow2 class (width 128)
TILE_CASES = {
    "adjacent_spill": ((1500,) * 4, (5, 1, 64, 20)),
    "spaced_spill": ((1500, 7, 1500, 3, 1500), (8, 2, 50, 3, 1)),
    "padded_class": ((100, 77, 120), (4, 9, 1)),
}


@pytest.mark.parametrize("case", sorted(TILE_CASES))
def test_class_tiles_bit_identical_to_reference(case):
    from repro.segmented import segment_sort_impl, segment_topk_impl

    lengths, ks = TILE_CASES[case]
    offs = _csr(lengths)
    n = offs[-1]
    x = jnp.asarray(RNG.permutation(n).astype(np.float32))  # unique keys
    pay = jnp.asarray(RNG.normal(size=(n, 2)).astype(np.float32))
    for impl, kw in ((segment_topk_impl, {"k": ks}),
                     (segment_sort_impl, {"want_perm": True})):
        got = impl(x, offs, payload=pay, use_kernel=True, interpret=True,
                   **kw)
        ref = impl(x, offs, payload=pay, use_kernel=False, **kw)
        for g, r in zip(got, ref):
            if isinstance(r, tuple):  # topk's out_offsets
                assert g == r
            else:
                np.testing.assert_array_equal(np.asarray(g), np.asarray(r))


def test_full_rows_lower_without_tile_gather():
    import re

    import repro.obs as obs
    from repro.obs import metrics
    from repro.segmented import segment_topk_impl

    rows, v = 4, 2000
    offs = tuple(range(0, (rows + 1) * v, v))
    x = jnp.asarray(RNG.normal(size=(rows * v,)).astype(np.float32))
    prev = obs.set_enabled(True)
    metrics.reset()
    try:
        hlo = jax.jit(lambda t: segment_topk_impl(
            t, offs, (1, 8, 50, 3), use_kernel=True)[:2]).lower(x).as_text()
        sliced = metrics.counter("segmented.rows_sliced").value(
            op="segment_topk")
        gathered = metrics.counter("segmented.rows_gathered").value(
            op="segment_topk")
    finally:
        metrics.reset()
        obs.set_enabled(prev)
    tile_gathers = [ln for ln in hlo.splitlines()
                    if "gather" in ln
                    and re.search(rf"-> tensor<{rows}x{v}x", ln)]
    assert not tile_gathers, tile_gathers
    assert (sliced, gathered) == (rows, 0)


# ---------------------------------------------------------------------------
# hypothesis ragged sweeps
# ---------------------------------------------------------------------------


try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover
    HAVE_HYPOTHESIS = False


if HAVE_HYPOTHESIS:

    @st.composite
    def _offsets(draw, max_segments=7, max_len=33):
        lens = draw(st.lists(st.integers(0, max_len), min_size=0,
                             max_size=max_segments))
        offs = [0]
        for ln in lens:
            offs.append(offs[-1] + ln)
        return tuple(offs)

    @given(data=st.data())
    @settings(max_examples=25, deadline=None)
    def test_segment_sort_hypothesis_sweep(data):
        offs = data.draw(_offsets())
        descending = data.draw(st.booleans())
        backend = data.draw(st.sampled_from(["auto", "segmented"]))
        use_special = data.draw(st.booleans())
        x = RNG.normal(size=(offs[-1],)).astype(np.float32)
        if use_special and offs[-1]:
            spots = RNG.integers(0, offs[-1], size=min(4, offs[-1]))
            x[spots] = RNG.choice(
                [np.nan, np.inf, -np.inf]).astype(np.float32)
        out = repro.segment_sort(jnp.asarray(x), offs, backend=backend,
                                 descending=descending)
        np.testing.assert_array_equal(np.asarray(out),
                                      _ref_sort(x, offs, descending))

    @given(data=st.data())
    @settings(max_examples=20, deadline=None)
    def test_segment_topk_hypothesis_sweep(data):
        offs = data.draw(_offsets())
        n_segs = len(offs) - 1
        ks = tuple(data.draw(st.integers(0, 40)) for _ in range(n_segs))
        backend = data.draw(st.sampled_from(["auto", "segmented"]))
        x = RNG.normal(size=(offs[-1],)).astype(np.float32)
        vals, idx, oo = repro.segment_topk(jnp.asarray(x), offs, ks,
                                           backend=backend)
        for s, (o0, o1) in enumerate(zip(offs, offs[1:])):
            cnt = min(ks[s], o1 - o0)
            assert oo[s + 1] - oo[s] == cnt
            np.testing.assert_array_equal(
                np.asarray(vals[oo[s]:oo[s + 1]]),
                np.sort(x[o0:o1])[::-1][:cnt])

    @given(data=st.data())
    @settings(max_examples=20, deadline=None)
    def test_segment_merge_hypothesis_sweep(data):
        offs_a = data.draw(_offsets(max_segments=5, max_len=21))
        lens_b = tuple(data.draw(st.integers(0, 21))
                       for _ in range(len(offs_a) - 1))
        offs_b = (0,) + tuple(np.cumsum(lens_b).tolist())
        backend = data.draw(st.sampled_from(["auto", "segmented"]))
        a = RNG.normal(size=(offs_a[-1],)).astype(np.float32)
        b = RNG.normal(size=(offs_b[-1],)).astype(np.float32)
        for o0, o1 in zip(offs_a, offs_a[1:]):
            a[o0:o1] = np.sort(a[o0:o1])
        for o0, o1 in zip(offs_b, offs_b[1:]):
            b[o0:o1] = np.sort(b[o0:o1])
        out, oo = repro.segment_merge(jnp.asarray(a), jnp.asarray(b),
                                      offs_a, offs_b, backend=backend)
        for s in range(len(offs_a) - 1):
            ref = np.sort(np.concatenate([a[offs_a[s]:offs_a[s + 1]],
                                          b[offs_b[s]:offs_b[s + 1]]]))
            np.testing.assert_array_equal(
                np.asarray(out[oo[s]:oo[s + 1]]), ref)


# ---------------------------------------------------------------------------
# call-site integration: MoE ragged capacities + mixed-k serving
# ---------------------------------------------------------------------------


def _moe_cfg(moe):
    from repro.configs.base import ModelConfig

    return ModelConfig(name="t", family="moe", n_layers=1, d_model=16,
                       n_heads=2, n_kv_heads=2, d_ff=32, vocab_size=64,
                       moe=moe)


def test_moe_uniform_ragged_capacities_bit_identical():
    from repro.configs.base import MoEConfig
    from repro.models.moe import moe_apply, moe_init

    base = MoEConfig(n_experts=4, top_k=2, d_expert=8, router_block=4,
                     capacity_factor=8.0, dispatch="sorted")
    cfg_u = _moe_cfg(base)
    t = 12
    x = jnp.asarray(RNG.normal(size=(1, t, 16)).astype(np.float32))
    p, _ = moe_init(jax.random.PRNGKey(1), cfg_u)
    y_u = moe_apply(p, x, cfg_u)
    cap = int(np.ceil(t * 2 / 4 * 8.0))
    cap = max(4, cap + (-cap) % 4)
    cfg_r = _moe_cfg(dataclasses.replace(base,
                                         expert_capacities=(cap,) * 4))
    y_r = moe_apply(p, x, cfg_r)
    np.testing.assert_array_equal(np.asarray(y_u), np.asarray(y_r))


def test_moe_ragged_capacities_drop_overflow_per_expert():
    from repro.configs.base import MoEConfig
    from repro.models.moe import moe_apply, moe_init

    base = MoEConfig(n_experts=4, top_k=2, d_expert=8, router_block=4,
                     capacity_factor=8.0, dispatch="scatter")
    x = jnp.asarray(RNG.normal(size=(1, 10, 16)).astype(np.float32))
    p, _ = moe_init(jax.random.PRNGKey(2), cfg := _moe_cfg(base))
    y_full = moe_apply(p, x, cfg)
    # big ragged capacities admit every token -> equals the uniform path
    cfg_big = _moe_cfg(dataclasses.replace(base,
                                           expert_capacities=(40,) * 4))
    np.testing.assert_array_equal(np.asarray(y_full),
                                  np.asarray(moe_apply(p, x, cfg_big)))
    # tiny ragged capacities still produce finite output of the right shape
    cfg_tiny = _moe_cfg(dataclasses.replace(base,
                                            expert_capacities=(4, 8, 4, 16)))
    y_tiny = moe_apply(p, x, cfg_tiny)
    assert y_tiny.shape == y_full.shape
    assert bool(jnp.isfinite(y_tiny).all())


def test_moe_sorted_dispatch_hatch_equivalence():
    # the oblivious grouping sort routes through segment_sort on TPU when
    # the escape hatch is open (executor elsewhere); toggling the hatch
    # must be output-invariant on every platform
    from repro.configs.base import MoEConfig
    from repro.models.moe import moe_apply, moe_init

    base = MoEConfig(n_experts=4, top_k=2, d_expert=8, router_block=4,
                     capacity_factor=8.0, dispatch="sorted")
    cfg = _moe_cfg(base)
    x = jnp.asarray(RNG.normal(size=(1, 8, 16)).astype(np.float32))
    p, _ = moe_init(jax.random.PRNGKey(3), cfg)
    y_seg = moe_apply(p, x, cfg)
    prev = set_segmented_enabled(False)
    try:
        y_ref = moe_apply(p, x, cfg)
    finally:
        set_segmented_enabled(prev)
    np.testing.assert_array_equal(np.asarray(y_seg), np.asarray(y_ref))


def test_moe_grouping_sort_kernel_route_matches_executor():
    # the exact sort the TPU route runs (forced segmented kernel over the
    # composite grouping keys, interpret mode here) must agree with the
    # schedule-executor sort the other platforms keep
    n = 24
    flat_e = jnp.asarray(RNG.integers(0, 4, (n,)), jnp.int32)
    keys = flat_e * n + jnp.arange(n, dtype=jnp.int32)
    pos = jnp.arange(n, dtype=jnp.int32)
    out_k, perm_k = repro.segment_sort(keys, (0, n), payload=pos,
                                       backend="segmented")
    out_s, perm_s = repro.sort(keys, payload=pos, backend="schedule")
    np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_s))
    np.testing.assert_array_equal(np.asarray(perm_k), np.asarray(perm_s))


def test_sample_topk_ragged_matches_uniform():
    from repro.serving.sample import sample_topk

    logits = jnp.asarray(RNG.normal(size=(4, 64)).astype(np.float32))
    key = jax.random.PRNGKey(0)
    uniform = sample_topk(key, logits, k=8)
    ragged = sample_topk(key, logits, k=(8, 8, 8, 8))
    np.testing.assert_array_equal(np.asarray(uniform), np.asarray(ragged))
    # per-request k=1 rows are the argmax; larger-k rows draw from their
    # own candidate prefix only
    mixed = sample_topk(key, logits, k=(1, 1, 16, 64), temperature=0.25)
    np.testing.assert_array_equal(
        np.asarray(mixed[:2]), np.asarray(jnp.argmax(logits[:2], -1)))
    for r in (2, 3):
        k_r = (1, 1, 16, 64)[r]
        top = set(np.argsort(np.asarray(logits[r]))[::-1][:k_r].tolist())
        assert int(mixed[r]) in top


def test_serve_config_accepts_per_request_topk():
    from repro.serving.engine import ServeConfig

    sc = ServeConfig(top_k=(4, 8, 16))
    assert tuple(sc.top_k) == (4, 8, 16)
