"""Pluggable comparator-network layer tests (PR 8).

Every registered family (LOMS column device, single-stage S2MS,
3-periodic, Batcher bitonic) proves correct by the 0-1 principle: merge
programs lift into ``core.networks`` Schedules and run the complete
``validate_01_merge`` sweep at every emitted width; sort programs
compose into one Schedule where the levels allow (loms / s2ms) and take
an exhaustive executor-level 2^w 0-1 sweep otherwise. Bit-equality of
the kernel wrappers against lax covers NaN/±inf, descending, and payload
lanes for every family — as a deterministic grid always, and as
hypothesis sweeps when hypothesis is installed. The divisor fix for
``pick_merge_cols`` is regression-tested against the paper's
C* = sqrt(m*n/(m+n)) optimum, and an AST scan enforces the registry-only
rule: no kernel or streaming module imports a family generator directly.
"""
from __future__ import annotations

import ast
import math
import pathlib

import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # the deterministic grids below still run
    HAVE_HYPOTHESIS = False

from repro.core.networks import validate_01_merge, validate_01_sort
from repro.networks import (
    PERIODIC3_MAX_WIDTH,
    capable_families,
    divisor_cols,
    family_names,
    merge_program,
    merge_runs,
    pick_merge_cols,
    program_to_schedule,
    run_sort_program,
    sort_program,
    sort_program_to_schedule,
)

FAMILIES = ("loms", "s2ms", "periodic3", "bitonic")

#: every family's emitted merge widths under test — equal, ragged-divisor,
#: coprime (s2ms/periodic3), and non-equal pow2-total (bitonic) shapes
MERGE_SHAPES = {
    "loms": [(1, 1), (4, 4), (7, 7), (8, 8), (12, 9), (16, 16), (32, 32)],
    "s2ms": [(1, 1), (4, 4), (7, 5), (8, 8), (12, 9), (16, 16)],
    "periodic3": [(1, 1), (3, 5), (4, 4), (8, 8), (16, 16), (32, 32)],
    "bitonic": [(1, 1), (1, 7), (3, 5), (4, 4), (8, 8), (16, 16), (32, 32)],
}

RNG = np.random.default_rng(0)


def test_builtin_families_registered():
    assert set(FAMILIES) <= set(family_names())


# ---------------------------------------------------------------------------
# 0-1-principle validation (complete proofs, per family, per width)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "family,shape",
    [(f, s) for f in FAMILIES for s in MERGE_SHAPES[f]],
    ids=lambda v: str(v).replace(" ", ""),
)
def test_merge_program_01_valid(family, shape):
    m, n = shape
    sched = program_to_schedule(merge_program(family, m, n))
    assert validate_01_merge(sched, (m, n)), (family, shape)


@pytest.mark.parametrize("family", ("loms", "s2ms"))
@pytest.mark.parametrize("width", (8, 16))
def test_sort_program_01_valid_composable(family, width):
    # below the column-device cutover every loms/s2ms level is a depth-1
    # group merge, so the whole tree composes into one Schedule and the
    # exhaustive 0-1 sort validator applies to the composed network
    sched = sort_program_to_schedule(sort_program(family, width))
    assert validate_01_sort(sched), (family, width)


@pytest.mark.parametrize("family", FAMILIES)
def test_sort_executor_01_exhaustive(family):
    # executor-level complete proof at w=8: all 2^8 0-1 rows through
    # run_sort_program must come out ascending (covers the pair families,
    # whose levels don't compose into a single Schedule)
    w = 8
    prog = sort_program(family, w)
    pats = ((np.arange(2 ** w)[:, None] >> np.arange(w)[None, :]) & 1)
    keys, _ = run_sort_program(prog, jnp.asarray(pats, jnp.int32), None,
                               False)
    out = np.asarray(keys)
    assert (np.diff(out, axis=-1) >= 0).all(), family


def test_capability_gates():
    # bitonic needs a pow2 total; periodic3 is capped by construction cost
    assert "bitonic" in capable_families("merge2", (3, 5))
    assert "bitonic" not in capable_families("merge2", (3, 4))
    assert "periodic3" not in capable_families(
        "merge2", (PERIODIC3_MAX_WIDTH, PERIODIC3_MAX_WIDTH))
    for lens in ((3, 4), (3, 5), (8, 8)):
        assert "loms" in capable_families("merge2", lens)
        assert "s2ms" in capable_families("merge2", lens)


# ---------------------------------------------------------------------------
# pick_merge_cols: true divisors + the paper's C* optimum
# ---------------------------------------------------------------------------


def test_divisor_cols_are_actual_common_divisors():
    for m, n in ((12, 9), (7, 7), (18, 12), (512, 512), (7, 5)):
        cols = divisor_cols(m, n)
        assert all(m % c == 0 and n % c == 0 and c >= 2 for c in cols)
        g = math.gcd(m, n)
        assert set(cols) == {c for c in range(2, g + 1) if g % c == 0}


def test_pick_merge_cols_nearest_cstar():
    # the old hardcoded (2, 4, 8, 16) grid missed non-pow2 divisors and
    # every column count past 16; the divisor rule lands on the cost
    # optimum C* = sqrt(m*n/(m+n)) for each shape
    for m, n, expect in (
        (512, 512, 16),   # C* = 16 exactly
        (7, 7, 7),        # gcd divisor 7: invisible to the pow2 grid
        (12, 9, 3),       # non-pow2 divisor
        (7, 5, 1),        # coprime: no common column, single S2MS
    ):
        assert pick_merge_cols(m, n) == expect, (m, n)
    for m, n in ((24, 24), (36, 24), (128, 64), (64, 64), (512, 512)):
        cstar = math.sqrt(m * n / (m + n))
        picked = pick_merge_cols(m, n)
        assert all(
            abs(picked - cstar) <= abs(c - cstar) for c in divisor_cols(m, n))


# ---------------------------------------------------------------------------
# bit-equality vs lax (deterministic grid + hypothesis sweeps)
# ---------------------------------------------------------------------------


def _with_specials(shape):
    base = RNG.standard_normal(shape)
    m = RNG.random(shape)
    base = np.where(m < 0.2, np.nan, base)
    base = np.where((m >= 0.2) & (m < 0.35), np.inf, base)
    base = np.where((m >= 0.35) & (m < 0.5), -np.inf, base)
    return base.astype(np.float32)


def _check_merge_bits(family, m, n, descending):
    from repro.kernels.loms_merge import loms_merge2_pallas

    a = np.sort(_with_specials((3, m)), -1)
    b = np.sort(_with_specials((3, n)), -1)
    ref = np.sort(np.concatenate([a, b], -1), -1)  # NaNs last, like encode
    if descending:
        a, b, ref = a[:, ::-1], b[:, ::-1], ref[:, ::-1]
    n_cols = max(pick_merge_cols(m, n), 1) if family == "loms" else 2
    out = loms_merge2_pallas(
        jnp.asarray(a), jnp.asarray(b), network=family, n_cols=n_cols,
        block_batch=1, use_mxu=False, key_dtype="float32",
        descending=descending, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), ref)


def _check_sort_bits(family, n, descending):
    from repro.kernels.sort import loms_sort_pallas

    x = _with_specials((2, n))
    ref = np.sort(x, -1)
    if descending:
        ref = ref[:, ::-1]
    out = loms_sort_pallas(
        jnp.asarray(x), network=family, block_batch=1, use_mxu=False,
        key_dtype="float32", descending=descending, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), ref)


def _check_sort_payload(family, n):
    # tie-safe payload check for the non-stable families: the returned
    # permutation must reproduce both the values and every payload lane
    # by one gather from the raw input (no stable-argsort assumption)
    from repro.kernels.sort import loms_sort_pallas

    x = np.asarray(
        RNG.integers(0, 4, (2, n)), np.float32)  # duplicates guaranteed
    pay = np.arange(2 * n, dtype=np.int32).reshape(2, n)
    out, perm, (pout,) = loms_sort_pallas(
        jnp.asarray(x), (jnp.asarray(pay),), network=family, block_batch=1,
        use_mxu=False, want_perm=True, interpret=True)
    out, perm, pout = np.asarray(out), np.asarray(perm), np.asarray(pout)
    np.testing.assert_array_equal(out, np.sort(x, -1))
    np.testing.assert_array_equal(np.take_along_axis(x, perm, -1), out)
    np.testing.assert_array_equal(np.take_along_axis(pay, perm, -1), pout)


@pytest.mark.parametrize("descending", (False, True))
@pytest.mark.parametrize(
    "family,shape",
    [("loms", (8, 8)), ("loms", (12, 9)), ("s2ms", (7, 5)),
     ("s2ms", (16, 16)), ("periodic3", (3, 5)), ("periodic3", (8, 8)),
     ("bitonic", (3, 5)), ("bitonic", (16, 16))],
    ids=lambda v: str(v).replace(" ", ""),
)
def test_merge_bit_equality_grid(family, shape, descending):
    _check_merge_bits(family, *shape, descending)


@pytest.mark.parametrize("descending", (False, True))
@pytest.mark.parametrize("family", FAMILIES)
def test_sort_bit_equality_grid(family, descending):
    for n in (2, 5, 24):
        _check_sort_bits(family, n, descending)


@pytest.mark.parametrize("family", FAMILIES)
def test_sort_payload_rides_actual_permutation(family):
    for n in (4, 9, 16):
        _check_sort_payload(family, n)


if HAVE_HYPOTHESIS:

    def _family_merge_shape(data, family):
        if family == "bitonic":
            total = data.draw(st.sampled_from((8, 16, 32)))
            m = data.draw(st.integers(1, total - 1))
            return m, total - m
        m, n = data.draw(st.integers(1, 16)), data.draw(st.integers(1, 16))
        if family == "periodic3" and (m + n) % 2:
            # the 3-periodic family merges even totals only (its capability
            # gate, test_capability_gates); draw a shape it supports
            n = n + 1 if n < 16 else n - 1
        return m, n

    @given(data=st.data())
    @settings(max_examples=20, deadline=None)
    def test_merge_bit_equality_hypothesis(data):
        family = data.draw(st.sampled_from(("s2ms", "periodic3", "bitonic")))
        m, n = _family_merge_shape(data, family)
        _check_merge_bits(family, m, n, data.draw(st.booleans()))

    @given(data=st.data())
    @settings(max_examples=15, deadline=None)
    def test_sort_bit_equality_hypothesis(data):
        _check_sort_bits(data.draw(st.sampled_from(FAMILIES)),
                         data.draw(st.integers(2, 24)),
                         data.draw(st.booleans()))


# ---------------------------------------------------------------------------
# payload consistency at the program level (all families, one sweep)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("family", FAMILIES)
def test_merge_runs_payload_tracks_values(family):
    m, n = (8, 8)
    a = np.sort(RNG.integers(0, 6, (4, m)), -1).astype(np.int32)
    b = np.sort(RNG.integers(0, 6, (4, n)), -1).astype(np.int32)
    prog = merge_program(family, m, n)
    pa = np.arange(m, dtype=np.int32)[None].repeat(4, 0)
    pb = (np.arange(n, dtype=np.int32) + m)[None].repeat(4, 0)
    vals, pos = merge_runs(prog, jnp.asarray(a), jnp.asarray(b),
                           payload=(jnp.asarray(pa), jnp.asarray(pb)),
                           use_mxu=False)
    vals, pos = np.asarray(vals), np.asarray(pos)
    cat = np.concatenate([a, b], -1)
    np.testing.assert_array_equal(vals, np.sort(cat, -1))
    np.testing.assert_array_equal(np.take_along_axis(cat, pos, -1), vals)


# ---------------------------------------------------------------------------
# registry-only enforcement: kernels execute programs, never generators
# ---------------------------------------------------------------------------

#: modules no kernel/streaming file may import: the family generators
#: themselves (the networks registry is the only door) and the core LOMS
#: schedule builders the generators wrap
_GENERATOR_MODULES = ("repro.core.loms", "repro.networks.families")


def _forbidden_imports(path: pathlib.Path):
    tree = ast.parse(path.read_text())
    hits = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            hits += [a.name for a in node.names
                     if a.name.startswith(_GENERATOR_MODULES)]
        elif isinstance(node, ast.ImportFrom) and node.module:
            if node.module.startswith(_GENERATOR_MODULES):
                hits.append(node.module)
            if node.module == "repro.core":
                hits += [f"repro.core.{a.name}" for a in node.names
                         if a.name == "loms"]
            if node.module == "repro.networks":
                hits += [f"repro.networks.{a.name}" for a in node.names
                         if a.name == "families"]
    return hits


def test_kernels_import_registry_not_generators():
    src = pathlib.Path(__file__).resolve().parents[1] / "src" / "repro"
    scanned = 0
    for sub in ("kernels", "streaming"):
        for path in sorted((src / sub).glob("*.py")):
            scanned += 1
            assert not _forbidden_imports(path), (
                f"{path} imports a network family generator directly; "
                "kernels must execute registry-provided programs")
    assert scanned >= 10  # the rule actually covered the kernel layer
