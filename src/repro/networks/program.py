"""Merge-step program IR: the lowered, kernel-friendly sibling of
``core.networks.Schedule``.

A :class:`MergeProgram` describes one oblivious 2-run merge as either

* ``kind='columns'`` — the paper's column device family: ``n_cols == 1``
  is the single-stage S2MS rank-merge; ``n_cols == C > 1`` is the LOMS
  UP-m/DN-n device (stage 1: C strided-column S2MS merges, stage 2: row
  rank-sorts of the (R, C) stack); or
* ``kind='pairs'`` — a sequence of compare-exchange
  :class:`PairStage`\\ s over the concatenated runs (optionally with the
  hi run reversed on entry), which expresses Batcher bitonic halvers and
  periodic brick/reflect networks.

Programs are frozen trace-time constants built by the family generators
in :mod:`repro.networks.families` and handed to kernels through
:mod:`repro.networks.registry` — kernels never import a generator
directly, so tie-order and cutover behavior live in exactly one place.
The executors here (:func:`merge_runs`, :func:`run_sort_program`) are
plain ``jnp`` on the last axis built from the lane primitives of
:mod:`repro.kernels.common` (ranks, one-hot permutes, static rolls) —
safe inside Pallas kernel bodies in interpret mode and on the TPU (no
captured numpy constants, gathers, reversals or strided views).

:func:`program_to_schedule` lifts a program back into the validated
``Schedule`` IR so the 0-1-principle checkers in ``core.networks`` apply
to every family at every emitted width.

Tie caution (same contract as the old ``merge2_cols``): only the
``columns``/``n_cols == 1`` S2MS program is a *stable* merge (lo run
wins ties). Column devices and pair networks make no cross-run tie-order
promise — callers whose sentinels can tie genuine values must resolve
validity by mask (``stable_compact``), never by position.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax.numpy as jnp

from repro.kernels.common import (_iota, group_window, permute_lanes, run_rank,
                                  widen)

__all__ = [
    "PairStage",
    "MergeProgram",
    "SortProgram",
    "merge_runs",
    "run_sort_program",
    "program_to_schedule",
    "sort_program_to_schedule",
]


@dataclasses.dataclass(frozen=True)
class PairStage:
    """One compare-exchange stage over the working vector of length L.

    kind='xor'       — partner lanes ``i`` and ``i ^ d`` (the Batcher
                       halver stride; requires ``2*d | L``).
    kind='reflect'   — partner lanes ``i`` and ``L-1-i`` (the periodic
                       network's folding stage; requires L even).
    kind='brick_odd' — odd brick: pairs (1,2), (3,4), ... (L-3,L-2); the
                       ends idle (requires L even).

    Every stage is a standard comparator set: the min lands on the
    lower-indexed lane.
    """

    kind: str
    d: int = 1

    def __post_init__(self):
        assert self.kind in ("xor", "reflect", "brick_odd"), self.kind
        assert self.d >= 1


@dataclasses.dataclass(frozen=True)
class MergeProgram:
    """A lowered 2-run merge: ``(m, n) -> m + n`` along the last axis."""

    family: str
    m: int
    n: int
    kind: str  # 'columns' | 'pairs'
    n_cols: int = 1
    reverse_hi: bool = False
    stages: Tuple[PairStage, ...] = ()

    def __post_init__(self):
        assert self.kind in ("columns", "pairs"), self.kind
        if self.kind == "columns" and self.n_cols > 1:
            assert self.m % self.n_cols == 0 and self.n % self.n_cols == 0, (
                self.m, self.n, self.n_cols)

    @property
    def total(self) -> int:
        return self.m + self.n


@dataclasses.dataclass(frozen=True)
class SortProgram:
    """A pow2-width merge-tree sort: ``levels[i]`` merges run pairs of
    length ``2**i`` (so ``levels[i].m == levels[i].n == 2**i``)."""

    family: str
    width: int
    levels: Tuple[MergeProgram, ...] = ()

    def __post_init__(self):
        run = 1
        for mp in self.levels:
            assert mp.m == run and mp.n == run, (mp.m, mp.n, run)
            run *= 2
        assert run == max(self.width, 1), (self.width, len(self.levels))


# ---------------------------------------------------------------------------
# Executors (kernel-safe jnp)
# ---------------------------------------------------------------------------


def _partner(x, lane_bit, shift: int):
    """Value of lane ``i + shift`` where ``lane_bit`` is False and of lane
    ``i - shift`` where it is True (static rolls: slices + a concat)."""
    return jnp.where(lane_bit, jnp.roll(x, shift, axis=-1),
                     jnp.roll(x, -shift, axis=-1))


def _exchange(arrays, partner_of, lower, active):
    """Compare-exchange every active lane with its partner: the min lands
    on the lower lane. Both lanes of a pair evaluate the same strict
    comparison, so the swap is self-consistent under ties, and payload
    lanes follow the key swap."""
    keys = arrays[0]
    other = partner_of(keys)
    k, o = widen(keys), widen(other)
    swap = active & ((lower & (k > o)) | (~lower & (o > k)))
    out = [jnp.where(swap, other, keys)]
    for a in arrays[1:]:
        out.append(jnp.where(swap, partner_of(a), a))
    return out


def _pair_stage(st: PairStage, arrays, gw: int, use_mxu: bool):
    """One compare-exchange stage over groups of ``gw`` lanes."""
    width = arrays[0].shape[-1]
    lane = _iota((1, width), 1)
    t = lane % gw
    if st.kind == "xor":
        upper = (t & st.d) != 0
        return _exchange(arrays, lambda a: _partner(a, upper, st.d),
                         ~upper, True)
    if st.kind == "reflect":
        # lanes i and gw-1-i: the partner value is the group's reversal
        dest = lane - t + (gw - 1 - t)

        def partner_of(a):
            return permute_lanes(dest, [a], width, use_mxu=use_mxu,
                                 window=group_window(gw, width))[0]

        return _exchange(arrays, partner_of, t < gw // 2, True)
    assert st.kind == "brick_odd"
    if gw <= 2:
        return arrays
    lower = (t % 2) == 1
    active = (t >= 1) & (t <= gw - 2)
    return _exchange(arrays, lambda a: _partner(a, ~lower, 1), lower, active)


def _merge_level(prog: MergeProgram, arrays, gw: int, use_mxu: bool):
    """Run ``prog`` on every group of ``gw = m + n`` lanes, each laid out
    ``[lo | hi]``; ``arrays[0]`` are the keys, the rest ride along. One
    lane-flat pass for all groups: no reshape, strided view or reversal
    reaches the kernel, so the same code runs in interpret mode and on
    the TPU."""
    m, n = prog.m, prog.n
    assert gw == m + n, (gw, prog)
    keys = arrays[0]
    width = keys.shape[-1]
    lane = _iota((1, width), 1)
    single = gw == width
    t = lane if single else lane % gw
    base = lane - t
    hi = t >= m
    win = None if single else group_window(gw, width)
    if prog.kind == "pairs":
        out = list(arrays)
        if prog.reverse_hi:
            rev = jnp.where(hi, base + m + (gw - 1 - t), lane)
            out = permute_lanes(rev, out, width, window=win, use_mxu=use_mxu)
        for st in prog.stages:
            out = _pair_stage(st, out, gw, use_mxu)
        return out
    c_ = prog.n_cols
    grp = None if single else lane // gw
    if c_ <= 1 or m % c_ or n % c_:
        # S2MS: one stable rank-merge of the two runs (lo wins ties)
        run = hi.astype(jnp.int32)
        rank = run_rank(keys, grp, run, t - run * m, window=win)
        return permute_lanes(base + rank, arrays, width, window=win,
                             use_mxu=use_mxu)
    # the paper's UP-m/DN-n column device, in lane coordinates: lo lane i
    # sits in column i % C, hi lane j in column (C-1-j) % C, each at row
    # position //C. Stage 1 merges every column (the hi run wins ties, as
    # the column device lists it first); the merged position is the row.
    # Stage 2 ranks each row of C values, ties by column; the output is
    # the row-major flatten.
    i = jnp.where(hi, t - m, t)
    col = jnp.where(hi, (c_ - 1) - i % c_, i % c_)
    g0 = 0 if single else grp
    row = run_rank(keys, g0 * c_ + col, jnp.where(hi, 0, 1), i // c_,
                   window=win)
    rank2 = run_rank(keys, g0 * (gw // c_) + row, col, 0, window=win)
    return permute_lanes(base + row * c_ + rank2, arrays, width, window=win,
                         use_mxu=use_mxu)


def merge_runs(
    prog: MergeProgram,
    lo: jnp.ndarray,
    hi: jnp.ndarray,
    *,
    payload: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
    use_mxu: bool = True,
):
    """Execute one merge program on ascending runs ``lo``/``hi`` (last
    axis, any leading shape). With ``payload=(plo, phi)`` returns
    ``(vals, pvals)``."""
    assert lo.shape[-1] == prog.m and hi.shape[-1] == prog.n, (
        lo.shape, hi.shape, prog)
    lead = lo.shape[:-1]
    total = prog.m + prog.n
    arrays = [jnp.concatenate([lo, hi], axis=-1).reshape(-1, total)]
    if payload is not None:
        arrays.append(jnp.concatenate(payload, axis=-1).reshape(-1, total))
    out = [o.reshape(lead + (total,))
           for o in _merge_level(prog, arrays, total, use_mxu)]
    return (out[0], out[1]) if payload is not None else out[0]


def run_sort_program(prog: SortProgram, keys: jnp.ndarray,
                     pos: Optional[jnp.ndarray], use_mxu: bool):
    """Trace-time-unrolled merge-tree sort over pow2-width ``(bt, w)``
    rows, optionally threading an int32 position lane through every
    permute. The one home for the tree loop — the fused dense sort
    (kernels/sort.py) and the segmented class sort share it, so level
    structure (e.g. the LOMS column-device cutover, chosen by the family
    generator) and tie-order behavior can never diverge between them.
    Returns ``(keys, pos)``."""
    w = prog.width
    assert keys.shape[-1] == w, (keys.shape, w)
    arrays = [keys] if pos is None else [keys, pos]
    for mp in prog.levels:
        arrays = _merge_level(mp, arrays, 2 * mp.m, use_mxu)
    return arrays[0], (None if pos is None else arrays[1])


# ---------------------------------------------------------------------------
# Lifting back into the validated Schedule IR (for 0-1 checks / metrics)
# ---------------------------------------------------------------------------


def _pair_stage_to_groups(st: PairStage, L: int):
    from repro.core.networks import Group

    if st.kind == "xor":
        return tuple(
            Group(idx=(base + k, base + k + st.d))
            for base in range(0, L, 2 * st.d) for k in range(st.d))
    if st.kind == "reflect":
        return tuple(Group(idx=(i, L - 1 - i)) for i in range(L // 2))
    return tuple(Group(idx=(i, i + 1)) for i in range(1, L - 2, 2))


def program_to_schedule(mp: MergeProgram):
    """Lift a merge program into a ``core.networks.Schedule`` so the
    0-1-principle validators and depth/comparator metrics apply."""
    from repro.core.networks import Group, Schedule, Stage

    m, n, size = mp.m, mp.n, mp.total
    ident = tuple(range(size))
    name = f"{mp.family}_merge_{m}x{n}"
    meta = (("family", mp.family), ("kind", mp.kind))
    if mp.kind == "columns":
        if mp.n_cols > 1:
            from repro.core.loms import loms_2way

            return loms_2way(m, n, n_cols=mp.n_cols)
        runs = tuple(r for r in (m, n) if r > 0)
        group = Group(idx=ident, runs=runs if len(runs) > 1 else None)
        return Schedule(name=name, size=size, setup_scatter=ident,
                        output_gather=ident,
                        stages=(Stage(groups=(group,)),), meta=meta)
    setup = list(ident)
    if mp.reverse_hi:
        for j in range(n):
            setup[m + j] = m + (n - 1 - j)
    stages = tuple(
        Stage(groups=groups)
        for groups in (_pair_stage_to_groups(st, size) for st in mp.stages)
        if groups)
    return Schedule(name=name, size=size, setup_scatter=tuple(setup),
                    output_gather=ident, stages=stages, meta=meta)


def sort_program_to_schedule(prog: SortProgram):
    """Compose a sort program's levels into one merge-tree ``Schedule``.

    Only levels that are depth-1 group merges on the identity layout
    (``columns`` with ``n_cols == 1``) compose without inter-level
    permutations; programs with column-device or pair levels raise —
    validate those per-level via :func:`program_to_schedule` plus an
    executor-level exhaustive 0-1 sweep instead."""
    from repro.core.networks import Group, Schedule, Stage

    w = prog.width
    stages = []
    for mp in prog.levels:
        if mp.kind != "columns" or mp.n_cols > 1:
            raise ValueError(
                f"level {mp.m}x{mp.n} of {prog.family} is not a "
                "composable depth-1 merge")
        run = mp.m
        stages.append(Stage(groups=tuple(
            Group(idx=tuple(range(b, b + 2 * run)), runs=(run, run))
            for b in range(0, w, 2 * run))))
    ident = tuple(range(w))
    return Schedule(name=f"{prog.family}_sort_{w}", size=w,
                    setup_scatter=ident, output_gather=ident,
                    stages=tuple(stages), meta=(("family", prog.family),))
