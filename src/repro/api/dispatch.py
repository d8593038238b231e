"""Planner-driven backend selection for the unified sort ops.

``plan(spec, par)`` maps a :class:`~repro.api.spec.SortSpec` to a
:class:`Decision` — which backend runs the problem and why. The rules lean
on :mod:`repro.streaming.planner` (the paper's comparator cost model plus
the VMEM budget from DESIGN.md §2), the live JAX platform, and the offered
sharding, so callers state *what* to sort and this module picks *how* —
the one-abstraction-many-realizations stance of the merge literature
(FLiMS, Merge Path) applied to our device family.

The decision table (DESIGN.md §9):

  op       condition                                  backend    detail
  -------  -----------------------------------------  ---------  -----------
  topk     TP-sharded vocab (Parallelism + divisible) sharded    tree_topk
  topk     TPU, axis > 512                            pallas     vocab_topk
  topk     TPU, axis <= 512                           pallas     router_topk
  topk     otherwise (CPU/GPU hosts)                  schedule   blockwise
  sort     TP-sharded + total >= DIST_MIN_TOTAL       sharded    sample_sort
  sort     TPU + fits VMEM, not stable                pallas     sort_fused
  sort     otherwise (stable / over-VMEM / non-TPU)   schedule   merge_tree
  merge    TP-sharded + total >= DIST_MIN_TOTAL       sharded    sample_merge
  merge    payload, TPU + fits VMEM, not stable       pallas     fused_payload
  merge    payload / stable otherwise (perm needed)   schedule   payload
  merge    ragged lengths (no common column count)    schedule   ragged
  merge    working set past the VMEM budget           streaming  chunked
  merge    TPU, fits VMEM                             pallas     loms_merge2
  merge    otherwise                                  schedule   loms_2way
  merge_k  same ladder as merge                       ...        kway/chunked
  median   TPU + equal odd lists, no perm             pallas     kway_median
  median   otherwise                                  schedule   loms_median

The pallas rows run *fused*: NaN-policy key encode/decode, the payload
permute, and descending reversal all execute inside the kernel launch
(repro.api.fused), so a float32 ``repro.sort`` with ``nan_policy="last"``
and a payload is one ``pallas_call`` with no XLA-level encode/decode/
gather around it. Tile knobs (block_batch / n_cols / topk block) come
from the VMEM-aware autotuner (streaming.planner.plan_op: cache-hit
autotuned tiles, VMEM-fit heuristics otherwise).

The sharded rows engage when the caller offered a Parallelism whose TP
axis divides every list length (spec.sharded); below DIST_MIN_TOTAL the
partition + two all_to_alls cost more than they parallelize away, so
small sharded problems stay on the single-device ladder.

Explicit ``backend=`` hints skip the ladder but are still validated against
the backend's capability predicate, so impossible asks fail loudly instead
of silently computing the wrong thing.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import jax.numpy as jnp

from repro.kernels.topk import ROUTER_TOPK_MAX  # noqa: F401  (re-exported)
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace

from .registry import get_backend
from .spec import BACKEND_AUTO, SortSpec


@dataclasses.dataclass(frozen=True)
class Decision:
    """One routing outcome: backend name, kernel detail, human reason.

    ``source`` records how the backend was picked: ``"rule"`` (the static
    ladder), ``"measured"`` (a faster measured route sample overrode the
    rule), or ``"breaker"`` (an open circuit breaker rerouted the call
    down the degradation ladder — repro.resilience).
    ``measured_us`` carries the winning sample when one existed.
    ``network`` names the comparator-network family the pallas kernels
    will execute (the autotuner-tournament winner when a tuned entry
    exists for this point; ``None`` for non-pallas backends)."""

    backend: str
    detail: str = ""
    reason: str = ""
    source: str = "rule"
    measured_us: Optional[float] = None
    network: Optional[str] = None


def _merge2_fits_vmem(spec: SortSpec) -> bool:
    from repro.streaming.planner import fits_vmem

    m, n = spec.lengths[0], sum(spec.lengths[1:])
    return fits_vmem(m, n, dtype=jnp.dtype(spec.dtype))


def _kway_fits_vmem(spec: SortSpec) -> bool:
    from repro.streaming.planner import kway_fits_vmem

    return kway_fits_vmem(spec.total)


def _dist_min_total() -> int:
    from repro.parallel.dist_sort import DIST_MIN_TOTAL

    return DIST_MIN_TOTAL


def _fused_on() -> bool:
    """The fused-pipeline escape hatch (repro.api.fused): when switched
    off, the auto ladder stops offering the fused pallas rows, so sort
    and payload merges revert to the pre-fusion executor routing.
    Explicit ``backend="pallas"`` asks are still honored."""
    from .fused import fused_enabled

    return fused_enabled()


def _dist_eligible(spec: SortSpec) -> bool:
    """Sharded sample-sort rows: a usable TP axis was offered (the ops
    layer sets spec.sharded only when every list length divides it) and
    the problem is large enough to amortize the two all_to_alls."""
    return (spec.sharded and spec.network == "loms"
            and spec.total >= _dist_min_total())


def _segmented_on() -> bool:
    """The segmented-subsystem escape hatch (repro.segmented): when off,
    auto routing degrades to the per-segment XLA reference instead of the
    bucketed kernel launches. Explicit ``backend="segmented"`` asks are
    still honored (and still run the kernels)."""
    from repro.segmented.core import segmented_enabled

    return segmented_enabled()


def _plan_segmented(spec: SortSpec) -> Decision:
    """Routing for CSR ragged specs: the segmented backend owns them all
    (no other backend understands per-segment semantics); the decision
    detail picks the bucketed kernel path vs the XLA reference."""
    if not _segmented_on():
        return Decision(
            "segmented", "reference",
            "segmented kernels disabled (escape hatch): per-segment XLA "
            "reference path",
        )
    if spec.device == "tpu":
        return Decision(
            "segmented", "bucketed_pallas",
            f"{spec.n_segments} segments in pow2 size classes: one fused "
            "launch per class, FLiMS grid-merge spill",
        )
    return Decision(
        "segmented", "reference",
        f"{spec.device or 'non-TPU'} host: per-segment XLA reference "
        "(kernels available via backend='segmented')",
    )


def plan(spec: SortSpec, par=None) -> Decision:
    """Resolve the backend for one problem. Pure function of (spec, par).

    Every decision is recorded in the obs layer (``plan.decisions``
    counter: op / backend / detail / device labels) when ``REPRO_OBS``
    is on — the route-count telemetry the measured cost model audits
    fused-vs-unfused choices against. Disabled, the extra cost is one
    predicate check."""
    with obs_trace.span("plan", kind="trace", op=spec.op):
        dec = _resolve(spec, par)
        dec = _measured_override(spec, dec)
        # breaker avoidance (repro.resilience): a rung with an open
        # circuit breaker for this (op, shape-class) is skipped before it
        # can fail again; one dict miss when no failure was ever recorded
        dec = _resilience_reroute(spec, dec)
        if dec.backend == "pallas":
            entry = _tuned_entry(spec)
            dec = dataclasses.replace(
                dec, network=str((entry or {}).get("network", "loms")))
    if obs_trace.enabled():
        obs_metrics.counter("plan.decisions").inc(
            op=spec.op, backend=dec.backend, detail=dec.detail,
            device=spec.device or "?", segmented=spec.segmented,
            sharded=spec.sharded, payload=spec.has_payload,
            source=dec.source, network=dec.network or "-",
        )
    return dec


def _resilience_reroute(spec: SortSpec, dec: Decision) -> Decision:
    from repro.resilience.ladder import reroute

    return reroute(spec, dec)


def _resolve(spec: SortSpec, par=None) -> Decision:
    if spec.segmented and spec.backend == BACKEND_AUTO:
        return _plan_segmented(spec)
    if spec.backend != BACKEND_AUTO:
        be = get_backend(spec.backend)
        if not be.supports(spec):
            raise ValueError(
                f"backend {spec.backend!r} cannot run {spec.describe()} "
                f"(payload/stable={spec.needs_perm}, network={spec.network!r})"
            )
        return Decision(spec.backend, detail="explicit", reason="caller override")

    if spec.op == "topk":
        if spec.sharded:
            return Decision(
                "sharded", "tree_topk",
                "TP-sharded vocab: log-depth merge reduction over the mesh axis",
            )
        if spec.device == "tpu":
            if spec.total > ROUTER_TOPK_MAX:
                return Decision(
                    "pallas", "vocab_topk",
                    f"TPU, axis {spec.total} > {ROUTER_TOPK_MAX}: two-phase "
                    "block kernel + truncated merge levels",
                )
            return Decision(
                "pallas", "router_topk",
                f"TPU, axis {spec.total} <= {ROUTER_TOPK_MAX}: single-kernel "
                "blockwise top-k",
            )
        return Decision(
            "schedule", "blockwise_topk",
            f"{spec.device or 'non-TPU'} host: pure-JAX truncated-merge tree",
        )

    if spec.op == "sort":
        if _dist_eligible(spec):
            return Decision(
                "sharded", "sample_sort",
                f"TP-sharded, total {spec.total} >= {_dist_min_total()}: "
                "PSRS sample-sort over the mesh axis",
            )
        if (spec.device == "tpu" and _fused_on()
                and get_backend("pallas").supports(spec)):
            return Decision(
                "pallas", "loms_sort_fused",
                "TPU, fits VMEM: single-launch fused merge-tree sort "
                "(in-kernel key transform + payload lanes)",
            )
        return Decision(
            "schedule", "loms_merge_tree",
            "full sort = 2-sorter pairs + LOMS merge tree (stable / "
            "over-VMEM / non-TPU hosts)",
        )

    if spec.op == "median":
        if spec.device == "tpu" and get_backend("pallas").supports(spec):
            return Decision("pallas", "kway_median", "TPU, equal odd lists")
        return Decision("schedule", "loms_median", "schedule executor median")

    # merge / merge_k
    if _dist_eligible(spec):
        # checked before needs_perm: the sample-sort path carries the
        # position payload through both all_to_alls
        return Decision(
            "sharded", "sample_merge_k",
            f"TP-sharded, total {spec.total} >= {_dist_min_total()}: "
            "local k-way LOMS merge of list slices + PSRS exchange",
        )
    if spec.needs_perm:
        if (spec.device == "tpu" and _fused_on()
                and get_backend("pallas").supports(spec)):
            return Decision(
                "pallas", "fused_payload",
                "TPU, fits VMEM: payload rides the kernel permutes in "
                "VMEM (single fused launch)",
            )
        return Decision(
            "schedule", "payload",
            "payload/stable needs the permutation-carrying executor",
        )
    if spec.network != "loms":
        # pallas/streaming realize the LOMS devices only; an explicit
        # Batcher/MWMS/tree ask must not be silently swapped for LOMS
        return Decision(
            "schedule", "network",
            f"non-default network {spec.network!r}: schedule executor",
        )
    if spec.op == "merge":
        if spec.ragged2:
            return Decision(
                "schedule", "ragged",
                "no common column count divides both lists: hole-y setup "
                "array, executor handles it",
            )
        if not _merge2_fits_vmem(spec):
            return Decision(
                "streaming", "chunked_merge",
                "working set past the VMEM budget: fixed-tile carry-buffer "
                "pipeline",
            )
        if spec.device == "tpu":
            return Decision("pallas", "loms_merge2", "TPU, fits VMEM")
        return Decision(
            "schedule", "loms_2way", f"{spec.device or 'non-TPU'} host"
        )
    # merge_k
    if not _kway_fits_vmem(spec):
        return Decision(
            "streaming", "chunked_merge_k",
            "comparison cloud past the VMEM budget: merge-path tiled pipeline",
        )
    if spec.device == "tpu":
        return Decision("pallas", "kway_merge", "TPU, fits VMEM")
    return Decision("schedule", "loms_kway", f"{spec.device or 'non-TPU'} host")


# ---------------------------------------------------------------------------
# measured-cost dispatch: recorded route timings override the static ladder
# ---------------------------------------------------------------------------

#: single-device backends the measured ranking may choose between
_MEASURED_CANDIDATES = ("pallas", "schedule", "streaming")


def measured_dispatch_enabled() -> bool:
    """``REPRO_MEASURED_DISPATCH=0`` pins routing to the static rules."""
    import os

    return os.environ.get("REPRO_MEASURED_DISPATCH", "1") != "0"


def _route_key(spec: SortSpec, backend: str) -> str:
    """Cache key for one (op, shapes, dtype, k, payload, device, backend)
    route sample. The device kind rides in the key's backend tag so TPU and
    CPU timings — or two TPU generations — never rank against each
    other."""
    from repro.streaming.cache import device_tag, plan_key

    tag = (f"{device_tag()}:"
           f"{'payload' if spec.has_payload else 'plain'}:{backend}")
    return plan_key(f"route_{spec.op}",
                    shapes=(spec.batch,) + tuple(spec.lengths),
                    dtype=spec.dtype, k=spec.k, backend=tag)


def record_route_us(spec: SortSpec, backend: str, us: float) -> None:
    """Record one measured wall-time sample (µs) for running ``spec``
    through ``backend``. Keeps the fastest sample seen — a robust
    estimator under timer noise, and monotone: a route can only get
    *preferred* by measuring it faster. Benchmarks are the intended
    writers (``benchmarks/api_dispatch.py --measure-routes``); the samples
    persist in the autotune cache alongside the kernel tuning points."""
    from repro.streaming.cache import default_cache

    cache = default_cache()
    key = _route_key(spec, backend)
    prev = cache.get(key)
    best = float(us)
    if prev is not None and "us" in prev:
        best = min(best, float(prev["us"]))
    cache.put(key, {"us": best, "backend": backend, "op": spec.op})


def measured_route_us(spec: SortSpec, backend: str) -> Optional[float]:
    """Fastest recorded sample for routing ``spec`` via ``backend``."""
    from repro.streaming.cache import default_cache

    entry = default_cache().get(_route_key(spec, backend))
    if entry is None or "us" not in entry:
        return None
    return float(entry["us"])


def _measured_override(spec: SortSpec, dec: Decision) -> Decision:
    """Prefer the fastest *measured* candidate over the static rule.

    Engages only for auto, single-device, non-segmented specs, and only
    when at least two capable backends have recorded samples for this
    exact (op, shapes, dtype, k, payload, platform) point — one sample
    can't rank alternatives. Candidates respect the same escape hatches
    as the rules (a fused-pipeline opt-out also removes the fused pallas
    rows from the measured ranking)."""
    if (not measured_dispatch_enabled() or spec.backend != BACKEND_AUTO
            or spec.segmented or spec.sharded or dec.backend == "sharded"):
        return dec
    samples = {}
    for b in _MEASURED_CANDIDATES:
        if (b == "pallas" and not _fused_on()
                and (spec.op == "sort" or spec.needs_perm)):
            continue
        if not get_backend(b).supports(spec):
            continue
        us = measured_route_us(spec, b)
        if us is not None:
            samples[b] = us
    if len(samples) < 2:
        return dec
    winner = min(samples, key=samples.get)
    if winner == dec.backend:
        return dataclasses.replace(dec, measured_us=samples[winner])
    runner_b, runner_us = min(
        ((b, u) for b, u in samples.items() if b != winner),
        key=lambda kv: kv[1])
    return dataclasses.replace(
        dec, backend=winner, detail="measured",
        reason=(f"measured {samples[winner]:.1f}µs via {winner} beats "
                f"{runner_b} {runner_us:.1f}µs (rule chose {dec.backend})"),
        source="measured", measured_us=samples[winner])


def _tuned_entry(spec: SortSpec) -> Optional[dict]:
    """Full cached autotune entry for the spec's kernel tuning point, if
    an autotune sweep ever ran it on this platform. Carries the measured
    ``us`` sample and the ``network`` tournament winner."""
    from repro.streaming.cache import default_cache, plan_key

    op_map = {
        "sort": ("sort", (spec.lengths[0],), None),
        "merge": ("merge2", tuple(spec.lengths), None),
        "merge_k": ("kway", tuple(spec.lengths), None),
        "topk": ("topk", (spec.total,), spec.k),
    }
    if spec.segmented or spec.op not in op_map:
        return None
    op, lengths, k = op_map[spec.op]
    return default_cache().get(
        plan_key(op, shapes=(spec.batch,) + lengths, dtype=spec.dtype, k=k))


def _tuned_us(spec: SortSpec) -> Optional[float]:
    """Cached measured wall time (µs) for the spec's kernel tuning point.
    Surfaces the persisted ``MergePlan.us`` samples in
    :func:`decision_table` so perf regressions are inspectable without
    rerunning benchmarks."""
    entry = _tuned_entry(spec)
    if entry is None or "us" not in entry:
        return None
    return float(entry["us"])


def decision_table(device: Optional[str] = None) -> List[dict]:
    """Representative routing grid for docs and the dispatch benchmark.

    Each row carries ``tuned_us`` — the cached autotune wall-time sample
    for that tuning point (``None`` until an autotune sweep measured it
    on this platform)."""
    devices = (device,) if device else ("cpu", "tpu")
    rows: List[dict] = []
    cases = []
    for dev in devices:
        cases += [
            SortSpec(op="topk", lengths=(256,), k=8, batch=64, device=dev),
            SortSpec(op="topk", lengths=(32_000,), k=64, batch=8, device=dev),
            SortSpec(op="topk", lengths=(32_000,), k=64, batch=8, device=dev,
                     sharded=True),
            SortSpec(op="merge", lengths=(512, 512), batch=8, device=dev),
            SortSpec(op="merge", lengths=(7, 5), batch=8, device=dev),
            SortSpec(op="merge", lengths=(100_000, 100_000), device=dev),
            SortSpec(op="merge", lengths=(512, 512), batch=8, device=dev,
                     has_payload=True),
            SortSpec(op="merge_k", lengths=(64,) * 4, batch=8, device=dev),
            SortSpec(op="merge_k", lengths=(50_000,) * 4, device=dev),
            SortSpec(op="merge_k", lengths=(50_000,) * 4, device=dev,
                     sharded=True),
            SortSpec(op="sort", lengths=(1024,), batch=8, device=dev),
            SortSpec(op="sort", lengths=(1 << 20,), batch=8, device=dev,
                     sharded=True),
            SortSpec(op="median", lengths=(7, 7, 7), batch=8, device=dev),
            # segmented (CSR ragged) rows: MoE variable-capacity dispatch
            # and continuous-batching mixed-k vocab top-k
            SortSpec(op="sort", lengths=(168,), batch=4, device=dev,
                     segment_offsets=((0, 3, 40, 41, 168),)),
            SortSpec(op="topk", lengths=(96,), k=8, batch=3, device=dev,
                     segment_offsets=((0, 32, 64, 96),)),
            SortSpec(op="merge", lengths=(12, 20), batch=2, device=dev,
                     segment_offsets=((0, 5, 12), (0, 16, 20))),
        ]
    for spec in cases:
        dec = plan(spec)
        rows.append({
            "op": spec.op,
            "problem": spec.describe(),
            "sharded": spec.sharded,
            "payload": spec.has_payload,
            "segments": spec.n_segments,
            "backend": dec.backend,
            "detail": dec.detail,
            "reason": dec.reason,
            "source": dec.source,
            "network": dec.network,
            "measured_us": dec.measured_us,
            "tuned_us": _tuned_us(spec),
        })
    return rows
