"""Mixture-of-Experts with LOMS routing — the paper's primary integration.

Router: top-k over expert logits computed with the *blockwise LOMS merge*
(``repro.topk(backend="schedule")`` — local rank-sorts then truncated
UP-k/DN-k List Offset merges). This is pure-jnp oblivious networking, so
GSPMD shards it freely; the Pallas realization of the same network lives
in repro.kernels.topk and is used in the serving sampler.

Dispatch (expert parallelism): tokens are sequence-sharded over the
'model' axis for the MoE block; each shard buckets its local tokens into
capacity-bounded per-expert buffers, one all_to_all moves buckets to the
expert-owning shards, expert FFNs run as dense batched einsums, and a
second all_to_all returns outputs — deterministic shapes end to end.

``dispatch='sorted'`` demonstrates the paper's oblivious-routing angle:
bucket positions come from an actual List-Offset sort network over the
(expert_id, token) pairs instead of the cumsum — bit-identical routing,
data-oblivious schedule (usable for the paper's safety/security argument).
Used for small token counts (tests/examples); 'scatter' (cumsum) is the
production path.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import sort as unified_sort
from repro.api import topk as unified_topk
from repro.configs.base import ModelConfig
from .layers import dense_init

Params = dict


def moe_init(key, cfg: ModelConfig):
    mo = cfg.moe
    d, e, f = cfg.d_model, mo.n_experts, mo.d_expert
    ks = jax.random.split(key, 6)
    p, s = {}, {}
    p["router"], s["router"] = dense_init(ks[0], d, e, ("embed", "expert"))
    std = 1.0 / np.sqrt(d)
    p["wi"] = {"w": jax.random.normal(ks[1], (e, d, f), jnp.float32) * std}
    p["wg"] = {"w": jax.random.normal(ks[2], (e, d, f), jnp.float32) * std}
    p["wo"] = {"w": jax.random.normal(ks[3], (e, f, d), jnp.float32) * (1.0 / np.sqrt(f))}
    s["wi"] = {"w": ("expert", "embed", "mlp")}
    s["wg"] = {"w": ("expert", "embed", "mlp")}
    s["wo"] = {"w": ("expert", "mlp", "embed")}
    if mo.n_shared_experts:
        fs = f * mo.n_shared_experts
        p["shared_wi"], s["shared_wi"] = dense_init(ks[4], d, fs, ("embed", "mlp"))
        p["shared_wg"], s["shared_wg"] = dense_init(ks[5], d, fs, ("embed", "mlp"))
        p["shared_wo"], s["shared_wo"] = dense_init(
            jax.random.fold_in(ks[4], 7), fs, d, ("mlp", "embed"))
    return p, s


def router_topk(logits: jnp.ndarray, k: int, block: int):
    """LOMS blockwise top-k + renormalized softmax gates.

    logits: (T, E) -> gates (T, k) float, expert ids (T, k) int32."""
    e = logits.shape[-1]
    blk = min(block, e)
    while e % blk:
        blk -= 1
    # backend pinned to the pure-jnp schedule executor: the router runs
    # inside shard_map/GSPMD traces where the oblivious network shards freely
    vals, idx = unified_topk(
        logits.astype(jnp.float32), k, block=blk, backend="schedule")
    gates = jax.nn.softmax(vals, axis=-1)
    return gates, idx


def _positions_cumsum(flat_e: jnp.ndarray, n_experts: int):
    """GShard position-in-expert via one-hot cumsum (production path)."""
    oh = (flat_e[:, None] == jnp.arange(n_experts)[None, :]).astype(jnp.int32)
    pos = jnp.cumsum(oh, axis=0) - 1
    return (pos * oh).sum(-1)


def _positions_sorted(flat_e: jnp.ndarray, n_experts: int, par=None):
    """Oblivious position-in-expert via a List Offset sort network.

    Sort composite keys (expert_id * n + arrival_index) — unique, so the
    (unstable) LOMS network yields a STABLE expert grouping, bit-identical
    to the cumsum path; position-in-expert = rank - start_of_expert.
    Data-oblivious end to end (the paper's security/safety use case).

    On TPU without a sharding offer, the key sort routes through the
    segmented backend's kernel path (``repro.segment_sort`` with
    ``backend="segmented"``) whenever the problem fits one size class:
    the bucketed class network is exactly as oblivious as the schedule
    executor (a fixed trace-time comparison network, no data-dependent
    control flow), so the security/safety property is preserved while the
    sort gains the fused single-launch kernel. Two guards keep the old
    executor path: problems past the class budget (the segmented spill
    path's argsort is *not* oblivious and must never be picked here) and
    non-TPU hosts (interpret-mode kernel emulation would only slow the
    already-oblivious executor down). ``REPRO_DISABLE_SEGMENTED``
    restores the executor path outright. With a TP-sharded ``par`` (the
    non-EP path, where this runs outside any shard_map) the planner may
    instead route to the distributed sample-sort — large token counts
    then sort device-parallel."""
    n = flat_e.shape[0]
    keys = flat_e.astype(jnp.int32) * n + jnp.arange(n, dtype=jnp.int32)
    from repro.segmented import max_class_width, segmented_enabled

    if (par is None and segmented_enabled()
            and jax.default_backend() == "tpu"
            and n <= max_class_width(jnp.int32)):
        from repro.api import segment_sort

        sorted_keys, perm = segment_sort(
            keys, (0, n), payload=jnp.arange(n, dtype=jnp.int32),
            backend="segmented")
    else:
        sorted_keys, perm = unified_sort(
            keys, payload=jnp.arange(n, dtype=jnp.int32),
            backend="schedule" if par is None else "auto", par=par)
    sorted_e = sorted_keys // n
    counts = (flat_e[:, None] == jnp.arange(n_experts)[None, :]).sum(0)
    starts = jnp.cumsum(counts) - counts
    pos_sorted = jnp.arange(n) - starts[sorted_e]
    # scatter back to original slot order
    pos = jnp.zeros((n,), jnp.int32).at[perm].set(pos_sorted.astype(jnp.int32))
    return pos


def _expert_ffn(buf, p, act: str = "swiglu"):
    """buf: (E_local, C, D); expert weights stacked on the leading axis."""
    h = jnp.einsum("ecd,edf->ecf", buf, p["wi"]["w"].astype(buf.dtype))
    g = jnp.einsum("ecd,edf->ecf", buf, p["wg"]["w"].astype(buf.dtype))
    h = jax.nn.silu(h) * g
    return jnp.einsum("ecf,efd->ecd", h, p["wo"]["w"].astype(buf.dtype))


def _expert_ffn_csr(buf, p, caps: np.ndarray, starts: np.ndarray):
    """Expert FFN over a CSR buffer with static ragged capacities.

    ``buf``: (sum(caps), D) — expert ``i`` owns rows
    ``starts[i]:starts[i]+caps[i]``. The size-class idea of
    repro.segmented applied to expert *compute*: experts with equal
    capacity share one batched einsum, so a few large-capacity experts no
    longer force every buffer (and every FLOP) up to the max. Uniform
    capacities collapse to a single class = the dense path's one einsum."""
    d = buf.shape[-1]
    out = jnp.zeros_like(buf)
    classes = {}
    for i, c in enumerate(np.asarray(caps).tolist()):
        classes.setdefault(int(c), []).append(i)
    for c, ids in sorted(classes.items()):
        if c == 0:
            continue
        gmap = np.asarray(starts)[ids][:, None] + np.arange(c)[None, :]
        sub = buf[jnp.asarray(gmap)]  # (n_ids, c, D)
        pc = {nm: {"w": p[nm]["w"][jnp.asarray(ids)]}
              for nm in ("wi", "wg", "wo")}
        res = _expert_ffn(sub, pc)
        out = out.at[jnp.asarray(gmap.reshape(-1))].set(res.reshape(-1, d))
    return out


def moe_ffn_local(
    p: Params,
    x: jnp.ndarray,  # (T, D) local tokens
    cfg: ModelConfig,
    *,
    axis_name: Optional[str] = None,
    ep_size: int = 1,
    ep_psum: bool = False,
    par=None,
):
    """Routed expert FFN on local tokens. Two expert-parallel modes:
    all_to_all (tokens sequence-sharded; training/prefill) and ep_psum
    (tokens replicated over the EP axis, each rank computes only its own
    experts' contribution, one psum combines — used at decode where a
    single token cannot be sequence-sharded)."""
    mo = cfg.moe
    t, d = x.shape
    e, k = mo.n_experts, mo.top_k
    logits = jnp.einsum("td,de->te", x, p["router"]["w"].astype(x.dtype))
    gates, eids = router_topk(logits, k, mo.router_block)

    if ep_psum and axis_name is not None and ep_size > 1:
        e_loc = e // ep_size
        rank = jax.lax.axis_index(axis_name)
        local = (eids // e_loc) == rank
        gates = gates * local  # zero out non-local expert choices
        eids = jnp.where(local, eids - rank * e_loc, 0)
        e = e_loc  # bucket over local experts only; weights already local

    flat_e = eids.reshape(-1)
    tok_of = jnp.arange(t * k, dtype=jnp.int32) // k
    cap = int(np.ceil(t * k / e * mo.capacity_factor))
    cap = max(4, cap + (-cap) % 4)
    # the oblivious sorted dispatch is affordable up to 4096 keys on one
    # device; with a TP axis the distributed sample-sort extends the range
    # (keys stay exact int32 composites: e * t * k < 2^31 holds there).
    # The raise only applies from DIST_MIN_TOTAL up — below it the planner
    # would still pick the expensive single-device merge-tree sort.
    sorted_cap = 4096
    if par is not None and t * k >= sorted_cap and e * t * k < 2 ** 31:
        from repro.parallel.dist_sort import DIST_MIN_TOTAL
        from repro.parallel.sharding import dist_sort_axis

        if (t * k >= DIST_MIN_TOTAL
                and dist_sort_axis(par, (t * k,)) is not None):
            sorted_cap = 1 << 16
    if mo.dispatch == "sorted" and t * k <= sorted_cap:
        pos = _positions_sorted(flat_e, e, par=par)
    else:
        pos = _positions_cumsum(flat_e, e)

    if mo.expert_capacities is not None:
        # CSR ragged dispatch: expert i owns exactly caps[i] slots instead
        # of every buffer padding to a uniform capacity; the FFN runs one
        # einsum per capacity class (_expert_ffn_csr). Static shapes
        # throughout — the raggedness lives in the trace-time offsets.
        assert axis_name is None or ep_size == 1, (
            "expert_capacities is a non-EP feature: EP buckets must "
            "travel as dense (E, C, D) through all_to_all")
        caps_np = np.asarray(mo.expert_capacities, np.int64)
        assert caps_np.shape == (e,), (caps_np.shape, e)
        starts_np = np.concatenate([[0], np.cumsum(caps_np)])
        total = int(starts_np[-1])
        caps_j = jnp.asarray(caps_np, jnp.int32)
        starts_j = jnp.asarray(starts_np[:-1], jnp.int32)
        keep = pos < caps_j[flat_e]
        dest = jnp.where(keep, starts_j[flat_e] + pos, total)  # spill row
        buf_flat = jnp.zeros((total + 1, d), x.dtype).at[dest].add(x[tok_of])
        flat_out = _expert_ffn_csr(buf_flat[:-1], p, caps_np, starts_np)
        flat_out = jnp.concatenate([flat_out, jnp.zeros((1, d), x.dtype)])
        y_choice = flat_out[dest]  # spill row reads the zero pad
    else:
        keep = pos < cap
        dest = jnp.where(keep, flat_e * cap + pos, e * cap)  # spill row
        buf = jnp.zeros((e * cap + 1, d), x.dtype).at[dest].add(x[tok_of])
        buf = buf[:-1].reshape(e, cap, d)

        if axis_name is not None and ep_size > 1 and not ep_psum:
            # (E, C, D) -> (E/P, P*C, D): buckets travel to expert owners
            buf = jax.lax.all_to_all(buf, axis_name, split_axis=0,
                                     concat_axis=1, tiled=True)
            out = _expert_ffn(buf, p)
            out = jax.lax.all_to_all(out, axis_name, split_axis=1,
                                     concat_axis=0, tiled=True)
        else:
            out = _expert_ffn(buf, p)

        flat_out = out.reshape(e * cap, d)
        y_choice = flat_out[jnp.minimum(dest, e * cap - 1)]
    w = (gates.reshape(-1) * keep).astype(x.dtype)
    y = (y_choice * w[:, None]).reshape(t, k, d).sum(axis=1)

    if ep_psum and axis_name is not None and ep_size > 1:
        y = jax.lax.psum(y, axis_name)

    if mo.n_shared_experts:
        h = jnp.einsum("td,df->tf", x, p["shared_wi"]["w"].astype(x.dtype))
        g = jnp.einsum("td,df->tf", x, p["shared_wg"]["w"].astype(x.dtype))
        y = y + jnp.einsum(
            "tf,fd->td", jax.nn.silu(h) * g, p["shared_wo"]["w"].astype(x.dtype))
    return y


def moe_apply(p: Params, x: jnp.ndarray, cfg: ModelConfig, par=None):
    """x: (B, S, D). With a parallel context, run expert-parallel under
    shard_map (tokens sequence-sharded over the TP axis for this block)."""
    b, s, d = x.shape
    if par is None or not par.ep_enabled:
        # par (when given) rides along so the oblivious sorted dispatch can
        # engage the distributed sample-sort; inside the shard_map EP path
        # below it must stay None (no nested meshes)
        y = moe_ffn_local(p, x.reshape(b * s, d), cfg, par=par)
        return y.reshape(b, s, d)

    from jax.sharding import PartitionSpec as P

    mesh = par.mesh
    dp, tp = par.dp_axes, par.tp_axis
    ep_size = mesh.shape[tp]

    seq_shardable = s % ep_size == 0 and s >= ep_size

    def body(xb, pb):
        bb, sb, _ = xb.shape
        y = moe_ffn_local(pb, xb.reshape(bb * sb, d), cfg,
                          axis_name=tp, ep_size=ep_size,
                          ep_psum=not seq_shardable)
        return y.reshape(bb, sb, d)

    pspecs = jax.tree.map(lambda _: P(), p)
    for name in ("wi", "wg", "wo"):
        pspecs[name] = {"w": P(tp)}  # experts sharded over the TP axis
    x_spec = P(dp, tp, None) if seq_shardable else P(dp, None, None)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(x_spec, pspecs),
        out_specs=x_spec,
        check_vma=False,
    )(x, p)
