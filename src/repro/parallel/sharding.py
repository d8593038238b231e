"""Logical-axis sharding rules: FSDP over 'data', TP/EP over 'model'.

Every parameter carries a tuple of logical axis names (from the model
``init`` functions). ``build_param_pspecs`` maps logical axes onto mesh
axes with divisibility and no-duplicate checks, falling back to
replication — so a 40-head qwen1.5 on a 16-way model axis simply leaves
heads unsharded rather than failing.

FSDP: the 'embed'-like dimension of every weight shards over 'data' —
parameters and optimizer states are ZeRO-3 partitioned over both mesh
axes; GSPMD inserts the per-layer all-gathers inside the scan and
reduce-scatters the gradients.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class Parallelism:
    mesh: Mesh
    dp_axes: Tuple[str, ...] = ("data",)
    tp_axis: str = "model"
    ep_enabled: bool = True
    fsdp_axis: Optional[str] = "data"
    remat: str = "dots"

    @property
    def dp_size(self) -> int:
        return int(np.prod([self.mesh.shape[a] for a in self.dp_axes]))

    @property
    def tp_size(self) -> int:
        return int(self.mesh.shape[self.tp_axis])

    def dp_for(self, size: int):
        """dp axes if the batch size divides across them, else None."""
        return self.dp_axes if size % self.dp_size == 0 and size >= self.dp_size else None

    def tp_for(self, size: int):
        return self.tp_axis if size % self.tp_size == 0 and size >= self.tp_size else None

    def constrain(self, x, *dims):
        """with_sharding_constraint shorthand; dims are mesh axis names/None.

        Explicit anchors are required because sharding propagation through
        remat + scan + custom_vjp loses activation shardings (observed:
        replicated flash-attention buffers at 453 GiB/device)."""
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, P(*dims)))


def dist_sort_axis(par: Optional[Parallelism], lengths) -> Optional[str]:
    """Mesh axis for the distributed sample-sort (parallel.dist_sort), or
    None when the lists cannot shard evenly over the TP axis — every input
    list must split into equal per-device slices for the static-shape
    ``shard_map`` pipeline."""
    if par is None or getattr(par, "tp_size", 1) <= 1:
        return None
    if any(ln < par.tp_size or ln % par.tp_size for ln in lengths):
        return None
    return par.tp_axis


def vocab_topk_axis(par: Parallelism, vocab_size: int) -> Optional[str]:
    """Mesh axis for the serving device-tree top-k (streaming.tree), or None
    when the vocab can't shard over TP and sampling stays single-device."""
    if par is None or par.tp_size <= 1:
        return None
    if vocab_size % par.tp_size != 0:
        return None
    return par.tp_axis


def make_parallelism(mesh: Mesh, *, ep: bool = True, remat: str = "dots") -> Parallelism:
    axes = mesh.axis_names
    dp = tuple(a for a in axes if a in ("pod", "data"))
    return Parallelism(mesh=mesh, dp_axes=dp, tp_axis="model", ep_enabled=ep,
                       fsdp_axis="data", remat=remat)


# logical axis -> candidate mesh axis (first feasible wins; None = replicate)
LOGICAL_RULES = {
    "vocab": ("model",),
    "mlp": ("model",),
    "heads": ("model",),
    "heads_flat": ("model",),
    "kv_heads": ("model",),
    "expert": ("model",),
    "embed": ("data",),  # FSDP shard
    "kv_lora": ("data",),
    "frontend": (),
    "head_dim": (),
    None: (),
}


def _pspec_for(shape: Tuple[int, ...], logical: Sequence, mesh: Mesh) -> P:
    """Map one array. If ndim == len(logical)+1 the array is scan-stacked:
    the leading 'layers' axis stays unsharded."""
    names: list = list(logical)
    offset = len(shape) - len(names)
    assert offset in (0, 1), (shape, logical)
    out = [None] * len(shape)
    used = set()
    for i, name in enumerate(names):
        dim = shape[offset + i]
        for cand in LOGICAL_RULES.get(name, ()):  # first feasible rule
            if cand in used or cand not in mesh.axis_names:
                continue
            if dim % mesh.shape[cand] == 0 and dim >= mesh.shape[cand]:
                out[offset + i] = cand
                used.add(cand)
                break
    return P(*out)


def build_param_pspecs(param_shapes, specs, mesh: Mesh):
    """param_shapes: pytree of ShapeDtypeStruct (from jax.eval_shape);
    specs: matching pytree of logical-axis tuples. Returns PartitionSpecs."""

    def walk(shapes, spec):
        if isinstance(shapes, dict):
            out = {}
            for k, v in shapes.items():
                out[k] = walk(v, spec[k] if isinstance(spec, dict) else spec)
            return out
        if isinstance(shapes, (list, tuple)):
            return type(shapes)(
                walk(v, spec[i] if isinstance(spec, (list, tuple)) else spec)
                for i, v in enumerate(shapes))
        logical = spec if isinstance(spec, tuple) else ()
        return _pspec_for(shapes.shape, logical, mesh)

    return walk(param_shapes, specs)


def to_named(tree, mesh: Mesh):
    return jax.tree.map(
        lambda ps: NamedSharding(mesh, ps), tree,
        is_leaf=lambda x: isinstance(x, P))


def batch_pspecs(cfg, par: Parallelism):
    dp = par.dp_axes
    out = {"tokens": P(dp, None), "targets": P(dp, None)}
    if cfg.family == "vlm":
        out["tokens"] = P(dp, None)
        out["patches"] = P(dp, None, None)
    if cfg.family == "audio":
        out = {"frames": P(dp, None, None), "targets": P(dp, None)}
    return out


def cache_pspecs(cfg, par: Parallelism, cache_shapes):
    """Shard caches by name: batch over dp; the attention *contraction* dim
    (kv heads / head_dim / latent / ssm heads) over TP. Never the sequence
    dim — decode writes there (dynamic_update_slice at a traced index) and
    the partitioner would fully rematerialize the cache every token."""
    dp, tp = par.dp_axes, par.tp_axis
    tpn = par.tp_size
    dpn = par.dp_size

    def div(n):
        return n % tpn == 0 and n >= tpn

    def leaf_spec(name, shp):
        # shp excludes any layer-stacking prefix; shp[0] = batch
        base = [dp if shp[0] % dpn == 0 else None]
        rest = list(shp[1:])
        out = [None] * len(rest)
        if name == "k":  # (H, D, S)
            out[0 if div(rest[0]) else 1] = tp if (div(rest[0]) or div(rest[1])) else None
        elif name == "v":  # (H, S, Dv)
            out[0 if div(rest[0]) else 2] = tp if (div(rest[0]) or div(rest[2])) else None
        elif name in ("ckv", "kpe"):  # (L, S) / (R, S)
            out[0] = tp if div(rest[0]) else None
        elif name == "conv":  # (K-1, conv_dim)
            out[1] = tp if div(rest[1]) else None
        elif name == "ssm":  # (H, P, N)
            out[0 if div(rest[0]) else 2] = tp if (div(rest[0]) or div(rest[2])) else None
        return base + out

    def walk(tree, stacked):
        if isinstance(tree, dict):
            return {k: walk_leaf(k, v, stacked) if not isinstance(v, (dict, list))
                    else walk(v, stacked) for k, v in tree.items()}
        if isinstance(tree, list):
            return [walk(v, stacked) for v in tree]
        raise TypeError(type(tree))

    def walk_leaf(name, x, stacked):
        shp = list(x.shape)
        if stacked:
            shp = shp[1:]
        if len(shp) == 0:
            return P(None) if stacked else P()
        spec = leaf_spec(name, shp)
        if stacked:
            spec = [None] + spec
        return P(*spec)

    out = {}
    for key, sub in cache_shapes.items():
        out[key] = walk(sub, stacked=(key != "head"))
    return out
