"""Random weights for a served configuration, made on the device from the seed.

The tree has the layout the program's ``model_init`` declares (taken by
``jax.eval_shape``, so no parameter is ever computed by the program); every
leaf is drawn here, in one jitted call, directly in the serving dtype.
The plain reference reads the same tree by leaf name.

Scales: projections N(0, 1/fan_in) so activations stay O(1) through the
depth, the fan-in read from the logical axis names ``model_init`` declares
for each leaf (``fan_in``), so that any family's tree is drawn alike;
embeddings N(0, 1); norm scales 1 + N(0, 0.1) and biases N(0, 0.1), so
that neither is the identity the reference could silently skip.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int, salt: int = 0) -> jax.Array:
    """A raw threefry key from any non-negative seed (wider than 32 bits)."""
    words = np.random.SeedSequence([int(seed), int(salt)]).generate_state(2, np.uint32)
    return jnp.asarray(words, jnp.uint32)


def fan_in(shape: tuple, axes: tuple) -> int:
    """A leaf's input width, from the logical axis names the program declares
    for it: a stacked leaf has one leading axis more than its names (the
    layer axis), and an ``expert`` axis only indexes copies; the first axis
    left is the one each output sums over."""
    own = zip(shape[len(shape) - len(axes):], axes)
    return next(n for n, a in own if a != "expert")


def _leaf_rule(path: tuple, shape: tuple, axes: tuple):
    """(kind, std) for one leaf: kind is 'normal', 'norm' or 'bias'."""
    name = path[-1]
    if name == "scale":
        return "norm", 0.1
    if name == "b":
        return "bias", 0.1
    if name == "emb":
        return "normal", 1.0
    return "normal", 1.0 / math.sqrt(fan_in(shape, axes))


def _path_names(path) -> tuple:
    return tuple(getattr(p, "key", getattr(p, "idx", None)) for p in path)


def leaf_specs(cfg):
    """The parameter tree of ``cfg`` as the program's ``model_init`` declares
    it, with nothing computed: its treedef and, leaf by leaf in draw order,
    ``(path, shape, kind, std)``."""
    from repro.models import model_init

    declared = {}

    def init(key):
        params, declared["axes"] = model_init(key, cfg)
        return params

    shapes = jax.eval_shape(init, jax.random.PRNGKey(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    axes = treedef.flatten_up_to(declared["axes"])
    specs = []
    for (p, s), a in zip(flat, axes):
        path, shape = _path_names(p), tuple(s.shape)
        specs.append((path, shape) + _leaf_rule(path, shape, tuple(a)))
    return treedef, specs


def make_weights(cfg, seed: int):
    """The parameter tree of ``cfg`` in ``cfg.dtype``, drawn from ``seed``."""
    treedef, specs = leaf_specs(cfg)
    dtype = jnp.dtype(cfg.dtype)

    def draw(key):
        out = []
        for i, (_, shape, kind, std) in enumerate(specs):
            z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32) * std
            if kind == "norm":
                z = z + 1.0
            out.append(z.astype(dtype))
        return out

    leaves = jax.jit(draw)(seed_key(seed, salt=1))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def param_bytes(params) -> int:
    return sum(int(x.size) * x.dtype.itemsize for x in jax.tree.leaves(params))
