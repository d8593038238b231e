"""Random weights for a served configuration, made on the device from the seed.

The tree has the layout the program's ``model_init`` declares (taken by
``jax.eval_shape``, so no parameter is ever computed by the program); every
leaf is drawn here, in one jitted call, directly in the serving dtype.
The plain reference reads the same tree by leaf name.

Scales: projections N(0, 1/fan_in) so activations stay O(1) through the
depth; embeddings N(0, 1); norm scales 1 + N(0, 0.1) and biases N(0, 0.1),
so that neither is the identity the reference could silently skip.
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


def _leaf_rule(path: tuple, shape: tuple):
    """(kind, std) for one leaf: kind is 'normal', 'norm' or 'bias'."""
    name = path[-1]
    stacked = path[0] == "stack"
    if name == "scale":
        return "norm", 0.1
    if name == "b":
        return "bias", 0.1
    if name == "emb":
        return "normal", 1.0
    fan_in = shape[1] if stacked else shape[0]
    return "normal", 1.0 / math.sqrt(fan_in)


def _path_names(path) -> tuple:
    return tuple(getattr(p, "key", getattr(p, "idx", None)) for p in path)


def make_weights(cfg, seed: int):
    """The parameter tree of ``cfg`` in ``cfg.dtype``, drawn from ``seed``."""
    from repro.models import model_init

    shapes = jax.eval_shape(lambda k: model_init(k, cfg)[0], jax.random.PRNGKey(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    specs = [(_path_names(p), tuple(s.shape)) for p, s in flat]
    dtype = jnp.dtype(cfg.dtype)

    def draw(key):
        out = []
        for i, (path, shape) in enumerate(specs):
            kind, std = _leaf_rule(path, shape)
            z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32) * std
            if kind == "norm":
                z = z + 1.0
            out.append(z.astype(dtype))
        return out

    leaves = jax.jit(draw)(seed_key(seed, salt=1))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def param_bytes(params) -> int:
    return sum(int(x.size) * x.dtype.itemsize for x in jax.tree.leaves(params))
