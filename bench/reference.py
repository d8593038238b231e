"""Plain float32 reference forward pass for the dense decoder family.

The contract every reference module under ``bench/`` keeps (a configuration
file names its module with ``"reference": "<module>"``, this one without
the key): ``logits_at(params, model, tokens, rows, precision)`` returns the
logits ``(len(rows), vocab)`` at positions ``rows`` of the one sequence
``tokens``, from the weight tree ``bench/weights.py`` drew and the file's
model block ``model``; ``precision`` is ``"f32"`` (float32 under
``jax.default_matmul_precision("highest")``) or ``"fp8"`` (the control).

Straight ``jax.numpy`` in float32 at ``Precision.HIGHEST``: no kernels, no
cache, no batching, causal softmax attention written out in full. It
imports nothing of the program; it reads the weight tree the benchmark drew
(``bench/weights.py``) by leaf name and the model block of the cell's
configuration file.

Covered: RMSNorm, GQA with the query heads of kv-head ``j`` at
``j*g .. j*g+g-1``, optional qkv bias, optional per-head qk RMSNorm before
RoPE, RoPE on the first ``rope_fraction`` of each head's dimensions,
SwiGLU MLP, untied LM head.

Departure from the published descriptions, noted: RoPE rotates
interleaved pairs ``(x[2i], x[2i+1])`` as ChatGLM does. Qwen3 publishes the
rotate-half pairing ``(x[i], x[i+d/2])``; the two are the same map up to a
fixed permutation of the q/k head dimensions, which random weights do not
distinguish, and the program uses the interleaved form for both.

``precision="fp8"`` is the control: the same pass with every matmul operand
rounded to float8 e4m3 under a per-tensor scale (the step a lower-precision
serving path would take), accumulation and softmax still in float32.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def _q8(x):
    """Round to float8 e4m3 under a per-tensor scale, back in float32."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _mm(spec, a, b, fp8: bool):
    if fp8:
        a, b = _q8(a), _q8(b)
    return jnp.einsum(spec, a, b, precision=HI, preferred_element_type=F32)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, pos, fraction, theta):
    """x (S, H, D); interleaved pairs over the first ``fraction`` of D."""
    d = x.shape[-1]
    rot = int(d * fraction)
    rot -= rot % 2
    if rot == 0:
        return x
    inv = 1.0 / (theta ** (np.arange(0, rot, 2, dtype=np.float64) / rot))
    ang = pos[:, None].astype(F32) * jnp.asarray(inv, F32)  # (S, rot/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0:rot:2], x[..., 1:rot:2]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([out.reshape(x.shape[:-1] + (rot,)), x[..., rot:]], axis=-1)


@functools.partial(jax.jit, static_argnames=("m", "fp8"))
def _layer(x, body, i, *, m, fp8):
    """One decoder block over the whole sequence x (S, D), float32."""
    lw = jax.tree.map(lambda a: a[i].astype(F32), body)
    at, ff = lw["attn"], lw["ffn"]
    s = x.shape[0]
    h, hkv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    g = h // hkv
    pos = jnp.arange(s)
    y = _rms(x, lw["ln1"]["scale"], m["norm_eps"])
    q = _mm("sd,dhk->shk", y, at["wq"]["w"], fp8)
    k = _mm("sd,dhk->shk", y, at["wk"]["w"], fp8)
    v = _mm("sd,dhk->shk", y, at["wv"]["w"], fp8)
    if "b" in at["wq"]:
        q, k, v = q + at["wq"]["b"], k + at["wk"]["b"], v + at["wv"]["b"]
    if "qn" in at:
        q = _rms(q, at["qn"]["scale"], m["norm_eps"])
        k = _rms(k, at["kn"]["scale"], m["norm_eps"])
    q = _rope(q, pos, m["rope_fraction"], m["rope_theta"])
    k = _rope(k, pos, m["rope_fraction"], m["rope_theta"])
    causal = pos[:, None] >= pos[None, :]

    def kv_head(args):  # one kv head and its g query heads
        qj, kj, vj = args  # (S, g, hd) (S, hd) (S, hd)
        sc = _mm("qgk,sk->gqs", qj, kj, fp8) / np.sqrt(hd)
        sc = jnp.where(causal[None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return _mm("gqs,sk->qgk", p, vj, fp8)

    qg = jnp.moveaxis(q.reshape(s, hkv, g, hd), 1, 0)
    out = jax.lax.map(kv_head, (qg, jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0)))
    out = jnp.moveaxis(out, 0, 1).reshape(s, h * hd)
    x = x + _mm("sf,fd->sd", out, at["wo"]["w"], fp8)
    y = _rms(x, lw["ln2"]["scale"], m["norm_eps"])
    a = _mm("sd,df->sf", y, ff["wi"]["w"], fp8)
    b = _mm("sd,df->sf", y, ff["wg"]["w"], fp8)
    return x + _mm("sf,fd->sd", jax.nn.silu(a) * b, ff["wo"]["w"], fp8)


@functools.partial(jax.jit, static_argnames=("eps", "fp8"))
def _head(x, norm, w, *, eps, fp8):
    y = _rms(x, norm.astype(F32), eps)
    return _mm("sd,dv->sv", y, w.astype(F32), fp8)


class _Frozen(dict):
    """A hashable model block, so jit can take it as a static argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def model_block(model: Dict) -> _Frozen:
    d_head = model.get("d_head") or model["d_model"] // model["n_heads"]
    return _Frozen(
        n_heads=model["n_heads"], n_kv_heads=model["n_kv_heads"], head_dim=d_head,
        norm_eps=float(model.get("norm_eps", 1e-5)),
        rope_fraction=float(model.get("rope_fraction", 1.0)),
        rope_theta=float(model.get("rope_theta", 10_000.0)))


def logits_at(params, model: Dict, tokens: np.ndarray, rows: np.ndarray,
              precision: str = "f32") -> jax.Array:
    """Reference logits (len(rows), V) at sequence positions ``rows`` of
    one token sequence ``tokens`` (S,); causal, so padding after the last
    row read changes nothing."""
    assert precision in ("f32", "fp8"), precision
    fp8 = precision == "fp8"
    m = model_block(model)
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"]["emb"], jnp.asarray(tokens), axis=0).astype(F32)
        body = params["stack"]["body"]
        n_layers = jax.tree.leaves(body)[0].shape[0]
        for i in range(n_layers):
            x = _layer(x, body, jnp.int32(i), m=m, fp8=fp8)
        x = x[jnp.asarray(rows)]
        return _head(x, params["final_norm"]["scale"], params["lm_head"]["w"],
                     eps=m["norm_eps"], fp8=fp8)
