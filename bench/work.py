"""Operations and bytes computed from shapes alone.

Model FLOPs of the decoder (forward only, as served): per token, 2 x every
matmul parameter it multiplies (the embedding lookup is a gather and costs
none), plus attention over the context it sees: QK^T and PV, per context
position per layer, 2 x heads x (query-key width + value width).

The model block's optional sub-blocks are counted in the published
architecture's own form:

* ``mla``: latent attention with keys and values decompressed, as the
  DeepSeek-V2 paper states it: projections ``wq``, ``wdkv`` (latent and the
  shared RoPE key), ``wuk``, ``wuv``, ``wo``; QK^T over
  ``qk_nope_head_dim + qk_rope_head_dim``, PV over ``v_head_dim``.
* ``moe``: the first ``first_dense_layers`` layers are dense SwiGLU of
  ``d_ff``; every later layer runs its ``top_k`` routed and its
  ``n_shared_experts`` shared SwiGLU experts of ``d_expert``, plus the
  router ``d_model x n_experts``.
"""
from __future__ import annotations


def _d_head(m: dict) -> int:
    return m.get("d_head") or m["d_model"] // m["n_heads"]


def _attention_params(m: dict) -> int:
    """Projection parameters of one attention layer."""
    d, h = m["d_model"], m["n_heads"]
    mla = m.get("mla")
    if mla:
        nope, rope = mla["qk_nope_head_dim"], mla["qk_rope_head_dim"]
        lora, v = mla["kv_lora_rank"], mla["v_head_dim"]
        return d * h * (nope + rope) + d * (lora + rope) + lora * h * (nope + v) + h * v * d
    hd = _d_head(m)
    return d * (h + 2 * m["n_kv_heads"]) * hd + h * hd * d


def _ffn_params(m: dict) -> int:
    """Parameters a token multiplies through in the feed-forward layers of the
    whole stack (SwiGLU: wi, wg, wo)."""
    d, layers = m["d_model"], m["n_layers"]
    moe = m.get("moe")
    if not moe:
        return layers * 3 * d * m["d_ff"]
    dense = moe["first_dense_layers"]
    experts = moe["top_k"] + moe["n_shared_experts"]
    routed = 3 * d * moe["d_expert"] * experts + d * moe["n_experts"]
    return dense * 3 * d * m["d_ff"] + (layers - dense) * routed


def matmul_params(m: dict) -> int:
    """Parameters a token multiplies through: the blocks and the LM head."""
    return m["n_layers"] * _attention_params(m) + _ffn_params(m) + m["d_model"] * m["vocab_size"]


def attention_flops(m: dict, context: int) -> int:
    """QK^T and PV FLOPs of one token attending to ``context`` positions."""
    mla = m.get("mla")
    if mla:
        width = mla["qk_nope_head_dim"] + mla["qk_rope_head_dim"] + mla["v_head_dim"]
    else:
        width = 2 * _d_head(m)
    return m["n_layers"] * 2 * m["n_heads"] * width * context


def prefill_flops(m: dict, prompt: int) -> int:
    """One prompt of ``prompt`` tokens, causal: token i sees i + 1 positions.
    The LM head runs only for the last position, as the program serves it."""
    head = m["d_model"] * m["vocab_size"]
    body = matmul_params(m) - head
    ctx = prompt * (prompt + 1) // 2
    return 2 * body * prompt + 2 * head + attention_flops(m, 1) * ctx


def decode_flops(m: dict, context: int) -> int:
    """One decoded token at a cache holding ``context`` positions (itself
    included)."""
    return 2 * matmul_params(m) + attention_flops(m, context)


def topk_bytes(rows: int, vocab: int, ks, in_itemsize: int = 2,
               out_itemsize: int = 2, idx_itemsize: int = 4) -> int:
    """HBM bytes a top-k over ``rows`` x ``vocab`` must move at least: read
    every logit once, write each row's k values and int32 indices."""
    return rows * vocab * in_itemsize + sum(ks) * (out_itemsize + idx_itemsize)
