"""Operations and bytes computed from shapes alone.

Model FLOPs of the dense decoder (forward only, as served): per token,
2 x every matmul parameter it multiplies (the embedding lookup is a gather
and costs none), plus attention over the context it sees: QK^T and PV are
each 2 x heads x head_dim multiply-adds per context position per layer.
"""
from __future__ import annotations


def _d_head(m: dict) -> int:
    return m.get("d_head") or m["d_model"] // m["n_heads"]


def matmul_params(m: dict) -> int:
    """Parameters a token multiplies through: the blocks and the LM head."""
    d, hd = m["d_model"], _d_head(m)
    attn = d * (m["n_heads"] + 2 * m["n_kv_heads"]) * hd + m["n_heads"] * hd * d
    mlp = 3 * d * m["d_ff"]  # SwiGLU: wi, wg, wo
    return m["n_layers"] * (attn + mlp) + d * m["vocab_size"]


def attention_flops(m: dict, context: int) -> int:
    """QK^T and PV FLOPs of one token attending to ``context`` positions."""
    return m["n_layers"] * 2 * 2 * m["n_heads"] * _d_head(m) * context


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
