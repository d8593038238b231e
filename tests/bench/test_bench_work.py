"""Peaks table and work counts from shapes."""
import json
import os

import pytest

from bench import work
from bench.peaks import peaks

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def model(name):
    with open(os.path.join(ROOT, "bench", "configs", f"{name}.json")) as f:
        return json.load(f)["model"]


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        peaks("TPU v9 imaginary")
    assert peaks("TPU v5 lite")["bf16_flops"] == 197e12
    assert peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_qwen3_flops_per_token_by_hand():
    m = model("qwen3-8b")
    # per layer: q 4096x4096, k and v 4096x1024 each, o 4096x4096,
    # SwiGLU 3 x 4096 x 12288; 8 layers; untied LM head 4096 x 151936
    per_layer = 4096 * 4096 + 2 * 4096 * 1024 + 4096 * 4096 + 3 * 4096 * 12288
    assert per_layer == 192_937_984
    head = 4096 * 151_936
    assert work.matmul_params(m) == 8 * per_layer + head
    # a decoded token at context 1000: 2 FLOPs per matmul parameter, plus
    # QK^T and PV: 2 x 2 x 32 heads x 128 dims x 1000 positions x 8 layers
    assert work.decode_flops(m, 1000) == 2 * (8 * per_layer + head) + 4 * 32 * 128 * 1000 * 8


def test_chatglm3_flops_per_token_by_hand():
    m = model("chatglm3-6b")
    # q 4096x4096, k and v 4096x256 each (2 kv heads of 128), o 4096x4096,
    # SwiGLU 3 x 4096 x 13696
    per_layer = 2 * 4096 * 4096 + 2 * 4096 * 256 + 3 * 4096 * 13696
    head = 4096 * 65_024
    assert work.matmul_params(m) == 8 * per_layer + head
    assert work.decode_flops(m, 1) == 2 * (8 * per_layer + head) + 4 * 32 * 128 * 8
    # a prompt of 3: three body passes, one head, causal contexts 1 + 2 + 3
    assert work.prefill_flops(m, 3) == (2 * 8 * per_layer * 3 + 2 * head
                                        + 4 * 32 * 128 * 8 * 6)


def test_topk_bytes_from_shapes_alone():
    # 16 rows of 151,936 bf16 read once; 16 x 50 bf16 values and int32 ids
    assert work.topk_bytes(16, 151_936, [50] * 16) == 16 * 151_936 * 2 + 800 * 6
    assert work.topk_bytes(1, 10, [3], in_itemsize=4, out_itemsize=4) == 40 + 3 * 8
