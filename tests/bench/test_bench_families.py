"""A configuration of another family than the dense decoders, here the
registry's DeepSeek-V2-Lite (latent attention, routed and shared experts,
one leading dense layer), goes through the harness with no edit to it: its
model block with nested ``mla`` / ``moe`` groups, its weights drawn at each
leaf's true fan-in, its FLOPs, its smoke-size stand-in and the reference
its file names. The two dense configurations keep the weights they had."""
import dataclasses
import importlib
import json
import math
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import run as bench_run
from bench import work
from bench.drivers.serve import model_config, reference, served_gaps
from bench.weights import _path_names, leaf_specs, make_weights
from cells import TINY_MLA, TINY_MOE, cells_of, config_entry, shrink
from repro.configs.base import MLAConfig, MoEConfig
from repro.configs.registry import DEEPSEEK_V2_LITE, get_smoke_config

#: the planned cut: 1 dense + 7 MoE layers, every width as published
CUT = dataclasses.replace(DEEPSEEK_V2_LITE, n_layers=8)


def entry_of(cfg, **keys) -> dict:
    """A configuration file's content for ``cfg``, through JSON as it is read."""
    return json.loads(json.dumps(dict(keys, name=cfg.name, model=dataclasses.asdict(cfg))))


def true_fan_in(path: tuple, cfg) -> int:
    """The width each output of the leaf at ``path`` sums over, by hand from
    the DeepSeek-V2 block: inputs of d_model, the latent, the heads' values,
    the dense FFN, an expert or the shared experts."""
    m, e = cfg.mla, cfg.moe
    leaf = path[-2]
    if leaf in ("wq", "wdkv", "wi", "wg", "router", "shared_wi", "shared_wg", "lm_head"):
        return cfg.d_model
    if leaf in ("wuk", "wuv"):
        return m.kv_lora_rank
    if leaf == "shared_wo":
        return e.d_expert * e.n_shared_experts
    if path[-3] == "attn":  # wo
        return cfg.n_heads * m.v_head_dim
    return cfg.d_ff if "head" in path else e.d_expert  # ffn wo: dense layer or an expert


def projections(cfg):
    """(path, shape, std) of every leaf drawn N(0, 1/fan_in)."""
    _, specs = leaf_specs(cfg)
    out = [(p, s, std) for p, s, kind, std in specs if kind == "normal" and p[-1] != "emb"]
    assert len(out) >= 15
    return out


def assert_drawn_at_true_fan_in(cfg, seed):
    params = make_weights(cfg, seed)
    drawn = {_path_names(p): np.asarray(x, np.float32)
             for p, x in jax.tree_util.tree_flatten_with_path(params)[0]}
    for path, shape, std in projections(cfg):
        want = 1.0 / math.sqrt(true_fan_in(path, cfg))
        assert std == want, path
        assert drawn[path].shape == shape
        assert float(np.std(drawn[path])) == pytest.approx(want, rel=0.1), path


def test_model_config_builds_nested_mla_and_moe_groups():
    cfg = model_config(entry_of(CUT))
    assert isinstance(cfg.mla, MLAConfig) and isinstance(cfg.moe, MoEConfig)
    assert cfg == CUT
    hash(cfg)  # a static argument of the program's jits


def test_smoke_tree_is_drawn_at_true_fan_in():
    assert_drawn_at_true_fan_in(get_smoke_config(DEEPSEEK_V2_LITE.name), 2**40 + 5)


def test_published_widths_are_drawn_at_true_fan_in():
    """At the planned cut's shapes, by ``eval_shape`` alone: nothing is allocated."""
    rules = {p: (s, std) for p, s, std in projections(CUT)}
    for path, (_, std) in rules.items():
        assert std == 1.0 / math.sqrt(true_fan_in(path, CUT)), path
    # the leaves a rule by position alone drew 0.43x to 11x off
    for path, shape, fan in [
        (("stack", "body", "ffn", "wi", "w"), (7, 64, 2048, 1408), 2048),
        (("stack", "body", "ffn", "wo", "w"), (7, 64, 1408, 2048), 1408),
        (("stack", "head", 0, "attn", "wq", "w"), (2048, 16, 192), 2048),
        (("stack", "head", 0, "attn", "wuk", "w"), (512, 16, 128), 512),
        (("stack", "head", 0, "ffn", "wi", "w"), (2048, 10944), 2048),
    ]:
        assert rules[path] == (shape, 1.0 / math.sqrt(fan)), path


def parent_leaf_rule(path: tuple, shape: tuple):
    """The rule the dense configurations' weights were drawn by before the
    fan-in came from the declared axis names, frozen here."""
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


@pytest.mark.parametrize("name", ["qwen3-8b", "chatglm3-6b"])
def test_dense_configurations_keep_their_leaf_rules(name):
    """Every leaf at published shapes keeps its (kind, std) and its draw index."""
    from repro.models import model_init

    cfg = model_config(config_entry(name))
    shapes = jax.eval_shape(lambda k: model_init(k, cfg)[0], jax.random.PRNGKey(0))
    parent = [(_path_names(p), tuple(s.shape))
              for p, s in jax.tree_util.tree_flatten_with_path(shapes)[0]]
    _, specs = leaf_specs(cfg)
    assert [(p, s) for p, s, _, _ in specs] == parent
    for path, shape, kind, std in specs:
        assert (kind, std) == parent_leaf_rule(path, shape), path


@pytest.fixture
def named_reference(monkeypatch):
    """A reference module ``bench.family_ref`` whose logits rank token ids in
    order, so that the best token is the last id; it records its calls."""
    mod = types.ModuleType("bench.family_ref")
    mod.calls = []

    def logits_at(params, model, tokens, rows, precision="f32"):
        mod.calls.append((model["name"], precision))
        return jnp.tile(jnp.arange(model["vocab_size"], dtype=jnp.float32), (len(rows), 1))

    mod.logits_at = logits_at
    monkeypatch.setitem(sys.modules, "bench.family_ref", mod)
    return mod


def test_reference_resolves_from_the_configuration(named_reference):
    default = importlib.import_module("bench.reference")
    assert reference({"model": {}}) is default
    assert reference({"reference": "reference"}) is default
    assert reference({"reference": "family_ref"}) is named_reference
    for bad in ("../reference", "drivers.serve", ""):
        with pytest.raises(ValueError):
            reference({"reference": bad})
    # the served-token check reads the module the file names
    entry = {"reference": "family_ref", "model": {"name": "m", "vocab_size": 8}}
    mix = {"prompt": {"max": 6}, "output": {"max": 4}, "sampling": {"temperature": 1.0}}
    checked = [(np.arange(3, dtype=np.int32), np.array([7, 7, 5], np.int32), 1),
               (np.arange(5, dtype=np.int32), np.array([7], np.int32), 1)]
    assert served_gaps(None, entry, mix, checked) == [2.0, 0.0]
    assert named_reference.calls == [("m", "f32")] * 2


def test_work_counts_mla_and_moe_by_hand():
    m = entry_of(CUT)["model"]
    # MLA: wq 2048 x 16 x (128 + 64), wdkv 2048 x (512 + 64), wuk and wuv
    # 512 x 16 x 128 each, wo 16 x 128 x 2048
    attn = 2048 * 16 * 192 + 2048 * 576 + 2 * 512 * 16 * 128 + 16 * 128 * 2048
    assert attn == 13_762_560
    dense = 3 * 2048 * 10_944  # the leading dense layer's SwiGLU
    assert dense == 67_239_936
    moe = 3 * 2048 * 1408 * (6 + 2) + 2048 * 64  # 6 routed + 2 shared experts, router
    assert moe == 69_337_088
    head = 2048 * 102_400
    assert head == 209_715_200
    assert work.matmul_params(m) == 8 * attn + dense + 7 * moe + head == 872_415_232
    # per context position and layer: QK^T 2 x 16 x (128 + 64), PV 2 x 16 x 128
    assert work.attention_flops(m, 1) == 8 * (2 * 16 * 192 + 2 * 16 * 128) == 81_920
    assert work.decode_flops(m, 1000) == 2 * 872_415_232 + 81_920 * 1000
    assert work.prefill_flops(m, 3) == (2 * (872_415_232 - head) * 3 + 2 * head + 81_920 * 6)


def test_a_nested_configuration_goes_through_the_harness(named_reference):
    """A configuration file with nested groups and a reference of its own,
    put in a serving cell's place: the CPU stand-in keeps its groups at
    smoke widths, and the program's config, the weights, the work counts and
    the reference all follow from the file."""
    spec = bench_run.resolve(bench_run.load_benchmark(), cells_of("serve")[0])
    spec["config"] = entry_of(CUT, reference="family_ref",
                              scheduler={"n_slots": 16, "page_size": 64, "max_prefill_batch": 1})
    shrink(spec)
    entry = spec["config"]
    m = entry["model"]
    assert {k: m["mla"][k] for k in TINY_MLA} == TINY_MLA and m["d_head"] is None
    assert {k: m["moe"][k] for k in TINY_MOE} == TINY_MOE
    assert m["moe"]["n_shared_experts"] == 1 and m["moe"]["first_dense_layers"] == 1
    cfg = model_config(entry)
    assert cfg.mla == MLAConfig(**TINY_MLA)
    assert_drawn_at_true_fan_in(cfg, 7)
    assert work.decode_flops(m, 10) == 2 * work.matmul_params(m) + work.attention_flops(m, 10)
    assert work.attention_flops(m, 1) == 2 * 2 * 4 * (16 + 8 + 16)
    assert reference(entry) is named_reference
