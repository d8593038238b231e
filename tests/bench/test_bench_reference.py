"""The plain float32 reference each served configuration names, against the
program's prefill and decode through its cache, at smoke size on the CPU."""
import dataclasses
import inspect

import jax.numpy as jnp
import numpy as np
import pytest

from bench.drivers.serve import model_config, reference
from bench.weights import make_weights
from cells import config_entry, served_configs, tiny_model

#: program in float32 against the reference at HIGHEST: the two differ only
#: in summation order (chunked flash softmax, per-row decode attention), so
#: logits of O(1) agree to float32 rounding accumulated over 2 layers
F32_ATOL = 2e-5
#: program in its served dtype (bf16) against the float32 reference: bf16
#: keeps 8 bits of mantissa, so O(1) logits move by a few 1e-2 over 2
#: layers; the fp8 control moves them by tenths (test_bench_faults)
BF16_ATOL = 0.15


def tiny(name: str, dtype: str):
    """(model block, program config, reference module) of ``name`` at smoke size."""
    entry = config_entry(name)
    m = tiny_model(entry["model"], n_kv_heads=2 if entry["model"]["n_kv_heads"] > 2 else 1,
                   dtype=dtype)
    return m, model_config({"model": m}), reference(entry)


def program_logits(params, cfg, toks, n_decode):
    """Prefill the first len(toks) - n_decode tokens, then decode the rest
    one at a time through the cache; logits at every position read."""
    from repro.models import decode_step, init_cache, prefill

    plen = len(toks) - n_decode
    cache = init_cache(cfg, 1, len(toks) + 1)
    lg, cache = prefill(params, {"tokens": jnp.asarray(toks[None, :plen])}, cache, cfg)
    out = [lg[0]]
    for i in range(plen, len(toks)):
        lg, cache = decode_step(params, jnp.asarray(toks[None, i:i + 1]), cache, cfg,
                                positions=jnp.full((1, 1), i, jnp.int32))
        out.append(lg[0])
    return jnp.stack(out).astype(jnp.float32)


@pytest.mark.parametrize("name", served_configs())
@pytest.mark.parametrize("dtype,atol", [("float32", F32_ATOL), ("bfloat16", BF16_ATOL)])
def test_reference_matches_prefill_then_decode(name, dtype, atol):
    m, cfg, model_ref = tiny(name, dtype)
    params = make_weights(cfg, 2**40 + 3)
    toks = np.random.default_rng(1).integers(0, m["vocab_size"], 20).astype(np.int32)
    n_decode = 6
    got = program_logits(params, cfg, toks, n_decode)
    rows = np.arange(len(toks) - n_decode - 1, len(toks), dtype=np.int32)
    ref = model_ref.logits_at(params, m, toks, rows)
    err = float(jnp.max(jnp.abs(got - ref)))
    assert err <= atol, (name, dtype, err)
    assert float(jnp.std(ref)) > 0.5  # logits of O(1): the tolerance means something


@pytest.mark.parametrize("name", served_configs())
def test_fp8_control_departs_from_reference(name):
    """The control precision moves logits well past the bf16 tolerance."""
    m, cfg, model_ref = tiny(name, "float32")
    params = make_weights(cfg, 5)
    toks = np.random.default_rng(2).integers(0, m["vocab_size"], 16).astype(np.int32)
    rows = np.arange(16, dtype=np.int32)
    hi = model_ref.logits_at(params, m, toks, rows)
    lo = model_ref.logits_at(params, m, toks, rows, "fp8")
    assert float(jnp.max(jnp.abs(hi - lo))) > 2 * BF16_ATOL / 3


def test_reference_is_independent_of_the_program():
    for name in served_configs():
        src = inspect.getsource(reference(config_entry(name)))
        assert "repro" not in src.replace("reproduce", ""), name


def test_padding_after_the_rows_read_changes_nothing():
    m, cfg, model_ref = tiny(served_configs()[0], "float32")
    params = make_weights(cfg, 9)
    toks = np.random.default_rng(3).integers(0, m["vocab_size"], 10).astype(np.int32)
    rows = np.arange(10, dtype=np.int32)
    a = model_ref.logits_at(params, m, toks, rows)
    b = model_ref.logits_at(params, m, np.concatenate([toks, np.zeros(6, np.int32)]), rows)
    assert float(jnp.max(jnp.abs(a - b))) < 1e-5
    assert dataclasses.is_dataclass(cfg)
