"""Ahead-of-time compiles of the main-path kernels for a described TPU v5e.

Interpret mode runs a kernel body as plain jnp, so it cannot see what the
TPU lowering refuses (reversals, gathers, unaligned blocks, VMEM over
budget). These tests compile each main-path kernel at real widths for a
v5e that is described, not attached (``jax.experimental.topologies``),
with ``interpret=False``, and check that a Mosaic kernel is in the
executable. Nothing runs, so they say nothing about results or speed.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library, and every
test worker imports every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.loms import loms_kway
from repro.kernels.kway import kway_merge_pallas
from repro.kernels.loms_merge import loms_merge2_pallas
from repro.kernels.segmented import segment_class_sort_pallas
from repro.kernels.sort import loms_sort_pallas
from repro.kernels.topk import router_topk_pallas, vocab_topk_pallas


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    # a compile for a described chip cannot be read back without one:
    # keep it out of any persistent cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("k,block", [(64, 128), (128, 128), (64, 64)])
@pytest.mark.parametrize("key_dtype", [None, "float32"])
def test_vocab_topk_compiles(one_chip, no_compile_cache, k, block, key_dtype):
    _compile(lambda x: vocab_topk_pallas(
        x, k=k, block=block, block_batch=8, use_mxu=key_dtype is None,
        interpret=False, key_dtype=key_dtype),
        one_chip, ((8, 151_936), jnp.float32))


@pytest.mark.parametrize("rows", [1, 8])
@pytest.mark.parametrize("key_dtype", [None, "bfloat16"])
def test_vocab_topk_bf16_logits_compiles(one_chip, no_compile_cache, rows,
                                         key_dtype):
    # the model's logits are bf16; one row is the first-token sample
    _compile(lambda x: vocab_topk_pallas(
        x, k=64, block=64, block_batch=8, use_mxu=key_dtype is None,
        interpret=False, key_dtype=key_dtype),
        one_chip, ((rows, 151_936), jnp.bfloat16))


@pytest.mark.parametrize("dtype,key_dtype", [
    (jnp.float32, None), (jnp.float32, "float32"),
    (jnp.bfloat16, "bfloat16")])
@pytest.mark.parametrize("experts", [128, 160])
def test_router_topk_compiles(one_chip, no_compile_cache, dtype, key_dtype,
                              experts):
    _compile(lambda x: router_topk_pallas(
        x, k=8, block=32, block_batch=8, use_mxu=key_dtype is None,
        interpret=False, key_dtype=key_dtype),
        one_chip, ((4096, experts), dtype))


@pytest.mark.parametrize("use_mxu", [True, False])
@pytest.mark.parametrize("network", ["loms", "s2ms", "bitonic", "periodic3"])
def test_loms_merge2_compiles(one_chip, no_compile_cache, use_mxu, network):
    _compile(lambda a, b: loms_merge2_pallas(
        a, b, network=network, n_cols=4, block_batch=8, use_mxu=use_mxu,
        interpret=False),
        one_chip, ((8, 32), jnp.float32), ((8, 32), jnp.float32))


def test_loms_merge2_wide_compiles(one_chip, no_compile_cache):
    _compile(lambda a, b: loms_merge2_pallas(
        a, b, n_cols=16, block_batch=8, use_mxu=False, interpret=False,
        key_dtype="float32"),
        one_chip, ((64, 1024), jnp.float32), ((64, 1024), jnp.float32))


def test_loms_merge2_fused_payload_compiles(one_chip, no_compile_cache):
    _compile(lambda a, b, p: loms_merge2_pallas(
        a, b, (p,), n_cols=4, block_batch=8, use_mxu=False,
        interpret=False, key_dtype="float32", descending=True,
        want_perm=True),
        one_chip, ((8, 32), jnp.float32), ((8, 32), jnp.float32),
        ((8, 64), jnp.int32))


@pytest.mark.parametrize("key_dtype", [None, "float32"])
def test_kway_merge_compiles(one_chip, no_compile_cache, key_dtype):
    sched = loms_kway((7, 7, 7))
    _compile(lambda x: kway_merge_pallas(
        x, sched, block_batch=8, use_mxu=key_dtype is None,
        interpret=False, key_dtype=key_dtype),
        one_chip, ((8, 21), jnp.float32))


@pytest.mark.parametrize("key_dtype", [None, "float32"])
def test_loms_sort_compiles(one_chip, no_compile_cache, key_dtype):
    _compile(lambda x: loms_sort_pallas(
        x, block_batch=8, use_mxu=key_dtype is None, interpret=False,
        key_dtype=key_dtype),
        one_chip, ((8, 1024), jnp.float32))


def test_loms_sort_bf16_compiles(one_chip, no_compile_cache):
    # 16-bit rows walked one at a time travel widened to 32 bits
    _compile(lambda x: loms_sort_pallas(
        x, block_batch=16, use_mxu=False, interpret=False,
        key_dtype="bfloat16", descending=True),
        one_chip, ((16, 1000), jnp.bfloat16))


def test_loms_sort_payload_descending_compiles(one_chip, no_compile_cache):
    _compile(lambda x, p: loms_sort_pallas(
        x, (p,), block_batch=8, use_mxu=False, interpret=False,
        key_dtype="float32", descending=True, want_perm=True),
        one_chip, ((8, 1000), jnp.float32), ((8, 1000), jnp.int32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.uint8])
def test_segment_class_sort_compiles(one_chip, no_compile_cache, dtype):
    _compile(lambda x, n: segment_class_sort_pallas(
        x, n, k_out=16, flip=True, want_perm=True, block_batch=8,
        interpret=False),
        one_chip, ((16, 256), dtype), ((16, 1), jnp.int32))


def test_grid_merge_compiles(one_chip, no_compile_cache):
    from repro.streaming.grid_merge import grid_chunked_merge2

    _compile(lambda a, b: grid_chunked_merge2(a, b, tile=512, use_mxu=False,
                                              interpret=False),
             one_chip, ((8, 65_536), jnp.int32), ((8, 65_536), jnp.int32))
