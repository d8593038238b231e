"""The reduction from a profiler trace to busy/idle time, per-op device
time, attribution to host annotations and the breakdown."""
import os

import pytest

from bench import trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_and_gaps():
    iv = [(0, 10), (5, 20), (30, 40), (35, 36)]
    assert trace_reduce.union_length(iv) == 30
    assert trace_reduce.gaps(iv, -5, 50) == [(-5, 0), (20, 30), (40, 50)]
    assert trace_reduce.gaps([], 0, 7) == [(0, 7)]
    assert trace_reduce.union_length([]) == 0


def test_op_label_is_the_instruction_name():
    text = "%vocab_topk_pallas.14 = (s32[2048,16,64]) custom-call(s32[4096,16,64] %p)"
    assert trace_reduce.op_label(text) == "vocab_topk_pallas.14"
    assert trace_reduce.op_label("%fusion.5 = bf16[8] fusion()") == "fusion.5"
    assert trace_reduce.CONTAINERS.match("while.34")
    assert not trace_reduce.CONTAINERS.match("fusion.3")


@pytest.fixture(scope="module")
def chip_trace():
    """Five sampling calls (16 x 151,936 bf16, mixed k) traced on a TPU v5
    lite, each inside a ``bench.call`` annotation, all inside ``bench.slice``."""
    return trace_reduce.reduce(os.path.join(DATA, "sample_call.xplane.pb"))


def test_chip_trace_busy_and_idle(chip_trace):
    assert chip_trace["chips"] == 1
    assert chip_trace["window_s"] == pytest.approx(0.248, abs=0.002)
    assert 0 < chip_trace["busy_s"] < chip_trace["window_s"]
    idle = chip_trace["window_s"] - chip_trace["busy_s"]
    assert sum(s for _, s in chip_trace["idle_gaps"]) == pytest.approx(idle, rel=1e-6)
    assert chip_trace["idle_gaps"][0][0] == "bench.call"


def test_chip_trace_kernel_time_and_attribution(chip_trace):
    from bench import run as bench_run

    assert chip_trace["calls"] == [0, 1, 2, 3, 4]
    ops = chip_trace["ops"]
    kernels = {k: v for k, v in ops.items() if k.startswith("vocab_topk_pallas")}
    assert len(kernels) == 13  # block pass and merge levels of the vocabulary top-k
    # every device op of the slice ran inside one of the five calls
    inside = chip_trace["ops_in"]["bench.call"]
    assert sum(inside.values()) == pytest.approx(sum(ops.values()), rel=1e-9)
    assert len(chip_trace["device_ops"]) == 10
    assert chip_trace["device_ops"][0][1] >= chip_trace["device_ops"][-1][1]
    reader = bench_run.load_reader("topk_roofline.sample")
    rec = {"trace": chip_trace, "least_time_s_per_call": 6e-6, "peaks": {"hbm_bytes_per_s": 819e9}}
    share = reader.read(rec, "topk_roofline.sample")
    # the sampler cell's roofline is the whole call's: every device op in it
    assert chip_trace["call_share"] == pytest.approx(5.0, rel=1e-9)
    assert share == pytest.approx(100 * 5 * 6e-6 / sum(inside.values()), rel=1e-9)
    assert 0 < share < 1
    # serving cells count the top-k kernels alone: the gather before them is out
    assert 0 < reader.kernel_time(inside) < sum(inside.values())
    idle = bench_run.load_reader("idle_share.sample").read(rec, "idle_share.sample")
    assert 0 < idle < 100
