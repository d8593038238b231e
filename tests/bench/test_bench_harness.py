"""The harness finds everything by name, keeps BENCHMARK.json to its schema,
generates the same traffic for the same seed, and refuses to run off-chip."""
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

from bench import run as bench_run
from bench import traffic

ROOT = bench_run.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return bench_run.load_benchmark()


def test_every_workload_resolves_by_name(bench):
    for cell in bench["workloads"]:
        spec = bench_run.resolve(bench, cell["name"])
        assert spec["config"]["name"] == cell["config"]
        assert os.path.exists(os.path.join(ROOT, "bench", "drivers", f"{spec['driver']}.py"))
        assert any(m["name"] == "setup_s" for m in spec["end_to_end"])
        assert len(spec["end_to_end"]) >= 2 and spec["per_layer"]
        moved = {m["name"] for m in spec["end_to_end"]}
        for m in spec["per_layer"]:
            assert m["moves"] in moved, (cell["name"], m["name"])
            assert hasattr(bench_run.load_reader(m["name"]), "read")


def test_benchmark_json_keeps_to_its_schema(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    keys = {"configs": {"name", "source", "file", "reduced", "why"},
            "workloads": {"name", "config", "traffic", "chips", "why"},
            "end_to_end": {"name", "unit", "better", "bound", "source"},
            "per_layer": {"name", "unit", "better", "source", "layer", "moves"}}
    for group, allowed in keys.items():
        names = [e["name"] for e in bench[group]]
        assert len(names) == len(set(names))
        for e in bench[group]:
            assert set(e) - {"workloads"} == allowed, (group, e["name"])
            assert NAME.match(e["name"])
            if "unit" in e:
                assert UNIT.match(e["unit"])
                assert e["better"] in ("lower", "higher")
    for c in bench["configs"]:
        assert c["file"].startswith("bench/") and os.path.exists(os.path.join(ROOT, c["file"]))
        with open(os.path.join(ROOT, c["file"])) as f:
            entry = json.load(f)
        assert entry["reduced"] == c["reduced"] and entry["source"] == c["source"]
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for p in bench["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
    assert 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def _at(entry: dict, dotted: str):
    """``entry``'s value at a key path such as ``moe.n_experts``."""
    for key in dotted.split("."):
        entry = entry[key]
    return entry


def test_config_files_match_their_published_keys(bench):
    """The model block the program runs agrees with the published keys: each
    configuration file maps a model-block key (dotted into a nested group)
    to the published key it copies, under ``published_keys``, which no run
    reads."""
    for c in bench["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            entry = json.load(f)
        assert entry["published_keys"], c["name"]
        for ours, published in entry["published_keys"].items():
            assert _at(entry["model"], ours) == _at(entry, published), (c["name"], ours)


@pytest.mark.parametrize("mix", ["chat", "batch"])
def test_traffic_is_deterministic_per_seed_and_clipped(mix):
    m = traffic.load(mix)
    a = traffic.requests(m, 64, 1000, 2**40 + 1)
    b = traffic.requests(m, 64, 1000, 2**40 + 1)
    c = traffic.requests(m, 64, 1000, 7)
    for x, y in zip(a, b):
        assert x["due_s"] == y["due_s"] and x["max_new"] == y["max_new"]
        assert np.array_equal(x["prompt"], y["prompt"])
    plen = [r["prompt"].size for r in a]
    olen = [r["max_new"] for r in a]
    assert m["prompt"]["min"] <= min(plen) and max(plen) <= m["prompt"]["max"]
    assert m["output"]["min"] <= min(olen) and max(olen) <= m["output"]["max"]
    # another seed: the same sizes, reordered only within each block
    block = m["order_block"]
    for i in range(0, 64, block):
        blk = slice(i, i + block)
        assert sorted(plen[blk]) == sorted(r["prompt"].size for r in c[blk])
    assert ([r["prompt"].size for r in c] != plen) == (block > 1)
    assert not np.array_equal(a[0]["prompt"], c[0]["prompt"])
    assert all(0 <= r["prompt"].min() and r["prompt"].max() < 1000 for r in a)
    due = [r["due_s"] for r in a]
    assert due == sorted(due) and due[0] == 0.0
    if m.get("rate_per_s"):
        assert abs(len(due) / due[-1] - m["rate_per_s"]) < 0.5 * m["rate_per_s"]


def test_run_exits_non_zero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", "qwen3-8b.sample",
                        "--seed", "3", "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_reader_lookup_falls_back_to_the_stem():
    assert bench_run.reader_path("idle_share.chat").endswith("idle_share.py")
    assert bench_run.reader_path("idle_share.some_later_cell").endswith("idle_share.py")
