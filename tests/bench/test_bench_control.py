"""The control — the plain reference one precision below the
configuration's (fp8 for bf16), put in the program's place — comes out
worse than the program on the numbers each cell compares, at a size a
test run can hold, judged by the harness's own ``Check`` at the committed
limits. The chip readings at the cells' own sizes, from
``bench/control.py``, are in PERF.md with the limits set from them."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import control, run as bench_run
from bench.drivers import sampler, serve
from bench.harness import Check, Ctx
from cells import cells_of, shrink


def test_sampler_control_fails_where_the_program_is_exact():
    ks = (1, 8, 20, 40, 50, 64, 50, 40)
    x = (jax.random.normal(jax.random.PRNGKey(3), (8, 4096)) * 2).astype(jnp.bfloat16)
    host = np.asarray(x.astype(jnp.float32))
    exact = np.stack([np.sort(r)[::-1] for r in host])
    vals = np.concatenate([exact[s, :k] for s, k in enumerate(ks)])
    toks = np.argmax(host, axis=-1)
    bad_vals, bad_toks = sampler.compare(host, vals, toks, ks, (1.0,) * 8, 0.8)
    ctrl = control.fp8_topk_rows_differing(host, ks)
    cells = cells_of("sampler")
    assert cells
    for cell in cells:
        limit = bench_run.resolve(bench_run.load_benchmark(), cell)["traffic"]["check"][
            "topk_rows_differing"]
        assert not Check("topk_rows_differing", ctrl, limit).ok
        assert Check("topk_rows_differing", bad_vals, limit).ok
        assert Check("tokens_outside_candidates", bad_toks, limit).ok


@pytest.mark.parametrize("cell", cells_of("serve"))
def test_serve_control_reads_above_the_program(cell):
    """At smoke size the fp8 control's widest gap often stays under the
    committed limit (the limit is read at the cells' own widths, where the
    control fails it on every seed: PERF.md), so here it has to read above
    the program, which passes the committed limit."""
    spec = bench_run.resolve(bench_run.load_benchmark(), cell)
    shrink(spec)
    # greedy, the contract's own reading (a top-k draw's gap at k = 1), over
    # more requests: at smoke size a top-50 of 300 tokens seldom moves
    mix = spec["traffic"]
    spec["traffic"] = dict(mix, sampling=dict(mix["sampling"], k=1),
                           check=dict(mix["check"], requests=6))
    ctx = Ctx(cell=cell, config=spec["config"], traffic=spec["traffic"],
              seed=12345, seconds=1.5, trace=False, t_start_ns=time.perf_counter_ns(),
              peaks={"bf16_flops": 1.0, "hbm_bytes_per_s": 1.0})
    srv = serve.Server(ctx)
    out = control.serve_readings(srv, ctx)
    assert out["tokens"] > 0
    assert out["control_served_token_gap"] > out["served_token_gap"]
    assert out["program_ok"]
