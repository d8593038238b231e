"""The scheduler tick's host phases (``sched.*`` run spans) and the names of
the engine's programs.

With obs on, every tick is a row of non-overlapping phase spans around the
two program spans (``sched.prefill``, ``sched.decode``); with obs off
nothing is recorded. The phases are profiler annotations too, so they sit
on the profiler's host clock inside the tick that ran them, beside the
dispatch of the named ``sched_decode`` program, whose compiled HLO carries
the decode tick's five named scopes.
"""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.obs as obs
from repro.configs import get_smoke_config
from repro.models import model_init
from repro.obs import metrics, recorder, trace
from repro.serving.scheduler import SamplingParams, ScheduledEngine, SchedulerConfig

PHASES = ("sched.expire", "sched.admit", "sched.first_token", "sched.insert",
          "sched.decode_prep", "sched.decode_commit")
PROGRAMS = ("sched.prefill", "sched.decode")
TICK = ("sched.expire", "sched.admit", "sched.decode_prep", "sched.decode",
        "sched.decode_commit")
SCOPES = ("gather_view", "decode_step", "cache_write", "segment_topk", "draw")


@pytest.fixture(scope="module")
def engine():
    cfg = get_smoke_config("chatglm3-6b")
    params, _ = model_init(jax.random.PRNGKey(0), cfg)
    return ScheduledEngine(params, cfg, SchedulerConfig(n_slots=3, page_size=8,
                                                        pages_per_slot=4))


def _serve(eng):
    """Five requests of mixed k arriving at once into three slots: one
    greedy, one with top-p, one that finishes on its first token. Returns
    the rids and the number of ticks run."""
    rng = np.random.default_rng(5)
    specs = [SamplingParams(k=4, temperature=0.7, max_new_tokens=3, seed=1),
             SamplingParams(k=8, temperature=0.0, max_new_tokens=4, seed=2),
             SamplingParams(k=16, top_p=0.9, temperature=1.0, max_new_tokens=1, seed=3),
             SamplingParams(k=4, temperature=0.7, max_new_tokens=2, seed=4),
             SamplingParams(k=1, temperature=1.0, max_new_tokens=3, seed=5)]
    t0 = eng.t
    rids = [eng.submit(rng.integers(1, eng.cfg.vocab_size, 5 + i).astype(np.int32),
                       sp, arrival=t0) for i, sp in enumerate(specs)]
    eng.run()
    return rids, eng.t - t0


@pytest.fixture(scope="module", params=[True, False], ids=["obs_on", "obs_off"])
def served(engine, request):
    prev = obs.set_enabled(request.param)
    trace.clear()
    metrics.reset()
    recorder.clear()
    try:
        rids, ticks = _serve(engine)
        return {"on": request.param, "rids": rids, "ticks": ticks,
                "spans": trace.spans(), "waterfalls": obs.request_waterfalls(),
                "requests": {r: engine.requests[r] for r in rids}}
    finally:
        trace.clear()
        metrics.reset()
        recorder.clear()
        obs.set_enabled(prev)


def _ticks(spans):
    """The scheduler's spans in start order, cut into ticks at each
    ``sched.expire`` (a tick's first phase)."""
    ticks = []
    for sp in sorted((s for s in spans if s.name.startswith("sched.")),
                     key=lambda s: s.t0_ns):
        if sp.name == "sched.expire":
            ticks.append([])
        ticks[-1].append(sp)
    return ticks


def test_every_tick_runs_its_phases(served):
    if not served["on"]:
        assert served["spans"] == ()
        return
    ticks = _ticks(served["spans"])
    assert len(ticks) == served["ticks"]
    for tick in ticks:
        names = [sp.name for sp in tick]
        assert [n for n in names if n in TICK] == list(TICK)
        assert names[:2] == ["sched.expire", "sched.admit"]
        assert names[-3:] == ["sched.decode_prep", "sched.decode", "sched.decode_commit"]
        # each prefill batch is followed by one first draw and one insert
        # per admitted request, in that order
        i = 2
        while names[i] == "sched.prefill":
            batch = tick[i].attrs["batch"]
            per_req = names[i + 1:i + 1 + 2 * batch]
            assert per_req == ["sched.first_token", "sched.insert"] * batch
            rids = [sp.attrs["rid"] for sp in tick[i + 1:i + 1 + 2 * batch]]
            assert rids[0::2] == rids[1::2]
            i += 1 + 2 * batch
        assert i == len(names) - 3
    first = [sp for sp in served["spans"] if sp.name == "sched.first_token"]
    assert sorted(sp.attrs["rid"] for sp in first) == sorted(served["rids"])
    assert sum(sp.attrs["batch"] for sp in served["spans"] if sp.name == "sched.prefill") \
        == len(served["rids"])
    assert {sp.attrs["k"] for sp in first} == {1, 4, 8, 16}
    for sp in served["spans"]:
        if sp.name in PHASES + PROGRAMS:
            assert sp.kind == "run"
            assert all(isinstance(v, int) for v in sp.attrs.values())


def test_phases_never_overlap(served):
    spans = sorted((s for s in served["spans"] if s.name in PHASES + PROGRAMS),
                   key=lambda s: s.t0_ns)
    assert bool(spans) == served["on"]
    for a, b in zip(spans, spans[1:]):
        assert a.t0_ns + a.dur_ns <= b.t0_ns, (a.name, b.name)
        assert a.parent_id is None and b.parent_id is None


def test_waterfalls_reconcile_beside_the_phases(served):
    wfs = served["waterfalls"]
    if not served["on"]:
        assert wfs == []
        return
    assert sorted(w["rid"] for w in wfs) == sorted(served["rids"])
    for w in wfs:
        r = served["requests"][w["rid"]]
        assert w["state"] == "done"
        assert w["ttft_ns"] == r.t_first_ns - r.t_submit_ns
        assert w["total_ns"] == r.t_finish_ns - r.t_submit_ns
        assert w["unaccounted_ns"] >= 0
        assert w["decode_ticks"] == len(r.tokens) - 1
        assert [s["name"] for s in w["stages"]][:3] == ["req.queue_wait", "req.prefill",
                                                        "req.insert"]
    assert {len(r.tokens) for r in served["requests"].values()} == {1, 2, 3, 4}


def _host_events(path):
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    plane = next(p for p in pd.planes if p.name == "/host:CPU")
    return [(int(ev.start_ns), int(ev.end_ns), ev.name,
             dict(ev.stats) if ev.name == "sched.decode" else {})
            for line in plane.lines for ev in line.events]


def test_phases_share_the_profilers_clock(engine, tmp_path):
    """One short run under the profiler: the phases are host annotations
    inside the tick that ran them, beside the named decode program's
    dispatch; the decode program's HLO carries the five named scopes."""
    _serve(engine)  # obtain every program outside the profiled run
    prev = obs.set_enabled(True)
    step = engine.step

    def tick():
        with jax.profiler.TraceAnnotation("test.tick"):
            step()

    engine.step = tick
    try:
        with jax.profiler.trace(str(tmp_path)):
            _, n_ticks = _serve(engine)
    finally:
        del engine.step
        trace.clear()
        obs.set_enabled(prev)
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True)[0]
    events = _host_events(path)
    ticks = [(a, b) for a, b, n, _ in events if n == "test.tick"]
    assert len(ticks) == n_ticks
    sched = [e for e in events if e[2] in PHASES + PROGRAMS]
    assert {n for _, _, n, _ in sched} == set(PHASES + PROGRAMS)
    for a, b, name, _ in sched:
        assert sum(ta <= a and b <= tb for ta, tb in ticks) == 1, name
    decodes = [(a, b, s) for a, b, n, s in sched if n == "sched.decode"]
    assert len(decodes) == n_ticks and all("batch" in s for _, _, s in decodes)
    dispatch = [(a, b) for a, b, n, _ in events if n == "PjitFunction(sched_decode)"]
    assert dispatch
    for a, b in dispatch:
        assert any(da <= a and b <= db for da, db, _ in decodes)

    n = 2
    sig = (SamplingParams(k=4, temperature=0.7).sig, SamplingParams(k=8, temperature=0.0).sig)
    hlo = engine._decode_fn(sig).lower(
        engine.params, engine.pool.leaves,
        jnp.zeros((n, engine.sc.pages_per_slot), jnp.int32), jnp.zeros(n, jnp.int32),
        jnp.zeros((n, 1), jnp.int32), jnp.stack([jax.random.PRNGKey(0)] * n),
        jnp.ones(n), jnp.ones(n)).compile().as_text()
    assert hlo.startswith("HloModule jit_sched_decode")
    for scope in SCOPES:
        assert f'op_name="jit(sched_decode)/{scope}/' in hlo, scope
