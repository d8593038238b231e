"""Serving driver: the program's ``ScheduledEngine`` on the wall clock.

Two loops, chosen by the mix's ``loop``:

* ``open``: requests arrive at fixed due times (exponential gaps at
  ``rate_per_s``), whatever the server is doing. A request is submitted
  once its due time has passed, with ``arrival=eng.t``; time to first token
  runs from its due time, so a stall also charges the requests it delays.
  Arrivals start ``ramp_s`` before the window so that it opens on a loaded
  server; every request due in the window is followed to its first token.
* ``backlog``: every request is queued at the start (offline batch jobs);
  the window opens once every slot is busy.

After every ``step()`` the driver stamps the host clock; a request's token
times are its ``t_first_ns`` and then the stamps of the steps in which its
token count grew. Inter-token gaps are differences of those times.

Set-up builds the weights on the device, then obtains every program the
mix can reach: each prefill (bucket, batch) and insert (pages, bucket,
batch) shape its prompt lengths allow, and the decode program of every
batch size from 1 to the slot count; then a rehearsal of 16 requests
through the public API covers the host-side eager operations (first-token
sampling, key stacking and indexing) at every batch size.

Set-up ends with the collector settled (``bench.harness.settle``): what
set-up allocated is frozen out of the collector's reach, so that a full
collection in the window does not walk it.

Correctness: after the window, a sample of finished requests drawn from
the seed, with the one that served the most tokens, is run through the
configuration's plain float32 reference over its prompt and served tokens:
``bench/<module>.py`` for the configuration file's ``"reference":
"<module>"``, ``bench/reference.py`` without the key (``reference``). A
top-k draw is one of its row's k best, so no served token's reference
logit may lie below the reference's k-th best by more than the limit: the
widest such gap is compared. A program obtained (compiled or loaded)
inside the window, which a fallback would need, and a launch run again
after a failure both make the run not correct.
"""
from __future__ import annotations

import gc
import importlib
import math
import re
from typing import Dict, List

import numpy as np

from bench import traffic as traffic_gen
from bench import work
from bench.harness import (Check, CompileCounter, Ctx, GcWatch, Outcome, TraceSlice, log,
                           memory_peak_bytes, now_ns, percentile, settle)


def model_config(entry: dict):
    """The program's ``ModelConfig`` of a configuration file's model block;
    nested ``mla`` and ``moe`` groups become the program's own dataclasses
    (a JSON list a tuple, so the config stays hashable)."""
    from repro.configs.base import MLAConfig, ModelConfig, MoEConfig

    m = dict(entry["model"])
    for key, group in (("mla", MLAConfig), ("moe", MoEConfig)):
        if isinstance(m.get(key), dict):
            m[key] = group(**{k: tuple(v) if isinstance(v, list) else v
                              for k, v in m[key].items()})
    return ModelConfig(**m)


def reference(entry: dict):
    """The configuration's plain reference module, ``bench/<name>.py`` for its
    ``"reference"`` key (``reference`` without it). A reference module keeps
    the contract written in ``bench/reference.py``'s docstring."""
    name = entry.get("reference", "reference")
    if not re.fullmatch(r"[A-Za-z_][A-Za-z0-9_]*", name):
        raise ValueError(f"reference {name!r} names no module under bench/")
    return importlib.import_module(f"bench.{name}")


def sched_config(entry: dict, mix: dict):
    from repro.serving.scheduler import SchedulerConfig

    s = entry["scheduler"]
    need = mix["prompt"]["max"] + mix["output"]["max"]
    return SchedulerConfig(n_slots=s["n_slots"], page_size=s["page_size"],
                           pages_per_slot=math.ceil(need / s["page_size"]),
                           max_prefill_batch=s["max_prefill_batch"])


def sampling(mix: dict, max_new: int, seed: int):
    from repro.serving.scheduler import SamplingParams

    sp = mix["sampling"]
    return SamplingParams(k=sp["k"], temperature=sp["temperature"],
                          top_p=sp.get("top_p", 1.0), max_new_tokens=max_new,
                          seed=seed)


class Server:
    """Weights, engine and warm programs of one serving cell."""

    def __init__(self, ctx: Ctx):
        import jax

        from repro.serving.scheduler import ScheduledEngine

        from bench.weights import make_weights, param_bytes

        self.mix = ctx.traffic
        self.cfg = model_config(ctx.config)
        self.sc = sched_config(ctx.config, self.mix)
        self.params = make_weights(self.cfg, ctx.seed)
        jax.block_until_ready(self.params)
        log(f"[serve] {self.cfg.name}: {self.cfg.n_layers} layers, weights "
            f"{param_bytes(self.params) / 1e9:.3f} GB {self.cfg.dtype}; "
            f"{self.sc.n_slots} slots x {self.sc.pages_per_slot} pages of "
            f"{self.sc.page_size}")
        self.eng = ScheduledEngine(self.params, self.cfg, self.sc)
        self.warm()

    # ------------------------------------------------------------ set-up

    def reachable(self):
        """{bucket: sorted page counts} over every prompt length the mix
        can draw."""
        p = self.mix["prompt"]
        ps = self.sc.page_size
        out: Dict[int, set] = {}
        for n in range(p["min"], p["max"] + 1):
            out.setdefault(self.eng._bucket(n), set()).add(math.ceil(n / ps))
        return {b: sorted(v) for b, v in sorted(out.items())}

    def warm(self) -> None:
        import jax
        import jax.numpy as jnp

        eng, sc = self.eng, self.sc
        t0 = now_ns()
        n_prog = 0
        for blen, npgs in self.reachable().items():
            for bb in range(1, sc.max_prefill_batch + 1):
                toks = jnp.zeros((bb, blen), jnp.int32)
                lens = jnp.full((bb,), blen, jnp.int32)
                _, body = eng._prefill_fn(blen, bb)(eng.params, toks, lens)
                n_prog += 1
                for npg in npgs:  # page 0 is scratch: writing it is harmless
                    eng.pool.leaves = eng._insert_fn(npg, blen, bb)(
                        eng.pool.leaves, body, jnp.int32(0),
                        jnp.asarray(np.zeros(npg, np.int32)))
                    n_prog += 1
        sig = sampling(self.mix, 1, 0).sig
        key = jax.random.split(jax.random.PRNGKey(0))[0]
        for n in range(1, sc.n_slots + 1):
            eng.pool.leaves, _, toks = eng._decode_fn((sig,) * n)(
                eng.params, eng.pool.leaves,
                jnp.asarray(np.zeros((n, sc.pages_per_slot), np.int32)),
                jnp.asarray(np.zeros(n, np.int32)),
                jnp.asarray(np.zeros((n, 1), np.int32)),
                jnp.stack([key] * n),
                jnp.asarray(np.full(n, 1.0, np.float32)),
                jnp.asarray(np.full(n, 1.0, np.float32)))
            n_prog += 1
        jax.block_until_ready(eng.pool.leaves)
        t1 = now_ns()
        self.rehearse()
        log(f"[serve] warm: {n_prog} programs in {(t1 - t0) / 1e9:.1f} s, "
            f"rehearsal {(now_ns() - t1) / 1e9:.1f} s")

    def rehearse(self) -> None:
        """16 short requests through submit/step: admissions of 4, 3, 2, 1,
        4 and 2 in consecutive ticks, then one finishing per tick, so every
        prefill batch size and every decode batch size from the slot count
        down to 1 runs through the engine's host code once."""
        eng = self.eng
        plen = self.mix["prompt"]["min"]
        groups = [4, 3, 2, 1, 4, 2]
        n = min(sum(groups), self.sc.n_slots)
        rng = np.random.default_rng(0)
        rid = 0
        tick0 = eng.t
        for g, size in enumerate(groups):
            for _ in range(size):
                if rid >= n:
                    break
                finish = len(groups) + 2 + rid  # distinct finishing ticks
                eng.submit(rng.integers(0, self.cfg.vocab_size, plen, dtype=np.int32),
                           sampling(self.mix, finish - g + 1, rid), arrival=tick0 + g)
                rid += 1
        eng.run()

    # ------------------------------------------------------------ window

    def drive(self, ctx: Ctx) -> dict:
        """One window of the mix on the wall clock; returns the run record."""
        import jax.profiler as jprof

        from repro.obs import trace as obs_trace

        mix, eng = self.mix, self.eng
        open_loop = mix["loop"] == "open"
        seconds_ns = int(ctx.seconds * 1e9)
        if open_loop:
            horizon = mix["ramp_s"] + ctx.seconds + mix["drain_s"]
            n_req = math.ceil(mix["rate_per_s"] * horizon)
        else:
            n_req = mix["backlog"]
        reqs = traffic_gen.requests(mix, n_req, self.cfg.vocab_size, ctx.seed)
        if ctx.fault is not None:
            ctx.fault("serve.engine", eng)
        counter = CompileCounter()
        gcw = GcWatch()
        retries = self.count_retries(counter)
        obs_trace.clear()
        obs_trace.set_enabled(ctx.trace)

        rids: List[int] = []
        due_ns: Dict[int, int] = {}
        times: Dict[int, List[int]] = {}  # rid -> token times (ns)
        live: Dict[int, int] = {}  # rid -> tokens seen
        steps = []  # (index, t_start, t_end, decode_rows)
        late = []
        flops = []  # (t_ns, flops) of work finished in each step

        t_arr = now_ns()
        if open_loop:
            w0 = t_arr + int(mix["ramp_s"] * 1e9)
            w1 = w0 + seconds_ns
        else:
            w0 = w1 = None
        slice_ = None
        i = 0
        drain_until = None
        m = ctx.config["model"]

        def submit_due(t):
            nonlocal i
            with jprof.TraceAnnotation("bench.submit"):
                while i < n_req and (not open_loop or t_arr + int(reqs[i]["due_s"] * 1e9) <= t):
                    r = reqs[i]
                    due = t_arr + int(r["due_s"] * 1e9)
                    rid = eng.submit(r["prompt"], sampling(mix, r["max_new"], r["sample_seed"]),
                                     arrival=eng.t)
                    rids.append(rid)
                    due_ns[rid] = due
                    times[rid] = []
                    live[rid] = 0
                    if open_loop:
                        late.append(max(t - due, 0))
                    i += 1

        if not open_loop:
            submit_due(t_arr)

        while True:
            t = now_ns()
            if slice_ is None and w0 is not None and t >= w0:
                start = w0 + seconds_ns // 3
                slice_ = TraceSlice(ctx.trace, start, start + int(mix["trace_s"] * 1e9))
                counter.armed = gcw.armed = True
            if slice_ is not None:
                slice_.poll(t)
            if w1 is not None and t >= w1:
                counter.armed = gcw.armed = False
                if drain_until is None:
                    drain_until = t + int(mix["drain_s"] * 1e9)
                waiting = [r for r in rids if w0 <= due_ns[r] < w1 and not times[r]
                           and eng.requests[r].state.value not in ("failed", "timed_out")]
                if not open_loop or not waiting or t >= drain_until:
                    break
            if open_loop:
                submit_due(t)
            if not (len(eng.queue) or eng.active):
                if i >= n_req:
                    break
                nxt = t_arr + int(reqs[i]["due_s"] * 1e9)
                with jprof.TraceAnnotation("bench.wait"):
                    _sleep_until(nxt)
                continue
            idx = len(steps)
            t_beg = now_ns()
            with jprof.TraceAnnotation("bench.step", step=idx):
                eng.step()
            t_end = now_ns()
            rows = 0
            work_done = 0
            for rid in list(live):
                r = eng.requests[rid]
                n = len(r.tokens)
                seen = live[rid]
                if n > seen:
                    plen = int(r.prompt.size)
                    for j in range(seen, n):
                        if j == 0:
                            times[rid].append(r.t_first_ns)
                            work_done += work.prefill_flops(m, plen)
                        else:
                            times[rid].append(t_end)
                            work_done += work.decode_flops(m, plen + j)
                            rows += 1 if j == n - 1 else 0
                    live[rid] = n
                if r.state.value not in ("queued", "running"):
                    del live[rid]
            steps.append((idx, t_beg, t_end, rows))
            flops.append((t_end, work_done))
            if not open_loop and w0 is None and len(eng.active) == self.sc.n_slots:
                w0 = t_end
                w1 = w0 + seconds_ns
                counter.armed = gcw.armed = True
        t_stop = now_ns()
        counter.armed = False
        gcw.close()
        if slice_ is not None:
            slice_.finish()
        if not open_loop:
            # the window closes at the end of the last step begun inside it
            ends = [s[2] for s in steps if w0 is not None and s[1] < w1 and s[2] > w0]
            w1 = max(ends) if ends else t_stop
        return {
            "rids": rids, "due_ns": due_ns, "times": times, "steps": steps,
            "late_ns": late, "flops": flops, "w0": w0, "w1": w1,
            "compiles_in_window": counter.count, "compiled": counter.names,
            "retries_in_window": retries[0], "gc": gcw.summary(),
            "slice": slice_, "spans": [s.to_dict() for s in obs_trace.spans()],
        }

    # ------------------------------------------------------------ results

    def end_to_end(self, rec: dict) -> dict:
        w0, w1 = rec["w0"], rec["w1"]
        ttft = [(rec["times"][r][0] - rec["due_ns"][r]) / 1e6 for r in rec["rids"]
                if w0 <= rec["due_ns"][r] < w1 and rec["times"][r]]
        gaps, n_out = [], 0
        for r in rec["rids"]:
            ts = rec["times"][r]
            n_out += sum(1 for t in ts if w0 < t <= w1)
            gaps += [(b - a) / 1e6 for a, b in zip(ts, ts[1:]) if w0 < b <= w1]
        window_s = (w1 - w0) / 1e9
        out = {"window_s": window_s, "ttft_ms": ttft, "itl_ms": gaps,
               "output_tokens": n_out}
        if ttft:
            out["ttft_p50_ms"] = percentile(ttft, 50)
        if gaps:
            out["itl_p95_ms"] = percentile(gaps, 95)
        out["output_tok_per_s"] = n_out / window_s
        return out

    def count_retries(self, counter: CompileCounter) -> List[int]:
        """Launches the engine ran again after a failure while ``counter``
        is armed: the engine's own ``sched.retries`` counter is inert with
        obs off. Each launch closure is wrapped to count its attempts."""
        eng = self.eng
        launch = eng._with_retry
        n = [0]

        def with_retry(what, fn):
            tries = [0]

            def attempt():
                tries[0] += 1
                return fn()

            try:
                return launch(what, attempt)
            finally:
                if counter.armed:
                    n[0] += max(tries[0] - 1, 0)

        eng._with_retry = with_retry
        return n

    def due_in_window(self, rec: dict) -> List[int]:
        return [r for r in rec["rids"] if rec["w0"] <= rec["due_ns"][r] < rec["w1"]]

    def pick_checked(self, rec: dict, seed: int) -> List[int]:
        """Finished requests to compare: the one that served the most
        tokens, then others drawn from the seed."""
        done = [r for r in rec["rids"] if self.eng.requests[r].state.value == "done"]
        if not done:
            return []
        want = self.mix["check"]["requests"]
        longest = max(done, key=lambda r: (len(self.eng.requests[r].tokens), -r))
        rest = [r for r in done if r != longest]
        rng = np.random.default_rng([seed, 11])
        pick = rng.choice(len(rest), size=min(want - 1, len(rest)), replace=False) if rest else []
        return [longest] + [rest[j] for j in sorted(pick)]

    def checked_requests(self, rids: List[int]) -> list:
        out = []
        for r in rids:
            q = self.eng.requests[r]
            out.append((np.asarray(q.prompt, np.int32), np.asarray(q.tokens, np.int32),
                        q.params.k))
        return out

    def set_weights(self, seed: int) -> None:
        """New weights from ``seed``, served by the same warm programs."""
        from bench.weights import make_weights

        self.params = self.eng.params = None
        gc.collect()
        self.params = self.eng.params = make_weights(self.cfg, seed)

    def fresh_engine(self) -> None:
        """An empty engine (queue, slots, pool) that keeps the programs."""
        from repro.serving.scheduler import ScheduledEngine

        old = self.eng
        old.pool.leaves = None
        gc.collect()
        self.eng = ScheduledEngine(self.params, self.cfg, self.sc)
        for name in ("_prefill_jits", "_insert_jits", "_decode_jits", "_decode_seen"):
            setattr(self.eng, name, getattr(old, name))

    def free_engine(self) -> None:
        self.eng.pool.leaves = None
        self.eng = None
        gc.collect()


def _sleep_until(t_ns: int) -> None:
    import time

    dt = (t_ns - now_ns()) / 1e9
    if dt > 0:
        time.sleep(dt)


def served_gaps(params, entry: dict, mix: dict, checked: list, precision: str = "f32",
                control_seed=None) -> List[float]:
    """Per request, the widest gap by which a served token's reference logit
    lies below the reference's k-th best at its position; the reference is
    the one the configuration file ``entry`` names (``reference``).

    With ``control_seed`` the served tokens are replaced by the tokens the
    reference at ``precision`` would draw itself (top-k at the mix's
    temperature, keys from the seed), and their gaps are read against the
    float32 reference: that is the control put in the program's place."""
    import jax
    import jax.numpy as jnp

    from bench.weights import seed_key

    ref_mod, model = reference(entry), entry["model"]
    s_pad = mix["prompt"]["max"] + mix["output"]["max"]
    n_pad = mix["output"]["max"]
    temp = mix["sampling"]["temperature"]
    out = []
    for j, (prompt, toks, k) in enumerate(checked):
        n = len(toks)
        seq = np.zeros(s_pad, np.int32)
        seq[:prompt.size] = prompt
        seq[prompt.size:prompt.size + n - 1] = toks[:-1]
        rows = np.full(n_pad, prompt.size - 1, np.int32)
        rows[:n] = prompt.size - 1 + np.arange(n)
        ref = ref_mod.logits_at(params, model, seq, rows, "f32")[:n]
        kth = jax.lax.top_k(ref, k)[0][:, k - 1]
        if control_seed is None:
            drawn = jnp.asarray(toks)
        else:
            low = ref_mod.logits_at(params, model, seq, rows, precision)[:n]
            vals, idx = jax.lax.top_k(low, k)
            keys = jax.random.split(seed_key(control_seed, salt=100 + j), n)
            choice = jax.vmap(lambda kk, v: jax.random.categorical(kk, v / temp))(keys, vals)
            drawn = jnp.take_along_axis(idx, choice[:, None], axis=1)[:, 0]
        got = jnp.take_along_axis(ref, drawn[:, None].astype(jnp.int32), axis=1)[:, 0]
        out.append(float(jnp.max(jnp.maximum(kth - got, 0.0))))
    return out


def run(ctx: Ctx) -> Outcome:
    import jax

    srv = Server(ctx)
    settle()
    setup_s = (now_ns() - ctx.t_start_ns) / 1e9
    rec = srv.drive(ctx)
    gc.unfreeze()  # what set-up made may be collected again
    mem = memory_peak_bytes(jax.local_devices()[:1])
    e2e = srv.end_to_end(rec)
    mix = srv.mix

    due = srv.due_in_window(rec) if mix["loop"] == "open" else []
    bad_states = ("failed", "timed_out", "rejected")
    failed = sum(1 for r in rec["rids"] if srv.eng.requests[r].state.value in bad_states)
    never = sum(1 for r in due if not rec["times"][r])
    if mix["loop"] == "open":
        attempted = len(due)
    else:  # requests that emitted a token in the window
        attempted = sum(1 for r in rec["rids"]
                        if any(rec["w0"] < t <= rec["w1"] for t in rec["times"][r]))
    late = rec["late_ns"]
    if late:
        log(f"[serve] generator lateness: median {percentile(late, 50) / 1e6:.3f} ms, "
            f"p99 {percentile(late, 99) / 1e6:.3f} ms over {len(late)} submissions")
    if e2e["ttft_ms"]:
        log(f"[serve] ttft p50 {percentile(e2e['ttft_ms'], 50):.1f} ms, p90 "
            f"{percentile(e2e['ttft_ms'], 90):.1f} ms, p95 {percentile(e2e['ttft_ms'], 95):.1f} ms")
    log(f"[serve] window {e2e['window_s']:.3f} s: {len(e2e['ttft_ms'])} first tokens, "
        f"{len(e2e['itl_ms'])} gaps, {e2e['output_tokens']} output tokens, "
        f"{len(rec['steps'])} steps, {rec['compiles_in_window']} programs obtained "
        f"in the window {sorted(set(rec['compiled']))[:8]}")
    inside = [s for s in rec["steps"] if rec["w0"] <= s[1] < rec["w1"]]
    if inside:
        longest = max(inside, key=lambda s: s[2] - s[1])
        between = max(((b[1] - a[2], b[0]) for a, b in zip(inside, inside[1:])), default=(0, -1))
        log(f"[serve] longest step in the window {(longest[2] - longest[1]) / 1e6:.1f} ms "
            f"(step {longest[0]}), longest host gap between steps {between[0] / 1e6:.1f} ms "
            f"(before step {between[1]}); collector in the window: {rec['gc']}")
    spans = [sp for sp in rec["spans"] if sp["name"].startswith("sched.")
             and rec["w0"] <= sp["ts_ns"] < rec["w1"]]
    if spans:  # traced runs: where inside a step the longest wait was
        sp = max(spans, key=lambda sp: sp["dur_ns"])
        log(f"[serve] longest program span in the window: {sp['name']} "
            f"{sp['dur_ns'] / 1e6:.1f} ms {sp.get('attrs', '')}")

    trace = rec["slice"].result() if rec["slice"] is not None else None
    checked_ids = srv.pick_checked(rec, ctx.seed)
    checked = srv.checked_requests(checked_ids)
    srv.free_engine()
    gaps = served_gaps(srv.params, ctx.config, mix, checked)
    n_tok = sum(len(t) for _, t, _ in checked)
    log(f"[serve] reference over {len(checked)} requests, {n_tok} served tokens: "
        f"widest gap per request {[round(g, 5) for g in gaps]}")
    checks = [
        # no request checked reads 0 here and fails tokens_checked_short
        Check("served_token_gap", max(gaps, default=0.0), mix["check"]["served_token_gap"]),
        Check("tokens_checked_short", max(mix["check"]["min_tokens"] - n_tok, 0), 0),
        Check("requests_failed", failed + never, 0),
        # a fallback in the window needs a program set-up did not obtain
        Check("programs_obtained_in_window", rec["compiles_in_window"], 0),
        Check("launch_retries_in_window", rec["retries_in_window"], 0),
    ]
    rec.update(e2e)
    rec["peaks"] = ctx.peaks
    rec["trace"] = trace
    rec["vocab"] = ctx.config["model"]["vocab_size"]
    rec["k"] = mix["sampling"]["k"]
    del rec["slice"]
    metrics = {k: e2e[k] for k in ("ttft_p50_ms", "itl_p95_ms", "output_tok_per_s") if k in e2e}
    return Outcome(attempted=attempted, failed=failed + never, setup_s=setup_s,
                   end_to_end=metrics, record=rec, checks=checks, memory_peak_bytes=mem)
