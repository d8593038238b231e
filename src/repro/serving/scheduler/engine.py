"""Scheduled serving engine: admit → bucketed prefill → slot insert →
continuous-batching decode, bit-identical per request to solo generate().

Disaggregation: prefill batches compile per (bucket length, batch) pair;
the decode step compiles per batch *composition* — the tuple of per-slot
(k, greedy, top-p-active) signatures — and is reused for every tick with
that composition. The three programs are named ``sched_prefill``,
``sched_insert`` and ``sched_decode`` (``jit_sched_decode`` on a
profiler's ``XLA Modules`` line).

One scheduler tick is a row of host phases, each a ``kind="run"`` span
(so it lands on the profiler's host clock beside the device ops), none
overlapping another:

  * ``sched.expire``: time out queued and running requests past their
    deadlines;
  * ``sched.admit``: admit everything arrived (FIFO, head-of-line
    blocking);
  * per prefill batch, ``sched.prefill``: the prefill launch until its
    logits are ready; then per request ``sched.first_token`` (the eager
    first draw from its prefill row) and ``sched.insert`` (slot and page
    allocation, the insert launch);
  * ``sched.decode_prep``: the decode launch's host arrays and keys;
  * ``sched.decode``: one decode launch over all occupied slots, drawing
    every request's next token through a single segmented
    ``segment_topk``, until the tokens are on the host;
  * ``sched.decode_commit``: per-request bookkeeping of the new tokens
    and keys, finished requests released.

Bit-equality oracle (CI-gated, tests/test_scheduler.py): each request's
token stream equals running it alone through one-shot ``generate()`` with
``ServeConfig(cache_len = pages_per_slot * page_size)``. The load-bearing
pieces:
  * prefill logits are padding/batch-invariant (causal masking; the
    gather at ``lengths - 1`` picks each row's own last position);
  * ``decode_attention`` reduces per-row (``jax.lax.map``) so decode
    logits are invariant to how many slots share the batch;
  * candidate *values* from ``segment_topk`` match ``unified_topk``
    bitwise (selection copies inputs; no float arithmetic), and token
    emission canonicalizes ties to the lowest vocab id;
  * per-request PRNG chains split exactly like generate()'s
    (``vmap(split)`` produces the same per-row bits as solo splits);
  * the gathered slot view has the same sequence capacity
    (``pages_per_slot * page_size``) as the solo cache, so XLA lowers
    the same masked reduction.

Time is a virtual tick counter — admission order is a pure function of
the (arrival, rid) trace, never of wall clock. Wall time feeds only the
latency metrics (TTFT / TPOT / request latency) and the opt-in
``deadline_ms`` wall-clock deadline.

Failure hardening (DESIGN.md §16): ticks start by expiring requests past
their ``ttl_ticks``/``deadline_ms`` (slot + pages reclaimed); every
prefill/insert/decode launch runs under bounded retry with exponential
backoff, and exhausted retries turn the launch's requests terminal
FAILED instead of wedging the engine; ``submit`` load-sheds with
:class:`~.queue.QueueFull` once the queue holds ``max_queue`` requests.
The invariant the chaos suite gates: whatever faults fire, ``run()``
drains — every request reaches a terminal state and no slot or page
stays allocated.
"""
from __future__ import annotations

import dataclasses
import math
import os
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import segment_topk
from repro.configs.base import ModelConfig
from repro.models import decode_step, init_cache, prefill
from repro.obs import metrics as obs_metrics
from repro.obs import recorder as obs_recorder
from repro.obs.trace import enabled as obs_enabled
from repro.obs.trace import record_span, span
from repro.resilience.failpoints import failpoint

from ..sample import canonical_token, sample_greedy, sample_topk, scored_draw
from .paged import PagedKVCache, SlotManager, gather_view, scatter_col, split_pages, take_col
from .params import SamplingParams
from .queue import AdmissionQueue, QueueFull
from .request import Request, RequestState


def _next_pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


@dataclasses.dataclass
class SchedulerConfig:
    n_slots: int = 4
    page_size: int = 16
    pages_per_slot: int = 8
    #: pool size; default reserves page 0 as scratch and gives every slot
    #: a full complement
    n_pages: Optional[int] = None
    max_prefill_batch: int = 4
    #: free-slot reuse order ("fifo" | "lifo") — token bits must not
    #: depend on it (determinism tests flip it)
    slot_order: str = "fifo"
    #: admission-queue bound; ``submit`` past it raises
    #: :class:`~.queue.QueueFull` (None = unbounded, the pre-§16 behavior)
    max_queue: Optional[int] = None
    #: retries per prefill/insert/decode launch before the batch's
    #: requests go FAILED (0 = fail on the first error)
    max_retries: int = 2
    #: base of the exponential retry backoff (seconds; attempt n sleeps
    #: ``retry_backoff_s * 2**n``)
    retry_backoff_s: float = 0.05

    def __post_init__(self):
        assert self.page_size >= 1 and (self.page_size & (self.page_size - 1)) == 0, \
            f"page_size must be a power of two, got {self.page_size}"
        assert self.max_prefill_batch >= 1
        assert self.max_queue is None or self.max_queue >= 1
        assert self.max_retries >= 0 and self.retry_backoff_s >= 0.0

    @property
    def slot_capacity(self) -> int:
        return self.pages_per_slot * self.page_size


class ScheduledEngine:
    """Continuous-batching engine over a paged slot pool.

    Usage: ``submit()`` any number of requests (each with its own
    :class:`SamplingParams` and arrival tick), then ``run()`` to drain —
    or drive ``step()`` tick by tick."""

    def __init__(self, params, cfg: ModelConfig, sched: Optional[SchedulerConfig] = None):
        sched = sched or SchedulerConfig()
        assert cfg.family in ("dense", "moe"), (
            f"scheduler serves homogeneous attention stacks, not {cfg.family}")
        self.params = params
        self.cfg = cfg
        self.sc = sched
        n_pages = sched.n_pages or 1 + sched.n_slots * sched.pages_per_slot
        self.pool = PagedKVCache(cfg, n_pages, sched.page_size)
        self.slots = SlotManager(sched.n_slots, sched.pages_per_slot,
                                 n_pages, order=sched.slot_order)
        self.queue = AdmissionQueue(sched.max_queue)
        self.requests: Dict[int, Request] = {}
        self.active: Dict[int, Request] = {}  # slot -> request
        self.t = 0
        self._next_rid = 0
        self._prefill_jits: Dict[tuple, object] = {}
        self._insert_jits: Dict[tuple, object] = {}
        self._decode_jits: Dict[tuple, object] = {}
        #: batch signatures whose decode jit has already been launched —
        #: the first tick per signature pays the compile and is tagged
        #: ``compiled=True`` on its ``req.decode`` spans
        self._decode_seen: set = set()
        self._trace_prefix = f"{os.getpid():x}-{id(self) & 0xFFFF:04x}"

    # ----------------------------------------------------------------- API

    def submit(self, prompt, params: Optional[SamplingParams] = None,
               arrival: int = 0) -> int:
        params = params or SamplingParams()
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        assert prompt.size >= 1, "empty prompt"
        need = prompt.size + params.max_new_tokens
        if need > self.sc.slot_capacity:
            raise ValueError(
                f"prompt+max_new_tokens = {need} exceeds slot capacity "
                f"{self.sc.slot_capacity} (pages_per_slot * page_size)")
        rid = self._next_rid
        self._next_rid += 1
        req = Request(rid=rid, prompt=prompt, params=params,
                      arrival=int(arrival),
                      trace_id=f"{self._trace_prefix}-{rid}")
        req.t_submit_ns = time.perf_counter_ns()
        req.t_submit = req.t_submit_ns * 1e-9
        self.requests[rid] = req
        try:
            self.queue.push(req)
        except QueueFull as e:
            # load-shed: the request is kept (terminal REJECTED, queryable)
            # but never queued; the raised error carries the retry hint
            req.state = RequestState.REJECTED
            req.error = str(e)
            req.finish_tick = self.t
            req.t_finish_ns = time.perf_counter_ns()
            req.t_finish = req.t_finish_ns * 1e-9
            obs_metrics.counter("sched.rejected").inc()
            self._record_request(req)
            raise
        obs_metrics.counter("sched.submitted").inc()
        return rid

    def step(self) -> None:
        """One scheduler tick: expire → admit → prefill/insert → one
        decode step."""
        with span("sched.expire", kind="run", n=len(self.queue)):
            self._expire()
        with span("sched.admit", kind="run"):
            admitted = self._admit()
        if admitted:
            self._run_prefill(admitted)
        if self.active:
            self._run_decode()
        self._gauges()
        self.t += 1

    def run(self, max_steps: int = 1_000_000) -> Dict[int, np.ndarray]:
        """Drain the queue; returns {rid: generated tokens}.

        An *unhandled* exception (anything the retry/fail machinery did
        not absorb) dumps the flight recorder — to ``REPRO_OBS_DUMP`` if
        set, else a bounded event tail to stderr — before propagating,
        so the post-mortem has the breaker/failpoint/span history that
        led up to the crash."""
        steps = 0
        try:
            while (len(self.queue) or self.active) and steps < max_steps:
                if not self.active:
                    nxt = self.queue.next_arrival()
                    if nxt is not None and nxt > self.t:
                        self.t = nxt  # idle fast-forward to next arrival
                self.step()
                steps += 1
        except Exception as e:  # noqa: BLE001 — dump context, re-raise
            obs_recorder.crash_dump("sched.run", e)
            raise
        assert not len(self.queue) and not self.active, \
            f"drain incomplete after {steps} steps"
        return {rid: np.asarray(r.tokens, np.int32)
                for rid, r in self.requests.items()
                if r.state is RequestState.DONE}

    def result(self, rid: int) -> np.ndarray:
        r = self.requests[rid]
        assert r.state is RequestState.DONE, (
            f"request {rid} is {r.state.value}"
            + (f": {r.error}" if r.error else ""))
        return np.asarray(r.tokens, np.int32)

    # ----------------------------------------- deadlines, failures, retries

    def _expire(self) -> None:
        """Time out every request (queued or running) whose ``ttl_ticks``
        / ``deadline_ms`` has elapsed, reclaiming slot and pages."""
        now = time.perf_counter()
        for r in self.queue.drain_expired(lambda q: q.expired(self.t, now)):
            self._timeout(r)
        for r in [r for r in self.active.values() if r.expired(self.t, now)]:
            self._timeout(r)

    def _release(self, r: Request) -> None:
        if r.slot is not None:
            self.slots.release(r.slot)
            self.active.pop(r.slot, None)
            r.slot = None

    def _record_request(self, r: Request) -> None:
        """Close the request's root span at its terminal state. Stage
        spans (``req.queue_wait``/``req.prefill``/``req.insert``/
        ``req.decode``) were recorded as the stages ran; the root span
        carries the whole submit→terminal window plus the trace id, so
        the exporter (``obs.request_waterfalls``) can rebuild the
        per-request timeline and reconcile stage sums against the
        measured latency."""
        if not obs_enabled():
            return
        record_span("request", r.t_submit_ns,
                    (r.t_finish_ns or time.perf_counter_ns()) - r.t_submit_ns,
                    rid=r.rid, trace_id=r.trace_id, state=r.state.value,
                    tokens=len(r.tokens), arrival=r.arrival,
                    finish_tick=r.finish_tick)
        obs_recorder.emit("sched", f"request.{r.state.value}", rid=r.rid,
                          trace_id=r.trace_id, tokens=len(r.tokens))

    def _mark_finish(self, r: Request) -> None:
        r.finish_tick = self.t
        r.t_finish_ns = time.perf_counter_ns()
        r.t_finish = r.t_finish_ns * 1e-9

    def _timeout(self, r: Request) -> None:
        r.state = RequestState.TIMED_OUT
        r.error = f"deadline elapsed at tick {self.t}"
        self._mark_finish(r)
        self._release(r)
        obs_metrics.counter("sched.timed_out").inc()
        self._record_request(r)

    def _fail(self, r: Request, err: str) -> None:
        r.state = RequestState.FAILED
        r.error = err
        self._mark_finish(r)
        self._release(r)
        obs_metrics.counter("sched.failed").inc()
        self._record_request(r)

    def _with_retry(self, what: str, fn):
        """Run one launch closure with bounded retry + exponential
        backoff. The ``sched.{what}`` failpoint fires *before* the
        closure, so an injected fault never lands after a donated buffer
        was consumed — retries always see valid inputs."""
        attempt = 0
        while True:
            try:
                failpoint(f"sched.{what}")
                return fn()
            except Exception:
                if attempt >= self.sc.max_retries:
                    raise
                obs_metrics.counter("sched.retries").inc(what=what)
                if self.sc.retry_backoff_s:
                    time.sleep(self.sc.retry_backoff_s * (2 ** attempt))
                attempt += 1

    # ----------------------------------------------------------- admission

    def _npg_need(self, req: Request) -> int:
        return math.ceil(
            (req.prompt.size + req.params.max_new_tokens) / self.sc.page_size)

    def _admit(self) -> List[Request]:
        admitted = []
        free_slots = self.slots.free_slot_count
        free_pages = self.slots.free_page_count
        while len(self.queue):
            req = self.queue.peek()
            npg = self._npg_need(req)
            if req.arrival > self.t:
                break
            if free_slots < 1 or free_pages < npg:
                break  # head-of-line blocking keeps admission deterministic
            free_slots -= 1
            free_pages -= npg
            self.queue.pop()
            req.admit_tick = self.t
            admitted.append(req)
        if admitted:
            obs_metrics.counter("sched.admitted").inc(len(admitted))
        return admitted

    # ------------------------------------------------------------- prefill

    def _bucket(self, plen: int) -> int:
        return max(self.sc.page_size, _next_pow2(plen))

    def _prefill_fn(self, blen: int, bb: int):
        key = (blen, bb)
        if key not in self._prefill_jits:
            cfg = self.cfg

            def sched_prefill(params, tokens, lengths):
                cache = init_cache(cfg, bb, blen)
                logits, cache = prefill(params, {"tokens": tokens}, cache,
                                        cfg, lengths=lengths)
                return logits, cache["body"]

            self._prefill_jits[key] = jax.jit(sched_prefill)
        return self._prefill_jits[key]

    def _insert_fn(self, npg: int, blen: int, bb: int):
        key = (npg, blen, bb)
        if key not in self._insert_jits:
            ps = self.sc.page_size

            def sched_insert(leaves, body, row, page_ids):
                out = {}
                for name, pool_leaf in leaves.items():
                    val = split_pages(body[name], name, row, npg, ps)
                    out[name] = pool_leaf.at[:, page_ids].set(val)
                return out

            self._insert_jits[key] = jax.jit(sched_insert, donate_argnums=(0,))
        return self._insert_jits[key]

    def _first_token(self, logits_row, p: SamplingParams):
        """Sample the first token from the prefill logits, mirroring the
        head of generate(): greedy never touches the key; otherwise one
        split and a (1, V) eager sample_topk — exactly the solo shapes."""
        key = jax.random.PRNGKey(p.seed)
        if p.temperature <= 0.0:
            return int(sample_greedy(logits_row[None])[0]), key
        key, sub = jax.random.split(key)
        tok = sample_topk(sub, logits_row[None], k=p.k,
                          temperature=p.temperature, top_p=p.top_p)
        return int(tok[0]), key

    def _run_prefill(self, admitted: List[Request]) -> None:
        groups: Dict[int, List[Request]] = {}
        for r in admitted:
            groups.setdefault(self._bucket(r.prompt.size), []).append(r)
        for blen in sorted(groups):
            reqs = groups[blen]
            for i0 in range(0, len(reqs), self.sc.max_prefill_batch):
                self._prefill_batch(blen, reqs[i0:i0 + self.sc.max_prefill_batch])

    def _prefill_batch(self, blen: int, reqs: List[Request]) -> None:
        bb = len(reqs)
        toks = np.zeros((bb, blen), np.int32)
        lens = np.zeros((bb,), np.int32)
        for i, r in enumerate(reqs):
            toks[i, :r.prompt.size] = r.prompt
            lens[i] = r.prompt.size
        def launch_prefill():
            logits, body = self._prefill_fn(blen, bb)(
                self.params, jnp.asarray(toks), jnp.asarray(lens))
            jax.block_until_ready(logits)
            return logits, body

        traced = obs_enabled()
        t_pf0 = time.perf_counter_ns()
        with span("sched.prefill", kind="run", batch=bb, bucket=blen):
            try:
                logits, body = self._with_retry("prefill", launch_prefill)
            except Exception as e:  # noqa: BLE001 — retries exhausted
                for r in reqs:  # no slots were allocated yet: nothing leaks
                    self._fail(r, f"prefill failed: {type(e).__name__}: {e}")
                return
        t_pf1 = time.perf_counter_ns()
        if traced:
            # per-request stage spans share integer-ns endpoints so the
            # waterfall reconciles *exactly*: queue_wait ends where prefill
            # starts; the insert spans below chain from t_pf1 so
            # queue_wait + prefill + insert == TTFT per request
            for r in reqs:
                record_span("req.queue_wait", r.t_submit_ns,
                            t_pf0 - r.t_submit_ns, rid=r.rid,
                            trace_id=r.trace_id, arrival=r.arrival,
                            admit_tick=r.admit_tick)
                record_span("req.prefill", t_pf0, t_pf1 - t_pf0, rid=r.rid,
                            trace_id=r.trace_id, bucket=blen, batch=bb)
        obs_metrics.counter("sched.prefill_batches").inc()
        ps = self.sc.page_size
        for i, r in enumerate(reqs):
            with span("sched.first_token", kind="run", rid=r.rid, k=r.params.k):
                tok, key = self._first_token(logits[i], r.params)
            with span("sched.insert", kind="run", rid=r.rid):
                slot, pages = self.slots.alloc(self._npg_need(r))
                npg_store = math.ceil(r.prompt.size / ps)
                insert = self._insert_fn(npg_store, blen, bb)
                try:
                    self.pool.leaves = self._with_retry(
                        "insert", lambda: insert(
                            self.pool.leaves, body, jnp.int32(i),
                            jnp.asarray(pages[:npg_store])))
                except Exception as e:  # noqa: BLE001 — retries exhausted
                    self.slots.release(slot)  # not yet r.slot: reclaim directly
                    self._fail(r, f"insert failed: {type(e).__name__}: {e}")
                    continue
            r.state = RequestState.RUNNING
            r.slot = slot
            r.length = int(r.prompt.size)
            r.key = key
            r.tokens = [tok]
            r.t_first_ns = time.perf_counter_ns()
            r.t_first = r.t_first_ns * 1e-9
            if traced:
                # spans [t_pf1, t_first] per request: sampling + this (and
                # any earlier sibling's) insert — so each request's own
                # queue_wait/prefill/insert tile [t_submit, t_first]
                # exactly and their ns sum *is* its TTFT
                record_span("req.insert", t_pf1, r.t_first_ns - t_pf1,
                            rid=r.rid, trace_id=r.trace_id, slot=slot,
                            pages=npg_store)
            obs_metrics.histogram("sched.ttft_s").observe(r.t_first - r.t_submit)
            self.active[slot] = r
            if r.params.max_new_tokens == 1:
                self._finish(r)

    # -------------------------------------------------------------- decode

    def _decode_fn(self, sig: tuple):
        if sig in self._decode_jits:
            return self._decode_jits[sig]
        cfg, ps = self.cfg, self.sc.page_size
        v = cfg.vocab_size
        ns = len(sig)
        ks = tuple(s[0] for s in sig)
        offsets = tuple(range(0, (ns + 1) * v, v))

        # the named scopes only label ops (op_name metadata), so a trace's
        # device ops can be traced back to these five parts of the tick
        def sched_decode(params, leaves, page_table, lengths, tokens, keys,
                         temps, tps):
            with jax.named_scope("gather_view"):
                view = gather_view(leaves, page_table, lengths, ps)
            with jax.named_scope("decode_step"):
                logits, new_cache = decode_step(
                    params, tokens, {"body": view}, cfg,
                    positions=lengths[:, None])
            body = new_cache["body"]
            with jax.named_scope("cache_write"):
                page_ids = jnp.take_along_axis(
                    page_table, (lengths // ps)[:, None], axis=1)[:, 0]
                offs = lengths % ps
                out = {
                    name: scatter_col(leaves[name], name,
                                      take_col(body[name], name, lengths),
                                      page_ids, offs)
                    for name in leaves
                }
            # the key split stays ahead of the top-k, keeping the op order
            with jax.named_scope("draw"):
                split = jax.vmap(jax.random.split)(keys)  # (ns, 2, 2)
                new_keys, subs = split[:, 0], split[:, 1]
            with jax.named_scope("segment_topk"):
                # one segmented launch scores every slot's vocab row with
                # its own k; the CSR layout is static (out_offs is a host
                # tuple)
                vals, _, out_offs = segment_topk(logits.reshape(-1), offsets, ks)
            with jax.named_scope("draw"):
                toks = []
                for s, (k_s, greedy, topp) in enumerate(sig):
                    row = logits[s]
                    if greedy:
                        toks.append(jnp.argmax(row, axis=-1).astype(jnp.int32))
                        continue
                    vals_s = vals[out_offs[s]:out_offs[s + 1]][None]  # (1, k_s)
                    choice = scored_draw(subs[s], vals_s, temps[s],
                                         tps[s] if topp else None)
                    toks.append(canonical_token(row[None], vals_s, choice)[0])
                toks = jnp.stack(toks)
            return out, new_keys, toks

        self._decode_jits[sig] = jax.jit(sched_decode, donate_argnums=(1,))
        return self._decode_jits[sig]

    def _run_decode(self) -> None:
        with span("sched.decode_prep", kind="run", batch=len(self.active)):
            slots = sorted(self.active)
            reqs = [self.active[s] for s in slots]
            sig = tuple(r.params.sig for r in reqs)
            pt = jnp.asarray(self.slots.page_table[slots])
            lengths = jnp.asarray(np.asarray([r.length for r in reqs], np.int32))
            tokens = jnp.asarray(
                np.asarray([[r.tokens[-1]] for r in reqs], np.int32))
            keys = jnp.stack([r.key for r in reqs])
            temps = jnp.asarray(
                np.asarray([r.params.temperature for r in reqs], np.float32))
            tps = jnp.asarray(
                np.asarray([r.params.top_p for r in reqs], np.float32))
            compiled = sig not in self._decode_seen
        t_d0 = time.perf_counter_ns()
        with span("sched.decode", kind="run", batch=len(slots)):
            try:
                leaves, new_keys, toks = self._with_retry(
                    "decode", lambda: self._decode_fn(sig)(
                        self.params, self.pool.leaves, pt, lengths, tokens,
                        keys, temps, tps))
            except Exception as e:  # noqa: BLE001 — retries exhausted
                for r in reqs:
                    self._fail(r, f"decode failed: {type(e).__name__}: {e}")
                return
            toks = np.asarray(toks)
        t_d1 = time.perf_counter_ns()
        with span("sched.decode_commit", kind="run", batch=len(slots)):
            self._decode_seen.add(sig)
            if obs_enabled():
                # one tick span per request sharing the launch window; the
                # first tick per batch signature pays the jit compile and is
                # tagged so percentile readers can exclude it (DESIGN.md §17)
                for i, r in enumerate(reqs):
                    record_span("req.decode", t_d0, t_d1 - t_d0, rid=r.rid,
                                trace_id=r.trace_id, tick=self.t, slot=slots[i],
                                batch=len(slots), compiled=compiled)
            self.pool.leaves = leaves
            obs_metrics.counter("sched.decode_steps").inc()
            obs_metrics.counter("sched.tokens").inc(len(slots))
            for i, r in enumerate(reqs):
                r.key = new_keys[i]
                r.length += 1
                r.tokens.append(int(toks[i]))
                if len(r.tokens) >= r.params.max_new_tokens:
                    self._finish(r)

    # ------------------------------------------------------------- cleanup

    def _finish(self, r: Request) -> None:
        r.state = RequestState.DONE
        self._mark_finish(r)
        self.slots.release(r.slot)
        self.active.pop(r.slot, None)
        r.slot = None
        self._record_request(r)
        obs_metrics.counter("sched.completed").inc()
        obs_metrics.histogram("sched.request_latency_s").observe(
            r.t_finish - r.t_submit)
        if len(r.tokens) > 1 and r.t_first:
            obs_metrics.histogram("sched.tpot_s").observe(
                (r.t_finish - r.t_first) / (len(r.tokens) - 1))

    def _gauges(self) -> None:
        obs_metrics.gauge("sched.queue_depth").set(len(self.queue))
        obs_metrics.gauge("sched.slots_occupied").set(len(self.active))
        obs_metrics.gauge("sched.free_pages").set(self.slots.free_page_count)
