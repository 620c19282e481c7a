"""The serving engine façade: ``submit`` / ``step`` / ``collect``.

One ``Engine`` owns the device state (params stay caller-owned; paged KV
pools and per-slot SSM state live here) and the host bookkeeping
(scheduler, page allocator, per-request output buffers, statuses, latency
metrics).  Each ``step()`` is one continuous-batching iteration:

1. **expire/faults** — deadline-expired requests time out, the fault plan's
   scheduled faults (forced preemption, allocator exhaustion, clock skew)
   fire;
2. **admit** — waiting requests move into free slots (FIFO, page
   reservation per the admission mode), each running a jitted batch-1
   **prefill** at a power-of-two shape bucket (per-row ``logit_index``
   reads the true last token, so padding never changes results) which also
   samples the request's first token;
3. **grow/preempt** — under optimistic admission, each running slot's page
   coverage is extended to the coming segment's writes; when the pool runs
   dry the youngest-admitted request is preempted (pages released, request
   requeued at the head with its generated prefix folded into the prompt —
   counter-based sampling keyed on (seed, uid, position) makes the resume
   bit-identical);
4. **decode** — all running slots advance together through one jitted
   ``lax.while_loop`` segment of up to ``segment_len`` tokens, sampling via
   the counter-based sampler (`serve.sampling`); the loop exits early when
   a request finishes so its slot can be refilled next step;
5. **retire** — finished requests release pages + slot and their outputs
   become collectable.

Failures are *per-request*, never engine-wide: a NaN/Inf logits row (the
always-on finite-logits guard) quarantines exactly that request as
``FAILED`` while the batch keeps decoding; a request whose reservation can
never fit the pool fails instead of raising; deadlines and ``cancel(uid)``
retire requests as ``TIMED_OUT``/``CANCELLED``.  ``Engine.metrics[uid]
["status"]`` carries the :class:`~repro.serve.scheduler.RequestStatus`.

Decode runs every slot unconditionally — empty/retired slots write into
the trash page (see `serve.kvcache`) and their sampled tokens are
discarded, so the jitted segment never recompiles as the batch churns.
Cache buffers are donated to the segment on accelerator backends.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import time
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

import repro.obs as obs
from repro.configs.base import ModelConfig
from repro.kernels import ops
from repro.models import lm
from repro.obs.recorder import FlightRecorder
from repro.serve.faults import NO_FAULTS, POISON_OFF, FaultPlan
from repro.serve.kvcache import PagedKvCache, pages_needed
from repro.serve.sampling import sample_tokens
from repro.serve.scheduler import Request, RequestStatus, Scheduler

__all__ = ["EngineConfig", "Engine", "EngineDrainError"]


class EngineDrainError(RuntimeError):
    """``Engine.run`` hit ``max_steps`` before draining.  ``results`` holds
    ``{uid: tokens}`` for every request that *did* reach a terminal status,
    so the finished work is not lost with the exception."""

    def __init__(self, message: str, results: dict[int, list[int]]):
        super().__init__(message)
        self.results = results


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    num_slots: int = 8
    page_size: int = 16
    max_seq: int = 2048            # per-request prompt + generation cap
    num_pages: Optional[int] = None  # default: worst case, every slot full
    segment_len: int = 8           # decode tokens per jitted while_loop
    min_bucket: int = 8            # smallest prefill shape bucket
    stop_on_finish: bool = True    # early-exit segments to refill slots
    eos_token: Optional[int] = None
    seed: int = 0
    ep_axis: Optional[str] = None
    unroll_layers: bool = False
    admission: str = "reserve"     # "reserve" | "optimistic" page grants
    thrash_preemptions: int = 4    # optimistic→reserve fallback watermark:
    thrash_window: int = 8         #   ≥ N preemptions in the last W steps

    def __post_init__(self):
        if self.admission not in ("reserve", "optimistic"):
            raise ValueError(f"unknown admission mode {self.admission!r} "
                             "(want 'reserve' or 'optimistic')")

    @property
    def max_pages_per_slot(self) -> int:
        return max(1, math.ceil(self.max_seq / self.page_size))

    @property
    def slot_capacity(self) -> int:
        return self.max_pages_per_slot * self.page_size


class DecodeState(NamedTuple):
    """Per-slot device state threaded through the decode while_loop."""
    tok: jax.Array      # (B,) i32  last sampled token (next model input)
    pos: jax.Array      # (B,) i32  cache position that token occupies
    gen: jax.Array      # (B,) i32  tokens generated so far
    limit: jax.Array    # (B,) i32  max_new per request
    active: jax.Array   # (B,) bool
    bad: jax.Array      # (B,) bool non-finite logits seen (quarantine flag)
    uids: jax.Array     # (B,) u32  sampler counter key
    temp: jax.Array     # (B,) f32
    top_k: jax.Array    # (B,) i32
    top_p: jax.Array    # (B,) f32


def _is_mamba_leaf(path) -> bool:
    return any(isinstance(k, jax.tree_util.DictKey) and k.key == "mamba"
               for k in path)


def _fresh_slot_state(caches):
    """Mamba leaves sliced to a zeroed batch-1 row (a new request starts
    from zero SSM state); pool leaves pass through shared."""
    def f(path, a):
        if _is_mamba_leaf(path):
            return jnp.zeros(a.shape[:1] + (1,) + a.shape[2:], a.dtype)
        return a
    return jax.tree_util.tree_map_with_path(f, caches)


def _merge_slot_state(caches, new, slot):
    """Write batch-1 mamba rows back into ``slot``; take updated pools."""
    def f(path, old, upd):
        if _is_mamba_leaf(path):
            return jax.lax.dynamic_update_slice_in_dim(old, upd, slot, axis=1)
        return upd
    return jax.tree_util.tree_map_with_path(f, caches, new)


def _next_bucket(n: int, lo: int, cap: int) -> int:
    b = lo
    while b < n:
        b *= 2
    return min(b, cap)


class Engine:
    def __init__(self, cfg: ModelConfig, params, ecfg: EngineConfig, *,
                 faults: Optional[FaultPlan] = None, clock=None,
                 registry=None, tracer=None,
                 flight: Optional[FlightRecorder] = None,
                 flight_capacity: int = 256):
        if cfg.is_encdec:
            raise NotImplementedError(
                "the serving engine does not support encoder-decoder models")
        self.cfg, self.params, self.ecfg = cfg, params, ecfg
        num_pages = (ecfg.num_pages if ecfg.num_pages is not None
                     else ecfg.num_slots * ecfg.max_pages_per_slot)
        self.kv = PagedKvCache(ecfg.num_slots, num_pages, ecfg.page_size,
                               ecfg.max_pages_per_slot)
        self.sched = Scheduler(ecfg.num_slots, self.kv, mode=ecfg.admission)
        self.caches = lm.init_paged_cache(cfg, ecfg.num_slots, num_pages,
                                          ecfg.page_size)
        self._seed = jnp.uint32(ecfg.seed)
        self._faults = faults if faults is not None else NO_FAULTS
        self._poison_uid = jnp.uint32(self._faults.poison_uid)
        self._poison_pos = jnp.int32(self._faults.poison_pos)
        self._clock = clock if clock is not None else time.perf_counter
        self._skew = 0.0          # virtual seconds added by fault delays
        self._step_idx = 0

        b = ecfg.num_slots
        # decode state lives on device between segments; the host keeps only
        # the bookkeeping it needs to harvest tokens and retire slots
        self._state = DecodeState(
            tok=jnp.zeros(b, jnp.int32), pos=jnp.zeros(b, jnp.int32),
            gen=jnp.zeros(b, jnp.int32), limit=jnp.ones(b, jnp.int32),
            active=jnp.zeros(b, bool), bad=jnp.zeros(b, bool),
            uids=jnp.zeros(b, jnp.uint32),
            temp=jnp.zeros(b, jnp.float32), top_k=jnp.zeros(b, jnp.int32),
            top_p=jnp.ones(b, jnp.float32))
        self._gen = np.zeros(b, np.int32)
        self._done = np.zeros(b, bool)
        self._uids = np.zeros(b, np.uint32)
        self._prior = np.zeros(b, np.int64)  # tokens of uid before admission
        self._table_dev = jnp.asarray(self.kv.table())
        self._table_dirty = False

        self._out: dict[int, list[int]] = {}     # uid → generated tokens
        self._prompts: dict[int, list[int]] = {}  # uid → ORIGINAL prompt
        self._max_new: dict[int, int] = {}        # uid → original budget
        self._terminal: set[int] = set()
        self.metrics: dict[int, dict] = {}       # uid → latency + status
        self._preempt_log: list[int] = []        # step idx of preemptions
        self._fallback_step: Optional[int] = None
        self._next_uid = 0

        # -- observability (docs/observability.md) ---------------------------
        # Each engine owns its registry so two engines in one process never
        # mix counts; the tracer defaults to the process-wide one so engine
        # spans interleave with fusion/tune spans on a single timeline.  The
        # flight recorder is NOT gated by REPRO_OBS — it is the black box.
        if registry is not None:
            self.registry = registry
        else:
            self.registry = (obs.metrics.Registry() if obs.enabled()
                             else obs.metrics.NULL_REGISTRY)
        self.tracer = tracer if tracer is not None else obs.get_tracer()
        self.flight = flight if flight is not None \
            else FlightRecorder(flight_capacity)
        reg = self.registry
        self._c_tokens = reg.counter("serve.tokens")
        self._c_preempt = reg.counter("serve.preemptions")
        self._c_grows = reg.counter("serve.page_grows")
        self._c_dumps = reg.counter("serve.flight_dumps")
        self._c_iters = reg.counter("serve.decode_iterations")
        self._c_slot_iters = reg.counter("serve.decode_slot_iterations")
        self._c_kv_pages = reg.counter("serve.decode_kv_pages")
        self._c_moe = {
            "moe_assignments": reg.counter("serve.moe_assignments"),
            "moe_experts_touched": reg.counter("serve.moe_experts_touched")}
        self._c_submitted = reg.counter("serve.requests.submitted")
        self._term_counters = {
            RequestStatus.FINISHED: reg.counter("serve.requests.finished"),
            RequestStatus.FAILED: reg.counter("serve.requests.failed"),
            RequestStatus.CANCELLED: reg.counter("serve.requests.cancelled"),
            RequestStatus.TIMED_OUT: reg.counter("serve.requests.timed_out"),
        }
        self._g_queue = reg.gauge("serve.queue_depth")
        self._g_slots = reg.gauge("serve.slots.active")
        self._g_pages_used = reg.gauge("serve.pages.used")
        self._g_pages_total = reg.gauge("serve.pages.total")
        self._g_pages_total.set(num_pages)
        self._h_step = reg.histogram("serve.step_s")
        self._step_events: list[tuple[str, dict]] = []
        self._tokens_harvested = 0
        # what the decode segments' while_loops report: iterations run,
        # active slots summed over them, the KV pages (latent pages for
        # MLA) those slots' attention reads, and for an MoE model the
        # active slots' expert assignments on held experts and the held
        # experts they touch, summed over layers and iterations (plain
        # ints, like tokens_generated)
        self.decode_iterations = 0
        self.decode_slot_iterations = 0
        self.decode_kv_pages = 0
        self.moe_assignments = 0
        self.moe_experts_touched = 0

        self._prefill, self._segment = _jitted_fns(cfg, ecfg,
                                                   ops.current_backend())

    # -- public API ---------------------------------------------------------

    def submit(self, prompt, max_new: int, *, temperature: float = 0.0,
               top_k: int = 0, top_p: float = 1.0,
               uid: Optional[int] = None,
               ttft_deadline: Optional[float] = None,
               deadline: Optional[float] = None) -> int:
        """Queue one request; returns its uid (the sampler counter key).

        ``ttft_deadline``/``deadline`` are seconds after submission by which
        the first token / the whole request must land; a request past its
        deadline is retired as ``TIMED_OUT`` at the next step boundary.
        Nothing is registered until every argument validates — a rejected
        submit leaves the engine untouched."""
        prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
        uid = self._next_uid if uid is None else uid
        if uid in self.metrics:
            raise ValueError(
                f"duplicate uid {uid}: already "
                f"{self.metrics[uid]['status'].value}; uids key the "
                "sampler's counter stream and must be unique per engine")
        if not 0 <= uid < POISON_OFF:
            raise ValueError(f"uid {uid} out of range [0, {POISON_OFF})")
        req = Request(uid=uid, prompt=prompt, max_new=max_new,
                      temperature=temperature, top_k=top_k, top_p=top_p)
        if req.max_tokens > self.ecfg.max_seq:
            raise ValueError(
                f"request {uid}: prompt ({len(prompt)}) + max_new "
                f"({max_new}) = {req.max_tokens} exceeds max_seq "
                f"({self.ecfg.max_seq})")
        self.sched.submit(req)
        # -- validated: now (and only now) register the request -------------
        self._next_uid = max(self._next_uid, uid + 1)
        self._prompts[uid] = prompt
        self._max_new[uid] = max_new
        self._out[uid] = []
        self.metrics[uid] = {"submitted": self._now(),
                             "first_token": None, "finished": None,
                             "token_times": [],
                             "status": RequestStatus.WAITING,
                             "preemptions": 0,
                             "ttft_deadline": ttft_deadline,
                             "deadline": deadline}
        self._c_submitted.inc()
        self._g_queue.set(self.sched.num_waiting)
        return uid

    @property
    def stats(self) -> dict:
        """Aggregate counts — a read-through view over the engine's metrics
        registry (plain JSON-able dict, same keys as the pre-registry ad-hoc
        dict plus live ``waiting``/``in_flight``).  ``waiting`` counts the
        scheduler's queue *including PREEMPTED requeues* and ``in_flight``
        counts only slots actually running — a preempted request is back in
        line, not in flight (the old ad-hoc bookkeeping lumped it with the
        running set until re-admission).  With observability disabled
        (``REPRO_OBS=0``) the counter-backed keys read 0."""
        return {
            "preemptions": int(self._c_preempt.value),
            "page_grows": int(self._c_grows.value),
            "timeouts": int(self._term_counters[
                RequestStatus.TIMED_OUT].value),
            "failures": int(self._term_counters[RequestStatus.FAILED].value),
            "cancellations": int(self._term_counters[
                RequestStatus.CANCELLED].value),
            "fallback_to_reserve_step": self._fallback_step,
            "waiting": self.sched.num_waiting,
            "in_flight": len(self.sched.running),
        }

    @property
    def idle(self) -> bool:
        return self.sched.idle

    @property
    def tokens_generated(self) -> int:
        """Total tokens harvested across all requests so far.  Backed by a
        plain int (not the registry counter) so it reads correctly even with
        observability disabled."""
        return self._tokens_harvested

    def status(self, uid: int) -> RequestStatus:
        return self.metrics[uid]["status"]

    def cancel(self, uid: int) -> bool:
        """Abort a request from the host.  Returns True if it was alive
        (waiting or running) and is now ``CANCELLED``; False if it had
        already reached a terminal status."""
        if uid not in self.metrics:
            raise KeyError(f"unknown uid {uid}")
        if uid in self._terminal:
            return False
        if self.sched.remove_waiting(uid) is None:
            slot = next(s for s, r in self.sched.running.items()
                        if r.uid == uid)
            self._evict(slot)
        self._set_terminal(uid, RequestStatus.CANCELLED)
        return True

    def step(self) -> list[int]:
        """One continuous-batching iteration.  Returns the uids that
        reached a terminal status during this step.

        Each step opens an ``engine.step`` span around its phases' spans
        (``engine.admit`` ⊃ ``engine.prefill`` ⊃ ``engine.wait``,
        ``engine.retire``, ``engine.grow``, ``engine.decode_segment`` ⊃
        ``engine.wait``, ``engine.harvest``), updates the queue/pool
        gauges, and appends one record (this step's scheduler decisions) to
        the flight recorder."""
        idx = self._step_idx
        t0 = self._clock()
        self._step_events = []
        with self.tracer.span("engine.step", step=idx) as sp:
            newly = self._step_inner()
            sp.set(terminal=len(newly))
        self._h_step.observe(self._clock() - t0)
        self._g_queue.set(self.sched.num_waiting)
        self._g_slots.set(len(self.sched.running))
        self._g_pages_used.set(self.kv.num_pages - self.kv.free_pages)
        self.flight.record(
            step=idx, events=self._step_events, terminal=list(newly),
            queue_depth=self.sched.num_waiting,
            running=len(self.sched.running),
            free_pages=self.kv.free_pages,
            tokens_total=self._tokens_harvested)
        return newly

    def _step_inner(self) -> list[int]:
        plan, idx = self._faults, self._step_idx
        self._step_idx += 1
        self._skew += plan.clock_skew(idx)
        newly = self._expire_deadlines()
        if plan.force_preempt(idx) and self.sched.running:
            self.tracer.event("engine.fault", kind="force_preempt", step=idx)
            self._preempt(self.sched.youngest_running())
        if self.sched.idle:
            return newly
        blocked = plan.allocator_exhausted(idx)
        if blocked:
            self.tracer.event("engine.fault", kind="allocator_exhausted",
                              step=idx)
            self._step_events.append(("fault_exhausted", {}))
        if not blocked:
            with self.tracer.span("engine.admit",
                                  waiting=self.sched.num_waiting) as sp:
                newly += self._fail_impossible_heads()
                admitted = self.sched.admit()
                for slot, req in admitted:
                    failed_uid = self._admit(slot, req)
                    if failed_uid is not None:
                        newly.append(failed_uid)
                sp.set(admitted=len(admitted))
        newly += self._retire_done()
        self._ensure_segment_pages(grow_allowed=not blocked)
        if any(not self._done[s] for s in self.sched.running):
            bad = self._run_segment()
            newly += self._quarantine(bad)
            newly += self._retire_done()
        self._maybe_fallback_reserve()
        return newly

    def collect(self, uid: int) -> list[int]:
        """Full token list (original prompt + generated) of a request that
        reached a terminal status (check ``status(uid)`` for which one —
        FAILED/TIMED_OUT/CANCELLED requests return their partial output)."""
        if uid not in self._terminal:
            raise KeyError(f"request {uid} is not finished")
        return self._prompts[uid] + self._out[uid]

    def run(self, max_steps: int = 100_000) -> dict[int, list[int]]:
        """Drive ``step`` until idle; returns {uid: tokens} for every
        request in a terminal status — including ones that finished in
        earlier ``step``/``run`` calls.  On non-drain raises
        :class:`EngineDrainError` with the partial results attached."""
        for _ in range(max_steps):
            if self.idle:
                break
            self.step()
        results = {uid: self.collect(uid) for uid in sorted(self._terminal)}
        if not self.idle:
            err = EngineDrainError(
                f"engine did not drain within {max_steps} steps "
                f"({self.sched.num_waiting} waiting, "
                f"{len(self.sched.running)} running); partial results for "
                f"{len(results)} finished requests attached", results)
            err.flight = self._flight_dump(
                "engine_drain", max_steps=max_steps,
                waiting=self.sched.num_waiting,
                running=len(self.sched.running))
            raise err
        return results

    def validate(self) -> None:
        """Invariant checker (chaos tests run it after every step):
        allocator freelist + page tables + scheduler slots + DecodeState +
        host mirrors all agree.  A failure dumps the flight recorder before
        re-raising — the broken invariant plus the steps that led to it."""
        try:
            self._validate_inner()
        except AssertionError as exc:
            self._flight_dump("validate_failure", error=str(exc))
            raise

    def _validate_inner(self) -> None:
        self.sched.check_invariants()
        st = jax.device_get(self._state)
        running = set(self.sched.running)
        for slot in range(self.ecfg.num_slots):
            if slot not in running:
                assert not st.active[slot], \
                    f"slot {slot} active on device but not running"
                assert not self._done[slot], \
                    f"slot {slot} marked done but not running"
        waiting_uids = [r.uid for r in self.sched.waiting]
        assert len(waiting_uids) == len(set(waiting_uids)), \
            "uid queued twice"
        for slot, req in self.sched.running.items():
            uid = req.uid
            assert int(self._uids[slot]) == uid, "host uid mirror stale"
            assert int(st.uids[slot]) == uid, "device uid stale"
            assert uid not in waiting_uids, "uid both running and waiting"
            gen = int(self._gen[slot])
            assert int(st.gen[slot]) == gen, \
                f"slot {slot}: device gen {int(st.gen[slot])} != host {gen}"
            assert len(self._out[uid]) == self._prior[slot] + gen, \
                f"uid {uid}: harvested tokens disagree with gen counter"
            # every KV position written so far sits in an owned page (the
            # last sampled token is not written until the next decode step)
            written = len(req.prompt) + gen - 1
            assert self.kv.capacity(slot) >= written, \
                f"slot {slot}: {written} tokens written but pages cover " \
                f"only {self.kv.capacity(slot)}"
            assert not self.metrics[uid]["status"].terminal, \
                f"uid {uid} running with terminal status"
        for uid, m in self.metrics.items():
            terminal = m["status"].terminal
            assert terminal == (uid in self._terminal), \
                f"uid {uid}: status {m['status']} vs terminal-set mismatch"
            if terminal:
                assert uid not in waiting_uids, \
                    f"terminal uid {uid} still queued"

    # -- internals ----------------------------------------------------------

    def _now(self) -> float:
        return self._clock() + self._skew

    def _flight_dump(self, reason: str, **context) -> dict:
        # flush the in-progress step's decisions first: faults fire mid-step,
        # and the partial record is exactly what the postmortem needs (the
        # completed record for this step still lands when step() returns)
        if self._step_events:
            self.flight.record(
                step=self._step_idx - 1, partial=True,
                events=list(self._step_events),
                queue_depth=self.sched.num_waiting,
                running=len(self.sched.running),
                free_pages=self.kv.free_pages,
                tokens_total=self._tokens_harvested)
        self._c_dumps.inc()
        return self.flight.dump_on_fault(reason, **context)

    def _set_terminal(self, uid: int, status: RequestStatus) -> None:
        m = self.metrics[uid]
        m["status"] = status
        m["finished"] = self._now()
        self._terminal.add(uid)
        counter = self._term_counters.get(status)
        if counter is not None:
            counter.inc()

    def _deactivate_slot(self, slot: int) -> None:
        self._state = self._state._replace(
            active=self._state.active.at[slot].set(False))

    def _evict(self, slot: int) -> Request:
        """Release a slot whose request is leaving mid-flight (cancel,
        timeout, quarantine): free pages, silence the device lane."""
        req = self.sched.retire(slot)
        self._done[slot] = False
        self._deactivate_slot(slot)
        self._table_dirty = True
        return req

    def _preempt(self, slot: int) -> None:
        """Evict under memory pressure and requeue at the head of the line
        with the generated prefix folded into the prompt — the counter
        sampler (keyed on uid + absolute position) makes the resumed
        request's remaining tokens bit-identical to the uninterrupted
        run's."""
        req = self.sched.preempt(slot)
        self._done[slot] = False
        self._deactivate_slot(slot)
        self._table_dirty = True
        uid = req.uid
        resumed = Request(
            uid=uid, prompt=self._prompts[uid] + self._out[uid],
            max_new=self._max_new[uid] - len(self._out[uid]),
            temperature=req.temperature, top_k=req.top_k, top_p=req.top_p)
        self.sched.requeue_front(resumed)
        m = self.metrics[uid]
        m["status"] = RequestStatus.PREEMPTED
        m["preemptions"] += 1
        self._c_preempt.inc()
        self._preempt_log.append(self._step_idx)
        self.tracer.event("engine.preempt", uid=uid, slot=slot)
        self._step_events.append(("preempt", {"uid": uid, "slot": slot}))

    def _expire_deadlines(self) -> list[int]:
        now = self._now()
        expired = []
        for req in list(self.sched.waiting):
            m = self.metrics[req.uid]
            waited = now - m["submitted"]
            ttft, total = m["ttft_deadline"], m["deadline"]
            if ((ttft is not None and m["first_token"] is None
                 and waited > ttft)
                    or (total is not None and waited > total)):
                self.sched.remove_waiting(req.uid)
                self._set_terminal(req.uid, RequestStatus.TIMED_OUT)
                self._step_events.append(("timeout", {"uid": req.uid}))
                expired.append(req.uid)
        for slot, req in list(self.sched.running.items()):
            m = self.metrics[req.uid]
            total = m["deadline"]
            if total is not None and now - m["submitted"] > total:
                self._evict(slot)
                self._set_terminal(req.uid, RequestStatus.TIMED_OUT)
                self._step_events.append(("timeout", {"uid": req.uid}))
                expired.append(req.uid)
        return expired

    def _fail_impossible_heads(self) -> list[int]:
        """A head-of-line request whose reservation can never be satisfied
        fails (per-request status) instead of wedging the queue — the old
        behavior was an engine-wide RuntimeError."""
        failed = []
        while self.sched.waiting:
            req = self.sched.waiting[0]
            need = self.sched.required_pages(req)
            hopeless = (need > self.kv.max_pages_per_slot
                        or need > self.kv.num_pages)
            if not hopeless and not self.sched.running:
                # nothing running → no page will ever be freed
                hopeless = need > self.kv.free_pages
            if not hopeless:
                break
            self.sched.waiting.popleft()
            self._set_terminal(req.uid, RequestStatus.FAILED)
            self._step_events.append(("fail_head", {"uid": req.uid}))
            failed.append(req.uid)
        return failed

    def _admit(self, slot: int, req: Request) -> Optional[int]:
        """Prefill an admitted request into ``slot``.  Returns the uid if
        the prefill logits were non-finite (request quarantined → FAILED),
        else None."""
        plen = len(req.prompt)
        bucket = _next_bucket(plen, self.ecfg.min_bucket,
                              self.ecfg.slot_capacity)
        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, :plen] = req.prompt
        table = self.kv.table()
        with self.tracer.span("engine.prefill", uid=req.uid, slot=slot,
                              plen=plen, bucket=bucket):
            tok_bad, self.caches, self._state = self._prefill(
                self.params, self.caches, self._state, jnp.asarray(tokens),
                jnp.asarray(table[slot:slot + 1]), jnp.int32(plen),
                jnp.int32(slot), self._seed,
                jnp.uint32(req.uid), jnp.float32(req.temperature),
                jnp.int32(req.top_k), jnp.float32(req.top_p),
                jnp.int32(req.max_new), self._poison_uid, self._poison_pos)
            self._table_dirty = True
            with self.tracer.span("engine.wait"):
                first, was_bad = (int(v) for v in jax.device_get(tok_bad))
        uid = req.uid
        self._step_events.append(("admit", {"uid": uid, "slot": slot,
                                            "plen": plen}))
        self._uids[slot] = uid
        self._prior[slot] = len(self._out[uid])
        self._gen[slot] = 1
        if was_bad:
            self._evict(slot)
            self._set_terminal(uid, RequestStatus.FAILED)
            self._step_events.append(("prefill_nan", {"uid": uid}))
            return uid
        now = self._now()
        self._out[uid].append(first)
        self._tokens_harvested += 1
        self._c_tokens.inc()
        m = self.metrics[uid]
        if m["first_token"] is None:
            m["first_token"] = now
        m["token_times"].append(now)
        m["status"] = RequestStatus.RUNNING
        eos_hit = (self.ecfg.eos_token is not None
                   and first == self.ecfg.eos_token)
        self._done[slot] = bool(req.max_new <= 1 or eos_hit)
        return None

    def _ensure_segment_pages(self, grow_allowed: bool = True) -> None:
        """Extend every running slot's pages to cover the coming segment's
        KV writes (oldest request first).  Growth is a no-op for fully
        reserved slots; an optimistic slot that cannot grow preempts the
        youngest running request and retries — decoding past a slot's owned
        pages would silently drop KV into the trash page, so coverage is a
        hard precondition for the segment."""
        with self.tracer.span("engine.grow"):
            self._grow_pages(grow_allowed)

    def _grow_pages(self, grow_allowed: bool) -> None:
        seg = self.ecfg.segment_len
        order = sorted(self.sched.running,
                       key=self.sched.admitted_seq.__getitem__)
        for slot in order:
            if slot not in self.sched.running:
                continue                    # preempted by an older slot
            req = self.sched.running[slot]
            plen, gen = len(req.prompt), int(self._gen[slot])
            # next segment writes positions [plen+gen-1, plen+gen+seg-2];
            # the final sampled token is never fed back, so the request
            # never writes past plen + max_new - 2
            need_tokens = min(plen + gen - 1 + seg, req.max_tokens - 1)
            while True:
                need = (pages_needed(need_tokens, self.ecfg.page_size)
                        - self.kv.num_owned(slot))
                if need <= 0:
                    break
                if not grow_allowed:        # injected allocator exhaustion
                    self._preempt(slot)
                    break
                if self.kv.grow(slot, need):
                    self._c_grows.inc(need)
                    self._step_events.append(("grow", {"slot": slot,
                                                       "pages": need}))
                    self._table_dirty = True
                    break
                victim = self.sched.youngest_running()
                if victim == slot:
                    # nothing younger to evict — preempt the grower itself
                    self._preempt(slot)
                    break
                self._preempt(victim)

    def _run_segment(self) -> np.ndarray:
        """One jitted decode segment.  Returns the per-slot quarantine
        flags (non-finite logits seen) for the host to act on."""
        running = np.zeros(self.ecfg.num_slots, bool)
        for s in self.sched.running:
            running[s] = True
        if self._table_dirty:
            self._table_dev = jnp.asarray(self.kv.table())
            self._table_dirty = False
        refill = jnp.bool_(self.ecfg.stop_on_finish
                           and self.sched.num_waiting > 0)
        with self.tracer.span("engine.decode_segment",
                              slots=len(self.sched.running)) as sp:
            (self.caches, self._state, out, iters, slot_iters,
             kv_pages, moe) = self._segment(
                self.params, self.caches, self._state, self._table_dev,
                self._seed, refill, self._poison_uid, self._poison_pos)
            # ONE host sync per segment: everything the host bookkeeping needs
            with self.tracer.span("engine.wait"):
                (gen_after, still_active, bad, out, iters, slot_iters,
                 kv_pages, moe) = jax.device_get(
                    (self._state.gen, self._state.active, self._state.bad,
                     out, iters, slot_iters, kv_pages, moe))
        with self.tracer.span("engine.harvest") as hp:
            now = self._now()
            harvested = 0
            for slot in self.sched.running:
                n_new = int(gen_after[slot] - self._gen[slot])
                if n_new:
                    uid = int(self._uids[slot])
                    toks = [int(t) for t in out[slot, :n_new]]
                    self._out[uid].extend(toks)
                    self.metrics[uid]["token_times"].extend([now] * n_new)
                    harvested += n_new
            hp.set(tokens=harvested)
            sp.set(tokens=harvested)
            self._tokens_harvested += harvested
            self._c_tokens.inc(harvested)
            self.decode_iterations += int(iters)
            self.decode_slot_iterations += int(slot_iters)
            self.decode_kv_pages += int(kv_pages)
            self._c_iters.inc(int(iters))
            self._c_slot_iters.inc(int(slot_iters))
            self._c_kv_pages.inc(int(kv_pages))
            for name, n in moe.items():
                setattr(self, name, getattr(self, name) + int(n))
                self._c_moe[name].inc(int(n))
            self._gen = gen_after.copy()
            self._done |= running & ~still_active & ~bad
        return running & bad

    def _quarantine(self, bad: np.ndarray) -> list[int]:
        """Retire slots whose logits went non-finite as FAILED — one
        poisoned request must never take down the batch."""
        failed = []
        for slot in list(self.sched.running):
            if bad[slot]:
                req = self._evict(slot)
                self._set_terminal(req.uid, RequestStatus.FAILED)
                self.tracer.event("engine.quarantine", uid=req.uid, slot=slot)
                self._step_events.append(("quarantine", {"uid": req.uid,
                                                         "slot": slot}))
                failed.append(req.uid)
        if failed:
            self._flight_dump("nan_quarantine", uids=failed,
                              step=self._step_idx)
        return failed

    def _retire_done(self) -> list[int]:
        done = [slot for slot in self.sched.running if self._done[slot]]
        if not done:
            return []
        finished = []
        with self.tracer.span("engine.retire", slots=len(done)):
            for slot in done:
                req = self.sched.retire(slot)
                self._done[slot] = False
                self._table_dirty = True
                self._set_terminal(req.uid, RequestStatus.FINISHED)
                self._step_events.append(("retire", {"uid": req.uid,
                                                     "slot": slot}))
                finished.append(req.uid)
        return finished

    def _maybe_fallback_reserve(self) -> None:
        """Thrash watermark: when preemption churns (≥ thrash_preemptions
        in the last thrash_window steps), optimistic admission is costing
        more repeated prefill than it saves — fall back to full
        reservation for all future admissions.  Already-running optimistic
        slots keep growing via ``_ensure_segment_pages``."""
        if self.sched.mode != "optimistic":
            return
        floor = self._step_idx - self.ecfg.thrash_window
        self._preempt_log = [s for s in self._preempt_log if s > floor]
        if len(self._preempt_log) >= self.ecfg.thrash_preemptions:
            self.sched.mode = "reserve"
            self._fallback_step = self._step_idx
            self.tracer.event("engine.fallback_reserve", step=self._step_idx)
            self._step_events.append(("fallback_reserve",
                                      {"step": self._step_idx}))


# -- jitted bodies ----------------------------------------------------------

# Buffers the engine donates into its jitted bodies, BY NAME.  Both bodies
# return fresh versions of these (the engine rebinds them every step), so
# XLA may reuse their device memory for the outputs.  Donation is declared
# by parameter name and resolved to positions via signature inspection —
# the static analyzer (repro.analysis.invariance, TPP303) re-derives the
# positions and rejects a declaration that would donate a live input such
# as the weights.  BOUND_ARGS is the (cfg, ecfg) prefix bound before jit;
# donate_argnums are relative to the remaining parameters.
DONATED_ARGS = ("caches", "state")
# the expert layer's counts a decode segment returns (``lm.aux_zero``)
MOE_COUNTERS = ("moe_assignments", "moe_experts_touched")
BOUND_ARGS = 2


def donation_argnums(fn, *, bound: int = BOUND_ARGS) -> tuple[int, ...]:
    """Positions of :data:`DONATED_ARGS` in ``fn``'s signature, shifted by
    the ``bound`` leading parameters bound before jit."""
    import inspect
    params = list(inspect.signature(fn).parameters)
    return tuple(params.index(name) - bound for name in DONATED_ARGS)


@functools.lru_cache(maxsize=None)
def _jitted_fns(cfg: ModelConfig, ecfg: EngineConfig, backend: str):
    """One (prefill, segment) jit pair per (model, engine config, kernel
    backend) — shared across Engine instances so a fresh engine reuses
    compiled code.  The kernel backend is read while tracing, so it is part
    of the key and pinned around each trace: an engine built under one
    backend never runs a program traced under another."""
    # donation saves a cache copy per call on accelerators; XLA:CPU warns
    # and ignores it, so only request it off-CPU
    on_cpu = jax.default_backend() == "cpu"

    def pinned(fn):
        def body(*args):
            with ops.use_backend(backend):
                return fn(cfg, ecfg, *args)
        body.__name__ = fn.__name__
        return body

    segment = jax.jit(
        pinned(_decode_segment),
        donate_argnums=() if on_cpu else donation_argnums(_decode_segment))
    prefill = jax.jit(
        pinned(_prefill_one),
        donate_argnums=() if on_cpu else donation_argnums(_prefill_one))
    return prefill, segment

def _prefill_one(cfg, ecfg, params, caches, state, tokens, table_row, plen,
                 slot, seed, uid, temp, top_k, top_p, limit,
                 poison_uid, poison_pos):
    """Batch-1 prefill of one admitted request + its first sampled token,
    fused with the slot's DecodeState update (the state stays device-resident
    between engine steps; only (first token, quarantine flag) cross back to
    the host).  ``poison_*`` is the fault plan's NaN injection — with the
    no-op sentinel the `where` is a bitwise identity."""
    local = _fresh_slot_state(caches)
    logit_index = plen[None] - 1 if jnp.ndim(plen) == 0 else plen - 1
    logits, new_local = lm.prefill(
        cfg, params, local, {"tokens": tokens}, ep_axis=ecfg.ep_axis,
        unroll=ecfg.unroll_layers, page_table=table_row,
        page_size=ecfg.page_size, logit_index=logit_index)
    hit = (uid == poison_uid) & (logit_index + 1 >= poison_pos)
    logits = jnp.where(hit[:, None], jnp.float32(jnp.nan), logits)
    bad = ~lm.finite_logits(logits)[0]
    tok = sample_tokens(logits, uids=uid[None], positions=logit_index + 1,
                        seed=seed, temperature=temp[None],
                        top_k=top_k[None], top_p=top_p[None])[0]
    eos = (tok == ecfg.eos_token) if ecfg.eos_token is not None \
        else jnp.bool_(False)
    state = DecodeState(
        tok=state.tok.at[slot].set(tok),
        pos=state.pos.at[slot].set(plen),
        gen=state.gen.at[slot].set(1),
        limit=state.limit.at[slot].set(limit),
        active=state.active.at[slot].set((limit > 1) & ~eos & ~bad),
        bad=state.bad.at[slot].set(bad),
        uids=state.uids.at[slot].set(uid),
        temp=state.temp.at[slot].set(temp),
        top_k=state.top_k.at[slot].set(top_k),
        top_p=state.top_p.at[slot].set(top_p))
    tok_bad = jnp.stack([tok, bad.astype(jnp.int32)])
    return tok_bad, _merge_slot_state(caches, new_local, slot), state


def _decode_segment(cfg, ecfg, params, caches, state, table, seed, refill,
                    poison_uid, poison_pos):
    """Up to ``segment_len`` decode steps for every slot in one
    ``lax.while_loop``; finished slots go inactive (their writes keep
    landing in their own pages / the trash page and are discarded).
    ``refill`` (traced bool — requests are waiting) exits the loop as soon
    as any slot finishes OR is quarantined, so the freed slot refills next
    engine step instead of idling out the segment.  A slot whose logits go
    non-finite (organically, or via the fault plan's ``poison_*``
    injection) is flagged ``bad``, contributes no token, and stops
    advancing — the other slots keep decoding.  Also returns the
    iterations the loop ran, the active slots summed over them, the KV
    pages those slots' paged attention reads, ``ceil((pos + 1) /
    page_size)`` each (latent pages for MLA), summed the same way (int32
    scalars), and a dict of the expert layer's counts over the active
    slots (``MOE_COUNTERS``; empty for a dense model)."""
    seg = ecfg.segment_len
    b = state.tok.shape[0]
    out0 = jnp.full((b, seg), -1, jnp.int32)

    def cond(c):
        t, _, st, _, finished_any, _, _, _ = c
        return (t < seg) & jnp.any(st.active) & ~(refill & finished_any)

    def body(c):
        t, caches, st, out, finished_any, slot_iters, kv_pages, moe = c
        slot_iters = slot_iters + jnp.sum(st.active, dtype=jnp.int32)
        kv_pages = kv_pages + jnp.sum(
            jnp.where(st.active, (st.pos + ecfg.page_size) // ecfg.page_size,
                      0), dtype=jnp.int32)
        tok_in = jnp.where(st.active, st.tok, 0)
        logits, caches, counts = lm.decode_step(
            cfg, params, caches, tok_in, st.pos, ep_axis=ecfg.ep_axis,
            unroll=ecfg.unroll_layers, page_table=table,
            page_size=ecfg.page_size, token_mask=st.active)
        moe = {k: moe[k] + counts[k] for k in moe}
        hit = st.active & (st.uids == poison_uid) & (st.pos + 1 >= poison_pos)
        logits = jnp.where(hit[:, None], jnp.float32(jnp.nan), logits)
        bad_now = st.active & ~lm.finite_logits(logits)
        alive = st.active & ~bad_now
        nxt = sample_tokens(logits, uids=st.uids, positions=st.pos + 1,
                            seed=seed, temperature=st.temp, top_k=st.top_k,
                            top_p=st.top_p)
        rec = jnp.where(alive, nxt, -1)
        out = jax.lax.dynamic_update_slice(out, rec[:, None], (0, t))
        gen = st.gen + alive.astype(jnp.int32)
        eos = (nxt == ecfg.eos_token) if ecfg.eos_token is not None \
            else jnp.zeros_like(st.active)
        done = alive & ((gen >= st.limit) | eos)
        st = st._replace(
            tok=jnp.where(alive, nxt, st.tok),
            pos=st.pos + alive.astype(jnp.int32),
            gen=gen, active=alive & ~done, bad=st.bad | bad_now)
        return (t + 1, caches, st, out,
                finished_any | jnp.any(done) | jnp.any(bad_now), slot_iters,
                kv_pages, moe)

    moe0 = {k: jnp.int32(0) for k in MOE_COUNTERS} if cfg.is_moe else {}
    (iters, caches, st, out, _, slot_iters, kv_pages,
     moe) = jax.lax.while_loop(
        cond, body, (jnp.int32(0), caches, state, out0, jnp.bool_(False),
                     jnp.int32(0), jnp.int32(0), moe0))
    return caches, st, out, iters, slot_iters, kv_pages, moe
