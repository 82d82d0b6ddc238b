"""Continuous (iteration-level) batching scheduler for tpudecode.

The reference served autoregressive models the Paddle Serving way: one
request = one predictor run, batch membership frozen at admission, a
request that finishes early rides the batch until the longest member
is done. This scheduler replaces that with the iteration-level model:
every decode **step** is a scheduling opportunity —

    retire   slots whose request hit eos / its token budget / its
             deadline (the row is free *this* iteration, not at batch
             end);
    admit    queued requests into the freed rows, picked by weighted
             fair queuing (`qos.QosPolicy`), prefilled through the
             bucketed encoder executables;
    step     ONE compiled step function over all `num_slots` rows;
             only [num_slots] token ids cross the host boundary.

Admission control mirrors PR 3's batcher: bounded queue (fast
`RejectedError` on overload), per-request deadlines (`DeadlineExceeded`
— HTTP 504), plus QoS preemption (`PreemptedError` — HTTP 429).

The loop thread is supervised the same way ModelServer workers are:
a crash (including the injected `worker_crash` chaos fault at the
``serving.worker`` point) fails the in-flight requests, returns every
slot to the pool — leak-free, pinned by tests — and respawns.

Tests can skip the thread entirely: construct, `submit`, and call
`run_iteration()` by hand for a fully deterministic drive.
"""
import logging
import threading
import time

import numpy as np

from ... import telemetry as _tm
from ...resilience import chaos as _chaos
from ..batcher import (CancelledError, DeadlineExceeded, Future,
                       PreemptedError, RejectedError, ServerClosed)
from .qos import QosPolicy
from .slots import SlotPool

_LOG = logging.getLogger("paddle_tpu.serving.decode")

__all__ = ["DecodeConfig", "DecodeRequest", "DecodeResult",
           "ContinuousScheduler"]


class DecodeConfig:
    def __init__(self, max_queue_requests=256, default_deadline_ms=None,
                 default_max_new_tokens=None, bos=0, eos=None,
                 idle_wait_s=0.05):
        self.max_queue_requests = int(max_queue_requests)
        self.default_deadline_ms = default_deadline_ms
        self.default_max_new_tokens = default_max_new_tokens
        self.bos = int(bos)
        self.eos = eos if eos is None else int(eos)
        self.idle_wait_s = float(idle_wait_s)


class DecodeRequest:
    __slots__ = ("src", "src_len", "tenant", "max_new_tokens",
                 "deadline", "enqueue_t", "future", "request_id",
                 "cancelled", "poisoned")

    def __init__(self, src, src_len, tenant, max_new_tokens, deadline,
                 request_id=None, poisoned=False):
        self.src = src
        self.src_len = src_len
        self.tenant = tenant
        self.max_new_tokens = max_new_tokens
        self.deadline = deadline           # monotonic seconds or None
        self.enqueue_t = time.monotonic()
        self.future = Future(deadline)
        self.request_id = request_id
        # set by cancel(): the iteration loop retires the slot (it is
        # the slot pool's single writer; cancel never frees directly)
        self.cancelled = False
        # set by the request_poison chaos fault: stepping this request
        # crashes its replica (rides resubmissions by design)
        self.poisoned = poisoned

    def expired(self, now):
        return self.deadline is not None and now >= self.deadline


class DecodeResult:
    """What a decode future resolves to."""

    __slots__ = ("tokens", "finish_reason", "tenant", "ttft_s",
                 "decode_s", "token_t")

    def __init__(self, tokens, finish_reason, tenant, ttft_s, decode_s,
                 token_t=None):
        self.tokens = tokens                # np.int32 [n_generated]
        self.finish_reason = finish_reason  # "eos" | "length"
        self.tenant = tenant
        self.ttft_s = ttft_s
        self.decode_s = decode_s
        # time.monotonic() at which each token was emitted, np.float64
        # [n_generated]: np.diff gives every gap between tokens, so one
        # stalled iteration shows as itself and is not averaged away
        self.token_t = token_t

    def __repr__(self):
        return (f"DecodeResult({len(self.tokens)} tokens, "
                f"{self.finish_reason!r}, tenant={self.tenant!r})")


class ContinuousScheduler:
    """Continuous-batching decode over one `DecodeEngine`."""

    def __init__(self, engine, qos=None, config=None, name="decoder",
                 warmup=True):
        self.engine = engine
        self.qos = qos or QosPolicy()
        self.config = config or DecodeConfig()
        self.name = name
        self.pool = SlotPool(engine.num_slots)
        self.state = engine.init_state()
        # host mirrors of the per-slot decode cursor; free slots hold 0
        self._ids = np.zeros(engine.num_slots, np.int64)
        self._pos = np.zeros(engine.num_slots, np.int64)
        self._queues = {}            # tenant -> list of DecodeRequest
        self._queued = 0
        self._cond = threading.Condition()
        self._closed = False
        self._thread = None
        self._iteration = 0
        self._started = False
        self.restarts = 0
        self.preemptions = 0
        self.tokens_generated = 0    # lifetime tokens (goodput gauges)
        # set by serving.farm: this scheduler's replica index, carried
        # into the chaos ctx (worker_crash replica=R targeting) and
        # the serving.replica.<i>.* telemetry
        self.replica_index = None
        if warmup:
            engine.warmup()

    # ------------------------------------------------------ caller side
    def submit(self, src, src_len=None, tenant="default",
               max_new_tokens=None, deadline_ms=None,
               request_id=None, poison=False):
        """Enqueue one sequence; returns a Future resolving to a
        `DecodeResult`. Sheds immediately on a full queue or an
        oversized source (RejectedError) — overload never builds an
        unbounded backlog."""
        src = np.asarray(src, np.int64).reshape(-1)
        if src_len is None:
            src_len = len(src)
        src_len = int(src_len)
        if len(src) > self.engine.src_max_len:
            raise RejectedError(
                f"source of {len(src)} tokens exceeds the decode "
                f"tier's src_max_len {self.engine.src_max_len}")
        cap = self.engine.max_new_tokens
        if max_new_tokens is None:
            max_new_tokens = self.config.default_max_new_tokens or cap
        max_new_tokens = max(1, min(int(max_new_tokens), cap))
        if deadline_ms is None:
            deadline_ms = self.config.default_deadline_ms
        deadline = None if deadline_ms is None \
            else time.monotonic() + float(deadline_ms) / 1e3
        tenant = str(tenant)
        self.qos.tenant(tenant)        # strict mode rejects here
        req = DecodeRequest(src, src_len, tenant, max_new_tokens,
                            deadline, request_id=request_id,
                            poisoned=poison)
        with self._cond:
            if self._closed:
                raise ServerClosed("decoder is draining; not "
                                   "accepting new requests")
            if self._queued >= self.config.max_queue_requests:
                if _tm.enabled():
                    _tm.counter(
                        "serving.decode.rejected_queue_full").inc()
                raise RejectedError(
                    f"decode queue full "
                    f"({self.config.max_queue_requests} requests); "
                    f"retry later")
            backlogged = [t for t, q in self._queues.items() if q]
            if tenant not in backlogged:
                self.qos.on_backlogged(
                    tenant, backlogged
                    + list(self.pool.held_by_tenant()))
            self._queues.setdefault(tenant, []).append(req)
            self._queued += 1
            depth = self._queued
            if _tm.enabled():
                _tm.counter("serving.decode.requests").inc()
                _tm.gauge("serving.decode.queue_depth").set(depth)
            self._cond.notify()
        if request_id and _tm.reqtrace_enabled():
            _tm.reqtrace.event(request_id, "decode.enqueue",
                               replica=self.replica_index,
                               tenant=tenant, queue_depth=depth)
        return req.future

    def decode(self, src, timeout=None, **kw):
        """Blocking convenience: submit + wait -> DecodeResult."""
        return self.submit(src, **kw).result(timeout=timeout)

    def cancel(self, future):
        """Best-effort cancellation of the request behind `future`
        (the losing leg of a hedged request). A still-queued request
        is removed and failed with CancelledError right here; an
        admitted one is only FLAGGED — the iteration loop retires it
        and reclaims the slot at the next retire pass, because the
        slot pool has exactly one writer. Either way the future
        resolves exactly once: the queue removal happens under the
        same lock `_admit` pops under, and a flagged slot is touched
        only by the loop thread. Returns True when the request was
        found (still pending somewhere), False when it already
        finished or was never ours."""
        with self._cond:
            for tenant, q in self._queues.items():
                for req in q:
                    if req.future is future:
                        q.remove(req)
                        self._queued -= 1
                        req.future.set_error(CancelledError(
                            "cancelled while queued"))
                        if _tm.enabled():
                            _tm.counter(
                                "serving.decode.cancelled_queued").inc()
                        return True
        slot = self.pool.find(future)
        if slot is not None:
            req = slot.request      # snapshot: loop may retire it
            if req is not None:
                req.cancelled = True
                return True
        return False

    # ------------------------------------------------------- iteration
    def run_iteration(self):
        """One retire/admit/step cycle. Returns the number of active
        slots stepped (0 = nothing to do). Single-threaded by
        contract: either the started loop thread calls this, or a
        test drives it by hand — never both."""
        with _tm.span("serving.sched.iteration",
                      active=self.pool.active_count(),
                      queued=self._queued) as it_span:
            return self._run_iteration(it_span)

    def _run_iteration(self, it_span):
        now = time.monotonic()
        self._retire_deadlines(now)
        self._drop_expired_queued(now)
        had_work = self.pool.active_count() > 0 or self._queued > 0
        if had_work and _chaos.armed():
            # the serving.worker chaos point (worker_crash /
            # replica_slow / replica_flap faults): counted per working
            # iteration, like ModelServer counts per dequeued batch —
            # deterministic under load
            try:
                _chaos.check("serving.worker",
                             detail=f"decode loop {self.name}",
                             replica=self.replica_index)
            except _chaos.ChaosFault:
                if _tm.reqtrace_enabled():
                    # every request riding this replica is about to
                    # die with it — a chaos fault is a capture trigger
                    for slot in self.pool.active():
                        r = slot.request
                        if r is not None and r.request_id:
                            _tm.reqtrace.flag(r.request_id, "chaos")
                            _tm.reqtrace.event(
                                r.request_id, "chaos.fault",
                                replica=self.replica_index,
                                slot=slot.index)
                raise
            # a poisoned request (request_poison fault, tagged at farm
            # submit so the tag rides resubmissions) kills the replica
            # that stepped it — the blast the guard must contain
            for slot in self.pool.active():
                r = slot.request
                if r is not None and r.poisoned:
                    if r.request_id and _tm.reqtrace_enabled():
                        _tm.reqtrace.flag(r.request_id, "chaos")
                        _tm.reqtrace.event(
                            r.request_id, "chaos.request_poison",
                            replica=self.replica_index,
                            slot=slot.index)
                    raise _chaos.ChaosFault(
                        {"name": "request_poison",
                         "point": "serving.request"},
                        f"poisoned request in slot {slot.index} of "
                        f"{self.name}")
        it_span.set(admitted=self._admit())
        return self._step_active()

    def _retire_deadlines(self, now):
        for slot in self.pool.active():
            req = slot.request
            if req.cancelled:
                if not req.future.done():
                    req.future.set_error(CancelledError(
                        f"cancelled after {len(slot.tokens)} "
                        f"generated tokens; slot reclaimed"))
                self._finish_slot(slot, delivered=False,
                                  reason="cancelled")
                continue
            if req.expired(now):
                req.future.set_error(DeadlineExceeded(
                    f"deadline expired after {len(slot.tokens)} "
                    f"generated tokens"))
                self._finish_slot(slot, delivered=False,
                                  reason="deadline")
                if _tm.enabled():
                    _tm.counter("serving.decode.deadline_retired").inc()

    def _drop_expired_queued(self, now):
        with self._cond:
            for tenant, q in self._queues.items():
                live = []
                for req in q:
                    if req.expired(now):
                        req.future.set_error(DeadlineExceeded(
                            "deadline expired in decode queue"))
                        self._queued -= 1
                        if _tm.enabled():
                            _tm.counter(
                                "serving.decode.rejected_deadline").inc()
                    else:
                        live.append(req)
                self._queues[tenant] = live
            if _tm.enabled():
                _tm.gauge("serving.decode.queue_depth").set(
                    self._queued)

    def _admit(self):
        """Fill free slots from the queues by WFQ; preempt if allowed
        and somebody is starving below their fair share. Returns the
        number of requests admitted."""
        batch, slots = [], []
        while True:
            with self._cond:
                queued = [t for t, q in self._queues.items() if q]
                if not queued:
                    break
                held = self.pool.held_by_tenant()
                if self.pool.free_count() == 0:
                    victim_slot = self._pick_preemption(queued, held)
                    if victim_slot is None:
                        break
                    self._preempt(victim_slot)
                    held = self.pool.held_by_tenant()
                tenant = self.qos.pick_tenant(queued, held)
                if tenant is None:
                    break
                req = self._queues[tenant].pop(0)
                self._queued -= 1
            # WFQ charge at admission: the packet length is the
            # request's reserved token budget, so virtual time moves
            # BETWEEN picks and tenants interleave within one wave;
            # unconsumed budget is refunded at retirement
            self.qos.charge(tenant, req.max_new_tokens)
            slot = self.pool.alloc(req, self._iteration)
            self._ids[slot.index] = self.config.bos
            self._pos[slot.index] = 0
            batch.append(req)
            slots.append(slot.index)
            if _tm.enabled():
                _tm.histogram(
                    "serving.decode.queue_wait_seconds").observe(
                    time.monotonic() - req.enqueue_t)
                # admit marker on the timeline, carrying the caller's
                # request id so a trace can be searched by it
                _tm.instant_event("serving.decode.admit",
                                  tenant=req.tenant, slot=slot.index,
                                  request_id=req.request_id)
            if req.request_id and _tm.reqtrace_enabled():
                _tm.reqtrace.event(
                    req.request_id, "decode.admit",
                    replica=self.replica_index, slot=slot.index,
                    tenant=req.tenant,
                    queue_wait_ms=round(
                        (time.monotonic() - req.enqueue_t) * 1e3, 3))
        if batch:
            self.state = self.engine.admit(self.state, batch, slots)
            if _tm.enabled():
                _tm.counter("serving.decode.admitted").inc(len(batch))
                _tm.gauge("serving.decode.queue_depth").set(
                    self._queued)
        return len(batch)

    def _pick_preemption(self, queued, held):
        starved = self.qos.pick_tenant(queued, held)
        victim = self.qos.preemption_victim(
            starved, queued, held, self.pool.num_slots)
        if victim is None:
            return None
        cands = [s for s in self.pool.active()
                 if s.request.tenant == victim]
        if not cands:
            return None
        # evict the youngest slot: least generated work destroyed
        return max(cands, key=lambda s: (s.joined_iter, s.index))

    def _preempt(self, slot):
        req = slot.request
        req.future.set_error(PreemptedError(
            f"preempted after {len(slot.tokens)} generated tokens to "
            f"admit a tenant below its fair share; retry"))
        self._finish_slot(slot, delivered=False, reason="preempted")
        self.preemptions += 1
        if _tm.enabled():
            _tm.counter("serving.decode.preemptions").inc()
            _tm.counter(
                f"serving.decode.tenant.{req.tenant}.preemptions").inc()

    def _step_active(self):
        active = self.pool.active()
        if not active:
            if _tm.enabled():
                _tm.gauge("serving.decode.slot_occupancy").set(0.0)
            return 0
        self._iteration += 1
        with _tm.span("serving.decode.step", slots=self.pool.num_slots,
                      active=len(active)):
            nxt = self.engine.step(self.state, self._ids, self._pos,
                                   seed=self._iteration)
        now = time.monotonic()
        eos = self.config.eos
        trace = _tm.reqtrace_enabled()
        occupancy = self.pool.occupancy() if trace else None
        for slot in active:
            req = slot.request
            tok = int(nxt[slot.index])
            if trace and req.request_id:
                # per-iteration slot occupancy on the request's
                # timeline: which step, in how full a pool
                _tm.reqtrace.event(
                    req.request_id, "decode.step",
                    replica=self.replica_index, slot=slot.index,
                    iteration=self._iteration, occupancy=occupancy)
            if slot.first_token_t is None:
                slot.first_token_t = now
                if _tm.enabled():
                    _tm.histogram("serving.decode.ttft_seconds").observe(
                        now - req.enqueue_t)
                if trace and req.request_id:
                    _tm.reqtrace.event(
                        req.request_id, "decode.first_token",
                        replica=self.replica_index, slot=slot.index,
                        ttft_ms=round((now - req.enqueue_t) * 1e3, 3))
            slot.tokens.append(tok)
            slot.token_t.append(now)
            self.tokens_generated += 1
            if _tm.enabled():
                _tm.counter("serving.decode.tokens_total").inc()
                _tm.counter(
                    f"serving.decode.tenant.{req.tenant}.tokens").inc()
            if eos is not None and tok == eos:
                self._deliver(slot, "eos", now)
            elif len(slot.tokens) >= req.max_new_tokens:
                self._deliver(slot, "length", now)
            else:
                self._ids[slot.index] = tok
                self._pos[slot.index] += 1
        if _tm.enabled():
            _tm.gauge("serving.decode.slot_occupancy").set(
                self.pool.occupancy())
        return len(active)

    def _deliver(self, slot, reason, now):
        req = slot.request
        req.future.set_result(DecodeResult(
            tokens=np.asarray(slot.tokens, np.int32),
            finish_reason=reason, tenant=req.tenant,
            ttft_s=(slot.first_token_t - req.enqueue_t
                    if slot.first_token_t else None),
            decode_s=now - slot.joined_t,
            token_t=np.asarray(slot.token_t, np.float64)))
        self._finish_slot(slot, delivered=True, reason=reason)

    def _finish_slot(self, slot, delivered, reason):
        req = slot.request
        unused = req.max_new_tokens - len(slot.tokens or ())
        if unused > 0:
            self.qos.refund(req.tenant, unused)
        if req.request_id and _tm.reqtrace_enabled():
            if reason == "deadline":
                _tm.reqtrace.flag(req.request_id, "deadline")
            # the slot's admit->retire lifetime as one span, stamped
            # at retirement (the admit instant anchors its start)
            dur_us = int((time.monotonic() - slot.joined_t) * 1e6)
            _tm.reqtrace.span_at(
                req.request_id, "decode.slot",
                _tm.now_us() - dur_us, dur_us,
                replica=self.replica_index, slot=slot.index,
                reason=reason, delivered=delivered,
                tokens=len(slot.tokens or ()))
            _tm.reqtrace.event(
                req.request_id, "decode.retire",
                replica=self.replica_index, slot=slot.index,
                reason=reason, delivered=delivered)
        self.pool.release(slot)
        self._ids[slot.index] = 0
        self._pos[slot.index] = 0
        if _tm.enabled():
            _tm.counter("serving.decode.retired").inc()
            _tm.counter(f"serving.decode.retired_{reason}").inc()
            _tm.instant_event("serving.decode.retire",
                              tenant=req.tenant, slot=slot.index,
                              reason=reason, delivered=delivered,
                              request_id=req.request_id)

    # ------------------------------------------------------- lifecycle
    def start(self):
        """Spawn the supervised decode loop thread."""
        if self._thread is not None and self._thread.is_alive():
            return self
        self._started = True
        self._thread = threading.Thread(
            target=self._loop_guarded,
            name=f"tpudecode-{self.name}", daemon=True)
        self._thread.start()
        return self

    def _loop_guarded(self):
        try:
            self._loop()
        except BaseException as e:      # noqa: BLE001 — thread death
            if self._closed:
                return
            self._crash_recover(e)
            self.restarts += 1
            if _tm.enabled():
                _tm.counter("serving.decode.worker_restarts").inc()
            _LOG.warning(
                "tpudecode loop %s died (%s: %s) — slots reclaimed, "
                "restarting", self.name, type(e).__name__, e)
            # the dying thread IS self._thread and still alive here;
            # drop the reference so start() actually respawns
            self._thread = None
            self.start()

    def _loop(self):
        while True:
            stepped = self.run_iteration()
            if stepped:
                continue
            with self._cond:
                if self._closed and self._queued == 0 \
                        and self.pool.active_count() == 0:
                    return
                # stepped == 0 means nothing active and nothing
                # admissible; park until a submit notifies (bounded
                # wait so close/cap changes are re-checked)
                self._cond.wait(self.config.idle_wait_s)

    def _crash_recover(self, exc):
        """Leak-free crash cleanup: every bound slot's request fails
        with the crash error and its row returns to the pool; queued
        requests stay queued for the respawned loop."""
        for slot in self.pool.active():
            if not slot.request.future.done():
                slot.request.future.set_error(exc)
            self._finish_slot(slot, delivered=False, reason="crash")
        self.pool.check()

    def stop(self, drain=True, timeout=30.0):
        """Stop admitting; optionally let the loop drain queued +
        in-flight work before joining."""
        with self._cond:
            self._closed = True
            if not drain:
                for q in self._queues.values():
                    for req in q:
                        req.future.set_error(ServerClosed(
                            "decoder shut down before this request "
                            "ran"))
                    q.clear()
                self._queued = 0
            self._cond.notify_all()
        t = self._thread
        if t is not None:
            t.join(timeout)
        if not drain:
            for slot in self.pool.active():
                slot.request.future.set_error(ServerClosed(
                    "decoder shut down mid-generation"))
                self._finish_slot(slot, delivered=False,
                                  reason="shutdown")

    @property
    def queued(self):
        with self._cond:
            return self._queued

    @property
    def alive(self):
        """False exactly in the crashed-and-not-yet-respawned window
        of a started loop (the farm router's skip signal). A scheduler
        that was never start()ed is driven by hand — always alive."""
        if not self._started:
            return True
        t = self._thread
        return t is not None and t.is_alive()
