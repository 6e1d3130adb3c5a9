"""Slot-based continuous batching over a :class:`GenerationEngine`.

The decode batch is a fixed (B, …) shape; a *slot* is one row of it.
Queued requests are admitted into free slots only at step boundaries —
admission is a batch-1 prefill program writing one cache row, so joining
traffic never changes a shape and never recompiles anything. Finished rows
(EOS, token budget, cache end, page exhaustion, deadline, cancellation)
free their slot — and, on a paged engine, their pages — for the next
request.

On a **paged** engine (docs/INFERENCE.md "Paged cache") admission is
bounded by free *pages*, not just free slots: a request is admitted only
when the pool can cover its prompt; otherwise it stays queued and the
deferral is counted (``gen_admission_rejects_total{reason="free_pages"}``).
While the head is parked on pages, *smaller* later requests may bypass it
into free slots (the head keeps its queue position) — bounded by an
**aging guard**: after ``serve_head_aging_steps`` deferred boundaries the
bypass stops and freed pages are *reserved* for the head
(``engine.reserve_pages``), so a large request can never starve forever
behind a stream of small ones. Prompts that could never fit (no bucket,
or more pages than the whole pool) are rejected at ``submit`` with the
matching reason, instead of overflowing mid-decode.

Serving resilience (docs/RESILIENCE.md "Serving resilience"):

  - **deadlines** — requests carry ``deadline_s``; at every step boundary
    expired queued requests are dropped before admission and expired
    active rows are cancelled (finish reason ``"deadline"``), freeing
    their pages immediately through the same trash-page-safe reclaim as
    EOS;
  - **cancellation** — ``cancel(request_id)`` (or ``req.cancel()``) marks
    a request; the next step boundary applies it (``"cancelled"``) with
    the identical slot/page reclaim — surviving rows are never perturbed;
  - **overload control** — a bounded admission queue
    (``serve_max_queue``) with policy ``"reject"`` (shed the new request)
    or ``"shed"`` (evict the oldest queued request already past its
    deadline), plus a free-page load-shed watermark
    (``serve_shed_page_floor``). Shed requests finish with reason
    ``"shed"`` and are counted (``gen_shed_total{cause=}``,
    ``gen_queue_age_seconds{outcome=}``);
  - **degrade-to-safe speculation** — on a speculative engine a
    :class:`~mxnet_tpu.resilience.serving.SpeculationGovernor` watches the
    windowed accept rate and falls back to the plain paged decode step
    (token-identical) when it collapses, re-arming after a cooldown;
  - **dispatch watchdog** — every compiled dispatch runs under a soft
    ``serve_watchdog_s`` timeout that emits ``gen_stuck_dispatch``
    (program family + step id) instead of hanging the server silently;
  - **fault sites** — engine dispatches fire ``gen.prefill`` /
    ``gen.decode`` / ``gen.verify`` and run under
    :func:`~mxnet_tpu.resilience.retry.retry_call`, so ``make
    chaos-serve`` can prove transient serving faults are absorbed.

Serving telemetry (docs/OBSERVABILITY.md):

  - ``ttft_seconds``          — submit → first sampled token (queue wait
                                + service combined, kept for continuity),
                                per request;
  - ``ttft_queue_seconds``    — submit → admission: the queue-wait half
                                of TTFT, on the batcher clock;
  - ``ttft_service_seconds``  — admission → first sampled token: the
                                prefill-service half of TTFT, measured on
                                the REAL wall clock (fake-clock drills
                                still see true dispatch cost);
  - ``decode_tokens_per_s``   — generated-token rate after the first token,
                                per request;
  - ``gen_queue_depth``       — requests waiting for a slot (gauge);
  - ``gen_active_slots``      — rows currently decoding (gauge);
  - ``gen_queue_age_seconds{outcome=}`` — time spent queued, by how the
                                wait ended (admitted/shed/deadline/
                                cancelled);
  - ``gen_requests_total{reason=...}`` — completions by finish reason;
  - ``gen_admission_rejects_total{reason=...}`` — submit-time rejects and
                                page-bounded admission deferrals.

Request tracing (docs/OBSERVABILITY.md "Request tracing & SLO ledger"):
when ``self.tracer`` is set (the serving replica attaches one when the
``trace`` knob is on), every request's residency here becomes spans —
``replica.queue`` / ``prefill`` / ``decode`` (+ per-dispatch
``decode.round``) — buffered per trace and tail-sample-flushed at local
finish. ``trace_id`` rides in through :meth:`submit` (the fleet router
passes its request id so cross-process traces join); direct clients get
a local ``b{id}`` trace. Tracing off costs each site one
``tracer is None`` read.
"""
from __future__ import annotations

import functools
import itertools
import time
from collections import deque
from typing import List, Optional, Sequence

from .. import observability as _obs
from ..resilience import retry as _retry
from ..resilience import serving as _serving

__all__ = ["ContinuousBatcher", "GenRequest"]

#: every way a request can terminate — the chaos-serve gate asserts each
#: submitted request lands on exactly one of these. ``"redistributed"``
#: is the fleet tier's pull-back: the request was not abandoned, it is
#: being re-run on another replica (distinct from ``"cancelled"``, which
#: is a client decision and terminal for the work itself)
FINISH_REASONS = ("eos", "length", "cache_full", "page_exhausted",
                  "deadline", "cancelled", "shed", "redistributed")


class GenRequest:
    """Handle for one submitted generation request."""

    def __init__(self, req_id: int, prompt, max_new_tokens: int,
                 deadline_s: Optional[float] = None,
                 clock=time.perf_counter):
        self.id = req_id
        self.prompt = list(prompt)
        self.max_new_tokens = int(max_new_tokens)
        self.output: List[int] = []
        self.slot: Optional[int] = None
        # one of FINISH_REASONS once done
        self.finish_reason: Optional[str] = None
        self.submit_t = clock()
        self.deadline_s = None if deadline_s is None else float(deadline_s)
        #: absolute expiry point on the batcher's clock (None = no deadline)
        self.deadline_t = None if self.deadline_s is None \
            else self.submit_t + self.deadline_s
        self.first_token_t: Optional[float] = None
        self.finish_t: Optional[float] = None
        self.cancel_requested = False
        #: trace identity (docs/OBSERVABILITY.md "Request tracing") —
        #: the router's request id for fleet traffic, a local ``b{id}``
        #: for direct clients, None when tracing is off
        self.trace_id: Optional[str] = None
        #: admission timestamp (batcher clock) — the replica.queue /
        #: prefill span boundary and the ttft_queue_seconds sample
        self.admit_t: Optional[float] = None
        #: decode dispatch rounds this request rode
        self.rounds = 0
        #: N-way sampling (``submit(..., samples=N)``): the leader request
        #: this one should fork from at admission (None = independent),
        #: and — on the leader — the whole sample group's handles
        self._fork_of: Optional["GenRequest"] = None
        self.samples: Optional[List["GenRequest"]] = None
        #: True when this request was admitted by a copy-on-write fork
        #: (refcount bump) instead of a prefill
        self.forked = False

    @property
    def done(self) -> bool:
        return self.finish_reason is not None

    def cancel(self) -> None:
        """Request cancellation; applied at the next step boundary (the
        slot and its pages are reclaimed there, finish reason
        ``"cancelled"``). Idempotent; a no-op once the request is done."""
        self.cancel_requested = True

    def expired(self, now: float) -> bool:
        return self.deadline_t is not None and now >= self.deadline_t

    def result(self) -> List[int]:
        if not self.done:
            raise RuntimeError(f"request {self.id} still running")
        return list(self.output)

    @property
    def ttft(self) -> Optional[float]:
        if self.first_token_t is None:
            return None
        return self.first_token_t - self.submit_t


class ContinuousBatcher:
    """FIFO admission of queued requests into free decode slots, with
    deadlines, cancellation, overload shedding, and degrade-to-safe
    speculative decoding (see module docstring). Constructor knobs default
    to the ``serve_*`` config entries (``MXNET_TPU_SERVE_*``); pass
    ``clock=`` to drive deadline arithmetic from a fake clock in tests."""

    def __init__(self, engine, max_queue: Optional[int] = None,
                 queue_policy: Optional[str] = None,
                 shed_page_floor: Optional[int] = None,
                 head_aging_steps: Optional[int] = None,
                 default_deadline_s: Optional[float] = None,
                 spec_window: Optional[int] = None,
                 spec_floor: Optional[float] = None,
                 spec_cooldown: Optional[int] = None,
                 watchdog_s: Optional[float] = None,
                 retry_policy=None, clock=None):
        from .. import config

        self.engine = engine
        self._queue: deque = deque()
        self._slots: List[Optional[GenRequest]] = [None] * engine.batch_size
        self._ids = itertools.count()
        self._clock = clock or time.perf_counter
        self.max_queue = int(max_queue if max_queue is not None
                             else config.get("serve_max_queue"))
        self.queue_policy = str(queue_policy if queue_policy is not None
                                else config.get("serve_queue_policy"))
        if self.queue_policy not in ("reject", "shed"):
            raise ValueError(f"unknown queue policy {self.queue_policy!r}")
        self.shed_page_floor = int(
            shed_page_floor if shed_page_floor is not None
            else config.get("serve_shed_page_floor"))
        self.head_aging_steps = int(
            head_aging_steps if head_aging_steps is not None
            else config.get("serve_head_aging_steps"))
        self.default_deadline_s = float(
            default_deadline_s if default_deadline_s is not None
            else config.get("serve_default_deadline"))
        self._retry_policy = retry_policy or _retry.RetryPolicy()
        # one policy governs every serving retry, including the engine's
        # in-round gen.verify retry
        engine.retry_policy = self._retry_policy
        self._watchdog = _serving.DispatchWatchdog(
            float(watchdog_s if watchdog_s is not None
                  else config.get("serve_watchdog_s")))
        self.governor = None
        if getattr(engine, "speculative", False):
            self.governor = _serving.SpeculationGovernor(
                window=int(spec_window if spec_window is not None
                           else config.get("serve_spec_window")),
                floor=float(spec_floor if spec_floor is not None
                            else config.get("serve_spec_floor")),
                cooldown=int(spec_cooldown if spec_cooldown is not None
                             else config.get("serve_spec_cooldown")))
        self._step_id = 0
        #: rows admitted (prefilled or forked) and rows finished so far: a
        #: step's record (``serve_step``) counts each by difference
        self._admissions = 0
        self._finishes = 0
        self._head_id: Optional[int] = None
        self._head_deferrals = 0
        #: per-request span emitter (observability.tracing.Tracer) —
        #: attached by the serving replica when the ``trace`` knob is
        #: on; None costs every emission site one attribute read
        self.tracer = None
        #: drain mode (fleet tier): no new admissions — queued work is
        #: pulled back by the router, in-flight rows finish or expire
        self.draining = False

    # -- client side ---------------------------------------------------------
    def submit(self, prompt: Sequence[int], max_new_tokens: int = 32,
               deadline_s: Optional[float] = None,
               trace_id: Optional[str] = None,
               samples: int = 1) -> GenRequest:
        """Queue a request. Raises ``ValueError`` for prompts that could
        never be served (no bucket / more pages than the pool); returns an
        already-finished handle (``finish_reason == "shed"``) when overload
        control sheds it — callers must check ``req.done``.

        ``samples=N`` (paged engines) requests N-way parallel sampling
        from one prompt: the returned *leader* prefills once and N-1
        sibling rows are admitted by copy-on-write fork (refcount bump,
        zero recompute, first sibling token resampled from the leader's
        prefill logits). All N handles land on the leader's ``samples``
        list. Siblings ride the normal overload controls; if the leader
        finishes or sheds before a sibling is forked, the sibling falls
        back to an ordinary prefill (the prefix cache, when enabled,
        still makes that cheap).

        ``trace_id`` joins this request to a fleet-level trace (the
        router passes its request id); when tracing is on and no id is
        given, a local ``b{id}`` trace is opened."""
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if len(prompt) < 1:
            raise ValueError("empty prompt")
        if samples < 1:
            raise ValueError("samples must be >= 1")
        if samples > 1 and not self.engine.paged:
            raise ValueError("samples > 1 needs a paged engine "
                             "(copy-on-write fork)")
        try:
            self.engine.bucket_for(len(prompt))  # reject oversize prompts now
        except ValueError:
            # a prompt longer than every bucket is still admissible when
            # a cached prefix (multi-turn session resume) shrinks the
            # suffix into a bucket — the engine's can_admit probes that
            if not (self.engine.paged
                    and getattr(self.engine, "prefix_cache", None) is not None
                    and self.engine.can_admit(prompt)):
                _obs.counter(
                    "gen_admission_rejects_total",
                    "requests rejected or deferred at admission").inc(
                        reason="prompt_length")
                raise
        if (self.engine.paged
                and self.engine.pages_for(len(prompt)) > self.engine.num_pages):
            _obs.counter("gen_admission_rejects_total",
                         "requests rejected or deferred at admission").inc(
                             reason="prompt_pages")
            raise ValueError(
                f"prompt needs {self.engine.pages_for(len(prompt))} pages; "
                f"the whole pool holds {self.engine.num_pages}")
        if deadline_s is None and self.default_deadline_s > 0:
            deadline_s = self.default_deadline_s
        req = GenRequest(next(self._ids), prompt, max_new_tokens,
                         deadline_s=deadline_s, clock=self._clock)
        if self.tracer is not None:
            req.trace_id = str(trace_id) if trace_id is not None \
                else f"b{req.id}"
        now = req.submit_t
        if self.draining:
            # a draining replica takes nothing new — the router routes
            # around it; a direct client gets an explicit shed
            return self._shed(req, now, cause="draining")
        # -- overload control (docs/RESILIENCE.md "Serving resilience") ------
        if self.engine.paged and self.shed_page_floor > 0:
            # the watermark charges only what this request would actually
            # allocate: a cached prefix (pages_needed < pages_for) credits
            # the free-page balance, so a fully cached prompt never sheds
            # on page pressure it does not create
            cached = (self.engine.pages_for(len(prompt))
                      - self.engine.pages_needed(prompt))
            if (self.engine.free_pages + cached < self.shed_page_floor
                    and (self._queue or self.active
                         == self.engine.batch_size)):
                return self._shed(req, now, cause="page_floor")
        if self.max_queue > 0 and len(self._queue) >= self.max_queue:
            victim = None
            if self.queue_policy == "shed":
                victim = next((r for r in self._queue if r.expired(now)),
                              None)
            if victim is None:
                return self._shed(req, now, cause="queue_full")
            self._queue.remove(victim)
            self._shed(victim, now, cause="queue_full")
        self._queue.append(req)
        if samples > 1:
            req.samples = [req]
            for _ in range(samples - 1):
                sib = GenRequest(next(self._ids), prompt, max_new_tokens,
                                 deadline_s=deadline_s, clock=self._clock)
                sib._fork_of = req
                if self.tracer is not None:
                    sib.trace_id = f"b{sib.id}"
                req.samples.append(sib)
                if self.max_queue > 0 and len(self._queue) >= self.max_queue:
                    self._shed(sib, sib.submit_t, cause="queue_full")
                    continue
                self._queue.append(sib)
        self._gauges()
        return req

    def cancel(self, req_or_id) -> bool:
        """Mark a request for cancellation by handle or id. The next step
        boundary reclaims its slot and pages (finish reason
        ``"cancelled"``). Returns False for unknown/finished requests."""
        if isinstance(req_or_id, GenRequest):
            req = req_or_id if not req_or_id.done else None
        else:
            req = next((r for r in list(self._queue) + self._slots
                        if r is not None and r.id == req_or_id
                        and not r.done), None)
        if req is None:
            return False
        req.cancel()
        return True

    # -- fleet-tier drain hooks (mxnet_tpu.serving) --------------------------
    def begin_drain(self) -> None:
        """Enter drain mode: every later ``submit`` is shed
        (``cause="draining"``) and admission stops — queued work is meant
        to be pulled back with :meth:`withdraw_queued`, in-flight rows
        finish or expire normally. Idempotent; there is no un-drain (a
        drained replica gets replaced, not resurrected)."""
        self.draining = True

    def withdraw(self, req_or_id) -> bool:
        """Pull one *queued* request back for re-routing — it finishes
        immediately with reason ``"redistributed"`` (not ``"cancelled"``:
        the work is not abandoned, it re-runs elsewhere). Immediate, not
        boundary-deferred: a wedged replica never reaches another step
        boundary, and a queued request holds no slot or pages, so there
        is nothing to reclaim. Active rows cannot be withdrawn (their
        cache row lives here); returns False for those and for
        unknown/finished requests."""
        now = self._clock()
        if isinstance(req_or_id, GenRequest):
            req = req_or_id
        else:
            req = next((r for r in self._queue if r.id == req_or_id), None)
        if req is None or req.done or req not in self._queue:
            return False
        self._queue.remove(req)
        self._finish_queued(req, now, "redistributed")
        self._gauges()
        return True

    def withdraw_queued(self) -> List[GenRequest]:
        """Pull back EVERY queued request (drain entry): each finishes
        with reason ``"redistributed"``; the handles are returned so the
        router can re-enqueue the work."""
        out = list(self._queue)
        self._queue.clear()
        now = self._clock()
        for req in out:
            self._finish_queued(req, now, "redistributed")
        self._gauges()
        return out

    def abandon(self) -> List[GenRequest]:
        """Declare this batcher lost (replica DEAD): every live request —
        queued and in-flight — finishes with reason ``"redistributed"``.
        Bookkeeping only: no engine dispatch and no allocator mutation
        happens (the replica may be wedged inside one); the engine and
        its page pool are discarded with the replica."""
        now = self._clock()
        out = self.withdraw_queued()
        for slot, req in enumerate(self._slots):
            if req is None:
                continue
            self._slots[slot] = None
            req.finish_reason = "redistributed"
            req.finish_t = now
            tr = self.tracer
            if tr is not None and req.trace_id is not None:
                tr.span(req.trace_id, "decode",
                        req.first_token_t if req.first_token_t is not None
                        else now, now, rounds=req.rounds, slot=slot,
                        outcome="redistributed", req=req.id)
                tr.finish(req.trace_id, "redistributed", req.submit_t,
                          now, deadline=req.deadline_t, req=req.id)
            _obs.counter("gen_requests_total",
                         "completed generation requests").inc(
                             reason="redistributed")
            out.append(req)
        self._gauges()
        return out

    # -- queue telemetry the replica publishes (docs/INFERENCE.md) -----------
    def queue_ages(self, now: Optional[float] = None) -> List[float]:
        if now is None:
            now = self._clock()
        return [max(0.0, now - r.submit_t) for r in self._queue]

    def queue_age_p95(self, now: Optional[float] = None) -> float:
        """p95 age of the *currently queued* requests (0.0 when empty) —
        the live backlog-pressure signal the fleet router balances on,
        distinct from the ``gen_queue_age_seconds`` histogram which only
        records ages at queue *exit*."""
        ages = sorted(self.queue_ages(now))
        if not ages:
            return 0.0
        return ages[max(0, -(-len(ages) * 95 // 100) - 1)]

    @property
    def pending(self) -> int:
        return len(self._queue)

    @property
    def active(self) -> int:
        return sum(r is not None for r in self._slots)

    @property
    def watchdog(self) -> _serving.DispatchWatchdog:
        return self._watchdog

    # -- serving loop --------------------------------------------------------
    def _gauges(self):
        _obs.gauge("gen_queue_depth",
                   "requests waiting for a decode slot").set(len(self._queue))
        _obs.gauge("gen_active_slots", "decode rows in flight").set(self.active)

    def _queue_age(self, req: GenRequest, now: float, outcome: str):
        _obs.histogram("gen_queue_age_seconds",
                       "time spent in the admission queue, by outcome",
                       unit="s").observe(max(0.0, now - req.submit_t),
                                         outcome=outcome)

    def _victims(self) -> dict:
        """slot -> request id for every in-flight row — the watchdog
        attaches it to a stall event so a wedge names its victims.
        Computed only when the watchdog is armed."""
        return {str(s): r.id for s, r in enumerate(self._slots)
                if r is not None}

    def _trace_queue_exit(self, req: GenRequest, now: float, outcome: str,
                          terminal: bool, **attrs) -> None:
        """Span the request's admission-queue residency; when the wait
        ended the request (shed/expired/withdrawn), close the local
        trace too — the tail sampler decides whether the spans flush."""
        tr = self.tracer
        if tr is None or req.trace_id is None:
            return
        tr.span(req.trace_id, "replica.queue", req.submit_t, now,
                outcome=outcome, req=req.id, **attrs)
        if terminal:
            tr.finish(req.trace_id, outcome, req.submit_t, now,
                      deadline=req.deadline_t, req=req.id)

    def _shed(self, req: GenRequest, now: float, cause: str) -> GenRequest:
        req.finish_reason = "shed"
        req.finish_t = now
        _obs.counter("gen_requests_total",
                     "completed generation requests").inc(reason="shed")
        _obs.counter("gen_shed_total",
                     "requests shed by overload control").inc(cause=cause)
        self._queue_age(req, now, "shed")
        self._trace_queue_exit(req, now, "shed", terminal=True, cause=cause)
        return req

    def _finish_queued(self, req: GenRequest, now: float, reason: str):
        """Terminate a request that never reached a slot (deadline expiry
        or cancellation while queued)."""
        req.finish_reason = reason
        req.finish_t = now
        _obs.counter("gen_requests_total",
                     "completed generation requests").inc(reason=reason)
        if reason == "deadline":
            _obs.counter("gen_deadline_expired_total",
                         "requests expired by their deadline").inc(
                             where="queue")
        self._queue_age(req, now, reason)
        self._trace_queue_exit(req, now, reason, terminal=True)

    def _finish(self, slot: int, reason: str):
        self._finishes += 1
        req = self._slots[slot]
        self._slots[slot] = None
        if (reason in ("eos", "length", "cache_full")
                and getattr(self.engine, "prefix_cache", None) is not None):
            # index the clean finish's full pages before release: a
            # multi-turn follow-up (prompt + output + next user turn)
            # then resumes by refcount bump instead of re-prefill
            self.engine.cache_sequence(slot, list(req.prompt)
                                       + [int(t) for t in req.output])
        self.engine.release_slot(slot)
        req.finish_reason = reason
        req.finish_t = self._clock()
        tr = self.tracer
        if tr is not None and req.trace_id is not None:
            tr.span(req.trace_id, "decode",
                    req.first_token_t if req.first_token_t is not None
                    else req.finish_t,
                    req.finish_t, rounds=req.rounds, slot=slot,
                    outcome=reason, req=req.id)
            tr.finish(req.trace_id, reason, req.submit_t, req.finish_t,
                      deadline=req.deadline_t, req=req.id)
        _obs.counter("gen_requests_total", "completed generation requests").inc(
            reason=reason)
        if reason == "deadline":
            _obs.counter("gen_deadline_expired_total",
                         "requests expired by their deadline").inc(
                             where="slot")
        gen = len(req.output) - 1  # tokens after the TTFT token
        span = req.finish_t - (req.first_token_t or req.submit_t)
        if gen > 0 and span > 0:
            _obs.histogram("decode_tokens_per_s",
                           "per-request generation rate after first token",
                           unit="tokens/s").observe(gen / span)

    def _sweep(self, now: float):
        """Step-boundary housekeeping: apply cancellations and deadline
        expiry to queued requests and active slots. Slot reclaim goes
        through ``release_slot`` — pages free immediately and the device
        page-table row is cleared before the next dispatch writes
        anything, so surviving rows can never be corrupted."""
        if self._queue:
            keep: deque = deque()
            for req in self._queue:
                if req.cancel_requested:
                    self._finish_queued(req, now, "cancelled")
                elif req.expired(now):
                    self._finish_queued(req, now, "deadline")
                else:
                    keep.append(req)
            self._queue = keep
        for slot, req in enumerate(self._slots):
            if req is None:
                continue
            if req.cancel_requested:
                self._finish(slot, "cancelled")
            elif req.expired(now):
                self._finish(slot, "deadline")

    def _admit_into(self, slot: int, req: GenRequest, now: float):
        """One bucketed batch-1 prefill under the retry policy + watchdog
        (fault site ``gen.prefill`` fires inside the engine, before any
        allocator mutation)."""
        self._admissions += 1
        req.slot = slot
        self._slots[slot] = req
        req.admit_t = now
        self._queue_age(req, now, "admitted")
        self._trace_queue_exit(req, now, "admitted", terminal=False,
                               slot=slot)
        _obs.histogram("ttft_queue_seconds",
                       "submit -> admission: the queue-wait half of ttft",
                       unit="s").observe(max(0.0, now - req.submit_t))

        def _dispatch():
            # the watchdog arms per ATTEMPT (inside the retried closure):
            # retry backoff sleeps must never read as a stuck dispatch
            with self._watchdog.guard("prefill", self._step_id,
                                      victims={str(slot): req.id}
                                      if self._watchdog.enabled else None):
                return self.engine.prefill(req.prompt, slot)

        tok = _retry.retry_call(_dispatch, site="gen.prefill",
                                policy=self._retry_policy)
        # the engine's own record of the prefill that served it, the newest
        # of the loop ``prefill``: fault site to the prefix cache's insert
        svc = 1e-9 * _obs.step_records("prefill")[-1].duration_ns
        req.first_token_t = self._clock()
        _obs.histogram("ttft_seconds", "submit -> first sampled token",
                       unit="s").observe(req.first_token_t - req.submit_t)
        _obs.histogram("ttft_service_seconds",
                       "admission -> first sampled token: the service "
                       "half of ttft, on the real wall clock",
                       unit="s").observe(svc)
        tr = self.tracer
        if tr is not None and req.trace_id is not None:
            tr.span(req.trace_id, "prefill", req.admit_t,
                    req.first_token_t, service_s=round(svc, 6), slot=slot,
                    req=req.id)
        req.output.append(tok)
        if (req.samples is not None and self.engine.paged
                and not self.engine.done[slot]):
            # fork before the leader can finish: siblings need its pages
            self._admit_forks(req, now)
        if self.engine.done[slot]:  # first token was EOS
            self._finish(slot, "eos")
        elif req.max_new_tokens == 1:
            self._finish(slot, "length")

    def _admit_forks(self, leader: GenRequest, now: float):
        """Admit the leader's still-queued siblings into free slots by
        copy-on-write fork — a refcount bump plus one resample from the
        leader's stored prefill logits, no prefill and no new pages.
        Siblings that do not fit now stay queued; they fork on a later
        boundary while the leader lives, or fall back to prefill."""
        eng = self.engine
        for sib in [r for r in self._queue if r._fork_of is leader]:
            if eng.done[leader.slot]:
                break  # leader finished mid-loop (sampled EOS on fork)
            slot = next((s for s in range(eng.batch_size)
                         if self._slots[s] is None), None)
            if slot is None:
                break
            self._queue.remove(sib)
            self._admissions += 1
            sib.slot = slot
            sib.forked = True
            self._slots[slot] = sib
            sib.admit_t = now
            self._queue_age(sib, now, "admitted")
            self._trace_queue_exit(sib, now, "admitted", terminal=False,
                                   slot=slot, forked=True)
            svc0 = time.perf_counter()
            tok = eng.fork_slot(leader.slot, slot, resample_first=True)
            svc = time.perf_counter() - svc0
            sib.first_token_t = self._clock()
            _obs.histogram("ttft_queue_seconds",
                           "submit -> admission: the queue-wait half of "
                           "ttft", unit="s").observe(
                               max(0.0, now - sib.submit_t))
            _obs.histogram("ttft_seconds", "submit -> first sampled token",
                           unit="s").observe(
                               sib.first_token_t - sib.submit_t)
            _obs.histogram("ttft_service_seconds",
                           "admission -> first sampled token: the service "
                           "half of ttft, on the real wall clock",
                           unit="s").observe(svc)
            tr = self.tracer
            if tr is not None and sib.trace_id is not None:
                tr.span(sib.trace_id, "fork", sib.admit_t,
                        sib.first_token_t, service_s=round(svc, 6),
                        slot=slot, src=leader.slot, req=sib.id)
            sib.output.append(tok)
            if eng.done[slot]:  # resampled first token was EOS
                self._finish(slot, "eos")
            elif sib.max_new_tokens == 1:
                self._finish(slot, "length")

    def _admit(self, now: float):
        """Step-boundary admission: fill free slots FIFO. On a paged
        engine the head is only admitted when the pool covers its prompt;
        while it is parked, smaller later requests may bypass it — until
        the aging guard reserves freed pages for the head (see module
        docstring)."""
        if self.draining:
            return  # drain mode: in-flight only, nothing new starts
        eng = self.engine
        if eng.paged and getattr(eng, "prefix_cache", None) is not None:
            # a head admitted past the bucket check on the strength of a
            # cached prefix can lose that prefix to eviction while
            # queued; shed it now rather than let prefill raise
            while self._queue and not eng.can_admit(self._queue[0].prompt):
                self._shed(self._queue.popleft(), now,
                           cause="prefix_evicted")
        deferral_counted = False
        for slot in range(eng.batch_size):
            if not self._queue:
                break
            if self._slots[slot] is not None:
                continue
            head = self._queue[0]
            if not eng.paged:
                self._admit_into(slot, self._queue.popleft(), now)
                continue
            # charge only the pages the prefill will actually allocate: a
            # cached prefix is adopted by refcount bump, so its pages are
            # free as far as admission is concerned; eviction headroom
            # (available_pages >= free_pages) counts too — prefill evicts
            # cache-only pages itself when the free list runs short. Every
            # pool group has to cover it: the one that runs short decides
            need = eng.pages_needed(head.prompt)
            if eng.covers(head.prompt):
                eng.reserve_pages(0)
                self._head_id = None
                self._head_deferrals = 0
                self._admit_into(slot, self._queue.popleft(), now)
                continue
            # head parked on pages: ONE deferral per boundary, however
            # many free slots re-evaluate it
            if not deferral_counted:
                deferral_counted = True
                _obs.counter("gen_admission_rejects_total",
                             "requests rejected or deferred at admission").inc(
                                 reason="free_pages")
                if head.id != self._head_id:
                    self._head_id = head.id
                    self._head_deferrals = 0
                self._head_deferrals += 1
            if (self.head_aging_steps > 0
                    and self._head_deferrals > self.head_aging_steps):
                # aging guard: stop bypass and hold freed pages for the
                # head — decode-time growth can no longer consume them
                eng.reserve_pages(need)
                break
            # bypass: the first later request the unreserved pool covers
            # (the head keeps its queue position)
            cand = next((i for i in range(1, len(self._queue))
                         if eng.covers(self._queue[i].prompt,
                                       unreserved=True)), None)
            if cand is None:
                break
            req = self._queue[cand]
            del self._queue[cand]
            _obs.counter("gen_admission_bypass_total",
                         "small requests admitted past a page-parked "
                         "queue head").inc()
            self._admit_into(slot, req, now)
        if not self._queue:
            self._head_id = None
            self._head_deferrals = 0
            if eng.paged and eng.reserved_pages:
                eng.reserve_pages(0)

    def _done_reason(self, slot: int, last_token) -> str:
        """Why the engine marked this row done: a sampled EOS, a forced
        cache-end finish, or (paged) a page-pool eviction."""
        if (self.engine.paged
                and bool(self.engine.page_exhausted[slot])):
            return "page_exhausted"
        if (self.engine.eos_id is not None
                and last_token == self.engine.eos_id):
            return "eos"
        if self.engine.positions[slot] >= self.engine.max_length:
            return "cache_full"
        return "eos"

    def step(self) -> bool:
        """Sweep deadlines/cancellations, admit, then run one compiled
        decode step (or one speculative draft+verify round, or — in
        governor fallback — one plain step on the speculative engine).
        Returns True while any work (active rows or queued requests)
        remains.

        Every call leaves a record in ``obs.step_records("serve_step")``
        (root span ``mx.gen.step``; telemetry on or off; host clock marks
        and host integers, nothing of the device): the phases
        ``mx.gen.step.sweep``, ``.admit`` (the prefills it runs leave
        ``prefill`` records of their own inside it), ``.books``, ``.decode``
        (the engine's ``decode_step`` record nests inside) and ``.tokens``,
        and the counts ``active`` and ``queued`` as the step found them,
        ``admitted``, ``finished`` and ``steady``. A step that finds no
        active row closes its record with the phases it ran."""
        now = self._clock()
        self._step_id += 1
        with _obs.step_record("serve_step", self._step_id,
                              name="mx.gen.step") as rec:
            rec.counts = tally = {
                "active": self.active, "queued": len(self._queue),
                "admitted": 0, "finished": 0, "steady": 0}
            admissions, finishes = self._admissions, self._finishes
            try:
                return self._step_phases(now, tally)
            finally:
                tally["admitted"] = self._admissions - admissions
                tally["finished"] = self._finishes - finishes

    def _step_phases(self, now: float, tally: dict) -> bool:
        """What :meth:`step` does, statement for statement in the order it
        always had, each phase under its host span (a phase that the
        branches below split has its span in each)."""
        with _obs.span("mx.gen.step.sweep"):
            self._sweep(now)
        with _obs.span("mx.gen.step.admit"):
            self._admit(now)
        with _obs.span("mx.gen.step.books"):
            self._gauges()
            if self.active == 0:
                return bool(self._queue)
            was_active = [s for s, r in enumerate(self._slots)
                          if r is not None]
            speculative = getattr(self.engine, "speculative", False)
            use_spec = speculative and (self.governor is None
                                        or self.governor.speculating)
            tr = self.tracer
        if use_spec:
            r0 = self._clock() if tr is not None else now

            def _round():
                with self._watchdog.guard("spec_round", self._step_id,
                                          victims=self._victims()
                                          if self._watchdog.enabled
                                          else None):
                    return self.engine.spec_step()

            with _obs.span("mx.gen.step.decode"):
                toks, counts, done = _retry.retry_call(
                    _round, site="gen.decode", policy=self._retry_policy)
            with _obs.span("mx.gen.step.tokens"):
                r1 = self._clock() if tr is not None else now
                if (self.governor is not None
                        and self.engine.last_round_drafted):
                    self.governor.observe_round(
                        self.engine.last_round_accepted,
                        self.engine.last_round_drafted)
                for slot in was_active:
                    req = self._slots[slot]
                    req.rounds += 1
                    n = int(counts[slot])
                    appended = 0
                    for j in range(n):
                        req.output.append(int(toks[slot, j]))
                        appended += 1
                        if len(req.output) >= req.max_new_tokens:
                            break
                    if tr is not None and req.trace_id is not None:
                        tr.span(req.trace_id, "decode.round", r0, r1,
                                step=self._step_id, mode="spec", slot=slot,
                                accepted=int(
                                    self.engine.last_round_accepted),
                                drafted=int(self.engine.last_round_drafted),
                                tokens=appended)
                    if appended < n:  # budget hit inside the window
                        self._finish(slot, "length")
                    elif done[slot]:
                        self._finish(slot, self._done_reason(
                            slot, req.output[-1] if req.output else None))
                    elif len(req.output) >= req.max_new_tokens:
                        self._finish(slot, "length")
        else:
            with _obs.span("mx.gen.step.books"):
                if speculative:
                    step_fn = self.engine.plain_step
                else:
                    # every slot holds a request with two or more tokens to
                    # go: none ends on this step and none can be admitted,
                    # so the rows stay as they are until the next step (a
                    # cancellation or a deadline aside) and the engine may
                    # dispatch it ahead
                    steady = all(r is not None
                                 and r.max_new_tokens - len(r.output) >= 2
                                 for r in self._slots)
                    tally["steady"] = int(steady)
                    step_fn = (functools.partial(self.engine.decode_step,
                                                 ahead=True)
                               if steady else self.engine.decode_step)

                def _step():
                    with self._watchdog.guard("decode", self._step_id,
                                              victims=self._victims()
                                              if self._watchdog.enabled
                                              else None):
                        return step_fn()

                r0 = self._clock() if tr is not None else now
            with _obs.span("mx.gen.step.decode"):
                tok, done, _ = _retry.retry_call(
                    _step, site="gen.decode", policy=self._retry_policy)
            with _obs.span("mx.gen.step.tokens"):
                r1 = self._clock() if tr is not None else now
                if self.governor is not None:
                    self.governor.observe_plain_step()
                for slot in was_active:
                    req = self._slots[slot]
                    req.rounds += 1
                    if tr is not None and req.trace_id is not None:
                        tr.span(req.trace_id, "decode.round", r0, r1,
                                step=self._step_id,
                                mode="plain" if speculative else "decode",
                                slot=slot, tokens=1)
                    if (self.engine.paged and done[slot]
                            and bool(self.engine.page_exhausted[slot])):
                        # evicted BEFORE the dispatch: the row emitted pad
                        # this step, not a token — finish without appending
                        # it
                        self._finish(slot, "page_exhausted")
                        continue
                    req.output.append(int(tok[slot]))
                    if done[slot]:
                        self._finish(slot,
                                     self._done_reason(slot, req.output[-1]))
                    elif len(req.output) >= req.max_new_tokens:
                        self._finish(slot, "length")
        with _obs.span("mx.gen.step.tokens"):
            self._gauges()
        return bool(self._queue) or self.active > 0

    def run_until_idle(self, max_steps: Optional[int] = None) -> None:
        """Drive steps until queue and slots are empty (or ``max_steps``)."""
        steps = 0
        while self.step():
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
