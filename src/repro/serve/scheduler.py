"""Continuous-batching admission frontend over an :class:`EnsembleServer`.

Online traffic arrives one :class:`EnsembleRequest` at a time; ``submit()``
enqueues the request and returns a :class:`ResponseFuture` immediately.
Beyond the micro-batch coalescing of the original FIFO scheduler, this
frontend is deadline- and budget-aware:

* **EDF batch formation** — pending requests order by
  ``(absolute deadline, -priority, arrival)``; batches are formed from
  requests sharing a *policy group* (the engine's ``_policy_key``), so
  every dispatched micro-batch runs one vectorized ``select``.  Batch
  sizes snap to the :class:`~repro.serve.dispatch.BucketLadder`'s rungs —
  dispatching exactly a rung's worth means the fast path pads by zero
  rows and hits a bucket that is already compiled.
* **Dispatch triggers** — a policy group reaching ``max_batch_size``
  dispatches inline from ``submit``; ``tick()`` (the caller's logical
  clock) dispatches any request that has aged past ``max_wait_ticks`` or
  whose deadline is due; ``flush()`` drains everything;
  ``ResponseFuture.result()`` dispatches *only the batches up to and
  including the one containing that future* — it never force-flushes
  other submitters' young requests.
* **Async dispatch** — ``sync=False`` moves batch *service* (the engine
  call and its hedged retries) onto a
  :class:`~repro.serve.cluster.DispatchWorker` thread with a bounded
  inbox: batch formation stays on the caller's thread, so ``submit``
  returns as soon as the batch is enqueued and never blocks on a batch.
  The worker executes batches FIFO and every event carries the logical
  tick its batch was *dispatched* at, so the event trace is byte-
  identical to the ``sync=True`` path (pinned per preset scenario by
  ``tests/test_serve_cluster.py``).  Errors surface at ``result()``
  instead of propagating from ``submit``/``tick``.
* **Admission control** — the paper's per-query ε-constraint lifted to a
  rolling per-window fleet budget: realized cost (from
  ``EnsembleResponse.realized_cost``) over the last ``window_ticks`` is
  compared to the full-ensemble cost of the same window; past the soft
  threshold new requests are *downgraded* to a tighter per-request
  budget, past the hard threshold they are *shed* (their future raises
  :class:`RequestShed` — resolved, never hung).  With
  ``deadline_aware=True`` a request whose predicted queue delay (EWMA of
  recent inter-dispatch gaps × batches ahead of it) already exceeds its
  ``deadline_ticks`` is shed at admission — reason ``deadline`` — instead
  of being served late.  In async mode a full worker inbox sheds with
  reason ``backpressure`` — checked before anything waits, at admission
  and again at dispatch time — while the threshold decisions read
  realized-cost feedback and so synchronize with in-flight batches
  first (the documented feedback sync point — an admission-free
  scheduler never blocks, except on the bounded inbox itself).
* **Hedged retry** — when a :class:`~repro.serve.backends.MemberFailure`
  escapes the engine mid-batch, the batch is re-served with the failed
  member excluded (``serve_requests(..., exclude_members=...)``) instead
  of failing every sibling future.  A whole-host death
  (:class:`~repro.serve.backends.HostFailure`, raised by the cluster
  router when a host takes its last replicas down) escalates the same
  way, but re-serves with the dead members *masked out of the knapsack*
  (``masked_members=``): budget-aware policies re-solve over the
  survivors' costs.  Generation is deterministic and side-effect-free
  per call, so retries are exact, and requests that never selected the
  failed members get byte-identical responses.

Because the engine's request path is deterministic per request (see
``SimBackend``) and batch-position-invariant, a stream served through
this scheduler — under any batching, deadlines, hedging, or dispatch
mode — produces byte-identical fused responses to one offline
``EnsembleServer.serve`` call over the same records
(``tests/test_traffic_scenarios.py``, ``tests/test_serve_cluster.py``).

``events`` records every arrival / dispatch / completion / shed / hedge /
deadline-miss as a flat dict — the replayable trace the traffic
simulator (:mod:`repro.serve.traffic`) builds its reports from.
"""

from __future__ import annotations

import dataclasses
import hashlib
import itertools
import threading
import time
from typing import Dict, Iterator, List, Optional, Tuple

from repro.data.tokenizer import TOKENIZER
from repro.serve import spans
from repro.serve.api import EnsembleRequest, EnsembleResponse, StreamEvent
from repro.serve.backends import HostFailure, MemberFailure
from repro.serve.cluster.worker import DispatchWorker, InboxFull
from repro.serve.dispatch import BucketLadder
from repro.serve.engine import EnsembleServer

_NO_DEADLINE = float("inf")


class RequestShed(RuntimeError):
    """Raised by ``ResponseFuture.result()`` when admission control shed
    the request (fleet budget, hopeless deadline, or backpressure)."""


def _digest(text: str) -> str:
    return hashlib.blake2b(text.encode("utf-8", errors="replace"),
                           digest_size=8).hexdigest()


class ResponseFuture:
    """Handle for a submitted request; resolves when its batch is served."""

    def __init__(self, scheduler: "Scheduler", seq: int):
        self._scheduler = scheduler
        self.seq = seq  # arrival sequence number (the trace's request id)
        self._response: Optional[EnsembleResponse] = None
        self._error: Optional[BaseException] = None
        self._done = False
        self._resolved = threading.Event()
        # makes resolve-vs-timeout atomic: _set/_fail hold it, so an
        # expiring wait can re-check before declaring a timeout
        self._resolve_lock = threading.Lock()
        self._stream_cv = threading.Condition()
        self._stream_events: List[StreamEvent] = []
        self.deadline_missed = False  # dispatched after its deadline tick
        self.ttft_s: Optional[float] = None  # wall s, submit to first streamed token
        # submit -> resolution; its record (tracer on) carries the batch
        # that served it, when that service started, and the first token
        self._span = spans.start("serve.request", req=seq)

    def done(self) -> bool:
        return self._done

    def shed(self) -> bool:
        return isinstance(self._error, RequestShed)

    def result(self, timeout: Optional[float] = None) -> EnsembleResponse:
        """The response, dispatching this future's own batch if pending.

        Only batches up to and including the one containing this request
        are dispatched — other policy groups and younger same-group
        requests stay queued for their own triggers.  In async mode the
        call blocks until the worker has served the batch (``timeout``
        in seconds bounds the wait).  Raises the engine's exception if
        the batch failed, or :class:`RequestShed` if admission control
        dropped the request."""
        if not self._done:
            self._scheduler._dispatch_for(self)
            if not self._resolved.wait(timeout):
                # the wait expired — but the batch may have resolved between
                # the expiring wait and this line.  Re-check under the lock
                # _set/_fail hold, so a served request can never surface as
                # a TimeoutError (or spuriously bump result_timeouts / the
                # "timeout" trace event).
                with self._resolve_lock:
                    if not self._done:
                        # the batch stays in flight on the worker — record
                        # the abandoned wait in the trace (a silent
                        # TimeoutError used to leave no evidence) and keep
                        # the future resolvable: a later result() call
                        # returns normally once the batch lands
                        self._scheduler._note_result_timeout(self, timeout)
                        raise TimeoutError(
                            f"request {self.seq} not served within {timeout}s")
        if self._error is not None:
            raise self._error
        assert self._response is not None
        return self._response

    def stream(self, timeout: Optional[float] = None) -> Iterator[StreamEvent]:
        """Iterate this request's :class:`StreamEvent` increments as its
        fusion decodes, ending with a ``final=True`` event that carries the
        settled :class:`EnsembleResponse`.

        Like :meth:`result`, iterating dispatches this future's own batch
        if it is still queued.  Under a streaming scheduler events arrive
        one per decode step of this request's row; under a non-streaming
        scheduler (or the engine's coarse fallback) the iterator degrades
        to a single pass over whatever was buffered plus the final event.
        ``timeout`` bounds each wait for the *next* event; a failed or
        shed request raises from the iterator exactly as ``result()``
        would."""
        self._scheduler._dispatch_for(self)
        i = 0
        while True:
            with self._stream_cv:
                while len(self._stream_events) <= i and not self._done:
                    if not self._stream_cv.wait(timeout):
                        raise TimeoutError(
                            f"request {self.seq}: no stream progress "
                            f"within {timeout}s")
                pending = list(self._stream_events[i:])
                i += len(pending)
                finished = self._done and len(self._stream_events) == i
            yield from pending
            if finished:
                break
        response = self.result(timeout)  # raises the batch error / shed
        with self._stream_cv:
            last = self._stream_events[-1].tokens if self._stream_events else ()
        yield StreamEvent(seq=self.seq, tokens=last, text=response.text,
                          final=True, response=response)

    def _push_stream(self, tokens: List[int]) -> None:
        ev = StreamEvent(
            seq=self.seq, tokens=tuple(tokens),
            text=TOKENIZER.decode_capped(tokens, len(tokens)))
        with self._stream_cv:
            self._stream_events.append(ev)
            self._stream_cv.notify_all()

    def _set(self, response: EnsembleResponse) -> None:
        with self._resolve_lock:
            self._response = response
            self._done = True
            self._span.end()
            self._resolved.set()
        with self._stream_cv:
            self._stream_cv.notify_all()

    def _fail(self, error: BaseException) -> None:
        with self._resolve_lock:
            self._error = error
            self._done = True
            self._span.end()
            self._resolved.set()
        with self._stream_cv:
            self._stream_cv.notify_all()


@dataclasses.dataclass(frozen=True)
class AdmissionControl:
    """Rolling fleet-level ε plus deadline-feasibility admission.

    Over the trailing ``window_ticks`` scheduler ticks, the realized
    member cost of every served request is summed against the
    full-ensemble (LLM-BLENDER) cost of the same requests — the same
    fraction the per-query ε constrains, lifted to the fleet.  When the
    window fraction reaches ``downgrade_fraction``, newly submitted
    requests have their per-request budget tightened to
    ``downgrade_budget``; at ``shed_fraction`` they are shed outright.
    ``None`` disables a threshold.

    ``deadline_aware=True`` additionally sheds requests that cannot make
    their deadline: the scheduler keeps an EWMA (smoothing
    ``service_alpha``) of recent inter-dispatch gaps in ticks — how many
    ticks one batch of service currently costs — and predicts a new
    request's queue delay as that EWMA times the number of batches ahead
    of it.  A request whose ``deadline_ticks`` is below the prediction is
    shed at admission (event reason ``deadline``) rather than served
    past-deadline.  Requests without a deadline are never deadline-shed."""

    window_ticks: int = 8
    downgrade_fraction: Optional[float] = None  # soft: tighten request budgets
    downgrade_budget: float = 0.1  # ε applied to downgraded requests
    shed_fraction: Optional[float] = None  # hard: reject new requests
    deadline_aware: bool = False  # shed requests that cannot make their deadline
    service_alpha: float = 0.5  # EWMA smoothing for inter-dispatch gap ticks

    def needs_feedback(self) -> bool:
        """Whether admission decisions read served-batch feedback (and so
        must synchronize with in-flight batches in async mode)."""
        return (self.downgrade_fraction is not None
                or self.shed_fraction is not None
                or self.deadline_aware)


@dataclasses.dataclass
class _Pending:
    request: EnsembleRequest
    future: ResponseFuture
    key: Tuple  # engine policy-group key
    seq: int
    arrive_tick: int
    deadline_tick: Optional[int]  # absolute (arrival + deadline_ticks)
    priority: int
    age_ticks: int = 0

    def edf_key(self) -> Tuple[float, int, int]:
        d = _NO_DEADLINE if self.deadline_tick is None else self.deadline_tick
        return (d, -self.priority, self.seq)


@dataclasses.dataclass
class _BatchJob:
    """One formed batch, ready for service (inline or on the worker).

    ``dispatch_tick`` freezes the logical clock at formation time: every
    event, deadline-miss decision, and ledger entry the service produces
    is stamped with it, so the trace is identical whether the engine call
    runs inline or finishes on the worker thread several ticks later.
    ``events`` is this batch's pre-reserved slot in the scheduler's event
    log — the worker appends into it without racing later arrivals."""

    batch: List[_Pending]
    dispatch_tick: int
    events: List[dict]
    batch_id: int  # dispatch order; joins a request's span to its batch's


class Scheduler:
    """Deadline-aware continuous-batching front-end over an EnsembleServer."""

    def __init__(self, server: EnsembleServer, max_batch_size: int = 8,
                 max_wait_ticks: int = 4,
                 admission: Optional[AdmissionControl] = None,
                 ladder: Optional[BucketLadder] = None,
                 hedge: bool = True, record_events: bool = True,
                 sync: bool = True, inbox_capacity: int = 64,
                 allow_degraded: bool = False, stream: bool = False,
                 stream_capacity: int = 8,
                 prefill_chunk: Optional[int] = None):
        if max_batch_size < 1:
            raise ValueError("max_batch_size must be >= 1")
        self.server = server
        self.max_batch_size = max_batch_size
        self.max_wait_ticks = max_wait_ticks
        self.admission = admission
        self.ladder = ladder or getattr(server, "bucket_ladder", None) or BucketLadder()
        self.hedge = hedge
        # serve partial-ensemble responses (knapsack re-solved over the
        # survivors, tagged degraded=True, settled against the survivors'
        # full cost) even when ``hedge`` is off; a total outage — every
        # member unavailable — still raises
        self.allow_degraded = allow_degraded
        self.record_events = record_events
        self.sync = sync
        # token-level continuous batching: batches fuse through the
        # engine's persistent stream fuser, pushing per-step token events
        # into each row's ResponseFuture (see enable_streaming)
        self.stream = stream
        self.stream_capacity = stream_capacity
        self.prefill_chunk = prefill_chunk
        self.now = 0
        self._seq = 0
        self.last_submitted: Optional[ResponseFuture] = None
        self._queue: List[_Pending] = []
        # (tick, realized_flops, full_ensemble_flops) per served request —
        # the admission window's ledger
        self._ledger: List[Tuple[int, float, float]] = []
        # event log: flat dicts for submit-side events, one nested list per
        # dispatched batch (the batch's slot, reserved in dispatch order and
        # filled by whichever thread serves it) — see the `events` property
        self._events: List = []
        self._lock = threading.Lock()
        self._service_ewma: Optional[float] = None  # inter-dispatch gap ticks
        self._batch_ids = itertools.count()
        self._last_dispatch_tick: Optional[int] = None
        self._worker: Optional[DispatchWorker] = None
        if not sync:
            self._worker = DispatchWorker(self._serve_batch,
                                          capacity=inbox_capacity,
                                          on_orphan=self._orphan_batch)
        self.stats = {
            "submitted": 0, "dispatched_batches": 0, "dispatched_requests": 0,
            "shed": 0, "downgraded": 0, "deadline_misses": 0,
            "hedges": 0, "host_hedges": 0, "hedged_requests": 0,
            "padded_rows": 0, "result_timeouts": 0, "degraded_responses": 0,
            "stream_tokens": 0,
        }

    def enable_streaming(self, capacity: Optional[int] = None,
                         prefill_chunk: Optional[int] = None) -> None:
        """Flip this scheduler onto the token-level continuous-batching
        fusion path (``--stream`` / the ``streaming`` traffic preset).
        Final responses — and the whole event trace — are byte-identical
        to the batch-boundary path; only the decode mechanics and the
        incremental :class:`StreamEvent` feed change."""
        self.stream = True
        if capacity is not None:
            self.stream_capacity = capacity
        if prefill_chunk is not None:
            self.prefill_chunk = prefill_chunk

    # ------------------------------------------------------------------
    @property
    def events(self) -> List[dict]:
        """The flat event trace: batch slots flatten in dispatch order, so
        the sequence is deterministic regardless of dispatch mode."""
        out: List[dict] = []
        for e in self._events:
            if isinstance(e, list):
                out.extend(e)
            else:
                out.append(e)
        return out

    def _event(self, event: str, **fields) -> None:
        if self.record_events:
            self._events.append({"tick": self.now, "event": event, **fields})

    def _event_to(self, target: List[dict], tick: int, event: str,
                  **fields) -> None:
        if self.record_events:
            target.append({"tick": tick, "event": event, **fields})

    # -- admission window ----------------------------------------------
    def _window_ticks(self) -> int:
        return self.admission.window_ticks if self.admission else self.max_wait_ticks

    def window_cost_fraction(self) -> float:
        """Realized/full-ensemble cost over the trailing admission window."""
        floor = self.now - self._window_ticks()
        with self._lock:
            ledger = list(self._ledger)
        realized = full = 0.0
        for tick, r, f in ledger:
            if tick > floor:
                realized += r
                full += f
        return realized / full if full > 0 else 0.0

    def predicted_queue_delay(self) -> float:
        """Predicted ticks a request submitted now waits before dispatch:
        the inter-dispatch-gap EWMA times the batches queued ahead of it.
        0 until the first gap is observed (an idle scheduler admits)."""
        with self._lock:
            ewma = self._service_ewma
        if ewma is None:
            return 0.0
        batches_ahead = len(self._queue) // self.max_batch_size + 1
        return ewma * batches_ahead

    def _note_result_timeout(self, future: ResponseFuture,
                             timeout: Optional[float]) -> None:
        """Trace a ``result(timeout=)`` expiring while its batch is still
        in flight.  Not a shed — the batch will land and a later
        ``result()`` resolves — but the abandoned wait must be trace
        evidence, not silence."""
        with self._lock:
            self.stats["result_timeouts"] += 1
        self._event("timeout", req=future.seq, waited_s=timeout)

    def _orphan_batch(self, job: "_BatchJob") -> None:
        """Resolve a batch the dispatch worker accepted but never ran
        (it raced past the closed check): same error a losing
        ``try_submit`` sees, so no accepted future can hang."""
        exc = RuntimeError("worker is closed")
        for p in job.batch:
            p.future._fail(exc)

    def _shed(self, future: ResponseFuture, reason: str, detail: str,
              **fields) -> None:
        self.stats["shed"] += 1
        self._event("shed", req=future.seq, reason=reason, **fields)
        future._fail(RequestShed(detail))

    def _admit(self, request: EnsembleRequest,
               future: ResponseFuture) -> Optional[EnsembleRequest]:
        """Admission decision: the request (possibly downgraded), or None
        if it was shed (the future is then already resolved)."""
        ac = self.admission
        if ac is None:
            return request
        if self._worker is not None and self._worker.full():
            # backpressure first: when the inbox is already full, shedding
            # must not wait on the feedback sync point below (the most
            # loaded moment is exactly when waiting hurts most)
            self._shed(
                future, "backpressure",
                f"dispatch inbox at capacity ({self._worker.capacity})")
            return None
        if self._worker is not None and ac.needs_feedback():
            # feedback sync point: thresholds compare against realized
            # cost and service-gap EWMAs, which in-flight batches are
            # still producing — wait for them so sync and async modes
            # make identical admission decisions
            self._worker.join()
        frac = self.window_cost_fraction()
        if ac.shed_fraction is not None and frac >= ac.shed_fraction:
            self._shed(
                future, "budget",
                f"admission window at {frac:.2f} of full-ensemble cost "
                f"(>= shed threshold {ac.shed_fraction:.2f})",
                window_fraction=frac)
            return None
        if ac.deadline_aware and request.deadline_ticks is not None:
            predicted = self.predicted_queue_delay()
            if predicted > request.deadline_ticks:
                self._shed(
                    future, "deadline",
                    f"predicted queue delay {predicted:.1f} ticks exceeds "
                    f"deadline {request.deadline_ticks}",
                    predicted_delay=predicted,
                    deadline_ticks=request.deadline_ticks)
                return None
        if (ac.downgrade_fraction is not None and frac >= ac.downgrade_fraction
                and (request.budget is None or request.budget > ac.downgrade_budget)):
            self.stats["downgraded"] += 1
            self._event("downgrade", req=future.seq, window_fraction=frac,
                        budget=ac.downgrade_budget)
            return dataclasses.replace(request, budget=ac.downgrade_budget)
        return request

    # ------------------------------------------------------------------
    def submit(self, request: EnsembleRequest) -> ResponseFuture:
        """Enqueue one request; dispatches inline once a policy group fills.

        The request's policy override is fully resolved here (name, kwargs,
        budget), so a malformed request is rejected before it can poison a
        micro-batch shared with other submitters.  In async mode a full
        policy group only *enqueues* its batch — the call never waits for
        the engine."""
        self.last_submitted: Optional[ResponseFuture] = None
        key = self.server._policy_key(request)
        hash(key)  # unhashable policy_kwargs values would break grouping
        self.server._build_policy(key)  # raises on unknown name / bad kwargs
        future = ResponseFuture(self, self._seq)
        # recoverable by the caller even if an inline dispatch below raises
        # (the batch's futures are resolved with the cause, but submit then
        # propagates before returning the handle)
        self.last_submitted = future
        self._seq += 1
        self.stats["submitted"] += 1
        admitted = self._admit(request, future)
        if admitted is None:
            return future  # shed: resolved with RequestShed, never queued
        if admitted is not request:
            key = self.server._policy_key(admitted)  # downgrade moved the group
        deadline = (None if admitted.deadline_ticks is None
                    else self.now + admitted.deadline_ticks)
        self._queue.append(_Pending(
            request=admitted, future=future, key=key, seq=future.seq,
            arrive_tick=self.now, deadline_tick=deadline,
            priority=admitted.priority,
        ))
        self._event("arrive", req=future.seq, key=repr(key),
                    deadline=deadline, priority=admitted.priority)
        while True:
            group = self._largest_group()
            if len(group) < self.max_batch_size:
                break
            self._dispatch_group(group, forced=self.max_batch_size)
        return future

    def tick(self) -> int:
        """Advance the logical clock; dispatch every request that has aged
        past ``max_wait_ticks`` or whose deadline tick is due.  Returns the
        number of requests dispatched this tick.  Cluster maintenance
        (host revival after probation, replica rebalance) runs first, so
        batches formed this tick already route through the healed
        placement."""
        self.now += 1
        self._maintain_cluster()
        for p in self._queue:
            p.age_ticks += 1
        served = 0
        while True:
            urgent = [p for p in self._queue if self._urgent(p)]
            if not urgent:
                break
            head = min(urgent, key=_Pending.edf_key)
            group = self._group(head.key)
            forced = sum(self._urgent(p) for p in group[:self.max_batch_size])
            served += self._dispatch_group(group, forced=max(forced, 1))
        return served

    def _maintain_cluster(self) -> None:
        """Apply due placement maintenance (cluster backends only): host
        revival once a recovery's probation window has elapsed, and
        replica re-placement for members that lost redundancy.  In-flight
        shards are drained first (``join``) so migration never races
        generation.  The pending-check reads only static schedule state —
        deciding from live host health would let an in-flight async batch
        (whose fault is about to flip a host dead) make this tick's
        decision differ from sync mode's — so the drain happens exactly
        on ticks where maintenance *might* apply, and the precise
        decision runs on drained state: maintenance events land in the
        flat trace at identical ticks in both dispatch modes.  Fleets
        with no recovery schedule and no rebalance never pay the
        barrier."""
        backend = self.server.backend
        pending = getattr(backend, "maintenance_pending", None)
        if not callable(pending) or not pending(self.now):
            return
        self.join()  # drain in-flight shards before migrating placement
        for ev in backend.maintain(self.now):
            ev = dict(ev)
            self._event(ev.pop("event"), **ev)

    def flush(self) -> int:
        """Dispatch everything queued, regardless of age, deadline, or rung."""
        served = 0
        while self._queue:
            head = min(self._queue, key=_Pending.edf_key)
            group = self._group(head.key)
            served += self._dispatch_group(
                group, forced=min(len(group), self.max_batch_size))
        return served

    def join(self) -> None:
        """Wait until every dispatched batch has been served.  A no-op in
        sync mode, where dispatch and service are the same step."""
        if self._worker is not None:
            self._worker.join()

    def close(self) -> None:
        """Stop the dispatch worker (async mode).  Queued-but-undispatched
        requests stay queued; in-flight batches finish first."""
        if self._worker is not None:
            self._worker.close()

    @property
    def pending(self) -> int:
        return len(self._queue)

    @property
    def in_flight(self) -> int:
        """Batches dispatched but not yet served (always 0 in sync mode)."""
        return self._worker.depth if self._worker is not None else 0

    # ------------------------------------------------------------------
    def _urgent(self, p: _Pending) -> bool:
        if p.age_ticks >= self.max_wait_ticks:
            return True
        return p.deadline_tick is not None and p.deadline_tick <= self.now

    def _group(self, key: Tuple) -> List[_Pending]:
        """The pending requests of one policy group, in EDF order."""
        return sorted((p for p in self._queue if p.key == key),
                      key=_Pending.edf_key)

    def _largest_group(self) -> List[_Pending]:
        counts: Dict[Tuple, int] = {}
        for p in self._queue:
            counts[p.key] = counts.get(p.key, 0) + 1
        if not counts:
            return []
        key = max(counts, key=lambda k: counts[k])
        return self._group(key)

    def _dispatch_for(self, future: ResponseFuture) -> None:
        """Dispatch batches from this future's policy group — in EDF order,
        so same-group requests ahead of it ride along — until the batch
        containing it has been dispatched.  Other groups are left queued.
        In async mode the batch may still be in flight on return; the
        future's event resolves it (``result()`` waits on it)."""
        while not future.done():
            entry = next((p for p in self._queue if p.future is future), None)
            if entry is None:  # in flight, resolved concurrently, or never queued
                break
            group = self._group(entry.key)
            ahead = group.index(entry) + 1  # everything up to and incl. it
            self._dispatch_group(group, forced=min(ahead, self.max_batch_size))
        if self._worker is None and not future.done():
            # sync mode must resolve before returning; the event-based wait
            # in result() would deadlock on a future nobody will serve
            raise RuntimeError(f"request {future.seq} failed to dispatch")

    # ------------------------------------------------------------------
    def _take_count(self, available: int, forced: int) -> int:
        """How many of a group's EDF-ordered candidates to dispatch.

        Snap down to the largest bucket-ladder rung <= available so the
        fast path pads by zero rows — unless that would strand a request
        that must go now (``forced``), in which case take all forced
        requests and pad up to the enclosing (still pre-compiled) rung.
        Never exceeds the ladder's top rung: a count above it (possible
        when ``max_batch_size`` is configured past the ladder, via either
        the exact-rung early return — ``batch_bucket`` falls back to the
        next power of two beyond the top — or ``forced`` itself) would
        compile a brand-new bucket on every steady-state dispatch.  The
        clamped remainder dispatches as a follow-on batch (see
        ``_dispatch_group``) instead."""
        top = self.ladder.batch[-1]
        available = min(available, self.max_batch_size, top)
        forced = min(forced, available)
        if available == self.ladder.batch_bucket(available):
            return available  # already exactly on a rung
        return max(self.ladder.floor_batch_rung(available), forced, 1)

    def _dispatch_group(self, group: List[_Pending], forced: int) -> int:
        """Pop the front of one policy group into a batch job and hand it
        to service — inline in sync mode, the worker's inbox otherwise.
        Returns requests dispatched."""
        if not group:
            return 0
        take = self._take_count(len(group), forced)
        batch = group[:take]
        members = set(id(p) for p in batch)
        self._queue = [p for p in self._queue if id(p) not in members]
        job = _BatchJob(batch=batch, dispatch_tick=self.now, events=[],
                        batch_id=next(self._batch_ids))
        if self.record_events:
            self._events.append(job.events)  # reserve the trace slot now
        if self._worker is None:
            self._serve_batch(job)
        else:
            try:
                if self.admission is not None:
                    # admission-controlled: never block on a full inbox —
                    # shed the batch with the backpressure reason instead
                    # (closes the admit-time-check / dispatch-time race)
                    self._worker.try_submit(job)
                else:
                    # no admission: the bounded put blocking the producer
                    # is the only brake left
                    self._worker.submit(job)
            except InboxFull:
                shed = RequestShed(
                    f"backpressure: dispatch inbox at capacity "
                    f"({self._worker.capacity})")
                with self._lock:
                    self.stats["shed"] += len(batch)
                for p in batch:
                    self._event_to(job.events, job.dispatch_tick, "shed",
                                   req=p.seq, reason="backpressure")
                    p.future._fail(shed)
            except RuntimeError as exc:
                # worker closed: resolve the popped batch's futures with
                # the cause rather than leaving them pending forever
                for p in batch:
                    p.future._fail(exc)
                raise
        leftover_forced = min(forced, len(group)) - take
        if leftover_forced > 0:
            # forced count exceeded the ladder's top rung: the clamp above
            # kept this batch on a compiled bucket, so the rest of the
            # must-go requests dispatch as follow-on rung-sized batches
            return len(batch) + self._dispatch_group(
                group[take:], forced=leftover_forced)
        return len(batch)

    def _serve(self, reqs: List[EnsembleRequest], batch: List[_Pending],
               exclude: frozenset, masked: frozenset,
               t0: float) -> List[EnsembleResponse]:
        """One engine call for a formed batch — batch-boundary fusion, or
        token-level streaming through the engine's persistent fuser.  The
        streaming path pushes every decode-step emission into the owning
        row's future; member failures (and their hedged retries) happen in
        member generation, *before* fusion starts streaming, so a stream
        never emits tokens for an attempt that is later retried — once
        tokens flow, the member set behind them is final."""
        if self.stream:
            return self.server.serve_requests_stream(
                reqs, on_token=self._stream_push(batch, t0),
                exclude_members=exclude, masked_members=masked,
                capacity=self.stream_capacity,
                prefill_chunk=self.prefill_chunk)
        if exclude or masked:
            return self.server.serve_requests(
                reqs, exclude_members=exclude, masked_members=masked)
        return self.server.serve_requests(reqs)

    def _stream_push(self, batch: List[_Pending], t0: float):
        """Row-indexed ``on_token`` fanning the engine's decode-step
        emissions out to each row's future (plus TTFT capture, counted from
        the request's submit; ``t0`` is when the batch's service began)."""
        def on_token(i: int, tokens: List[int]) -> None:
            fut = batch[i].future
            if fut.ttft_s is None:
                now = time.perf_counter_ns()
                fut.ttft_s = (now - fut._span.start_ns) / 1e9
                fut._span.set(first_token_ns=now)
            fut._push_stream(tokens)
            with self._lock:
                self.stats["stream_tokens"] += 1
        return on_token

    def _serve_batch(self, job: _BatchJob) -> None:
        """Serve one formed batch inside its ``serve.batch`` span, stamping
        each request's span with the batch and the service start.  Runs
        inline (sync) or on the worker thread (async)."""
        with spans.span("serve.batch", batch=job.batch_id,
                        rows=len(job.batch)) as service:
            for p in job.batch:
                p.future._span.set(batch=job.batch_id, service_ns=service.start_ns)
            self._serve_job(job)

    def _serve_job(self, job: _BatchJob) -> None:
        """The engine call plus hedged retries, then settlement.  Every
        tick stamp uses ``job.dispatch_tick``, so both modes write the
        same trace."""
        batch, tick = job.batch, job.dispatch_tick
        exclude: frozenset = frozenset()
        # pre-mask members already known dead (a cluster backend's plan
        # records host deaths), so only the batch in flight at the fault
        # pays a retry — later batches route around the dead host from
        # the start.  The state is SNAPSHOT exactly once per batch, at
        # dispatch time (service entry — inline at dispatch in sync mode;
        # on the FIFO worker in async mode, where every earlier batch has
        # already served, so both modes see the identical view), and the
        # snapshot is an atomic read under the plan's lock: tick-driven
        # revival/rebalance mutating the plan from the caller thread can
        # never tear this batch's masking decisions mid-service.
        dead_hook = getattr(self.server.backend, "dead_members", None)
        masked: frozenset = (frozenset(dead_hook()) if callable(dead_hook)
                             else frozenset())
        reqs = [p.request for p in batch]
        pool_n = self.server.backend.num_members()
        if len(masked) >= pool_n:
            # total outage: every member's placement is dead — fail the
            # batch with a clear cause instead of handing the engine an
            # empty pool to select from
            exc = RuntimeError(
                "no servable pool members: every placement host is dead")
            for p in batch:
                p.future._fail(exc)
            raise exc
        t_serve0 = time.perf_counter()
        while True:
            try:
                responses = self._serve(reqs, batch, exclude, masked, t_serve0)
                break
            except MemberFailure as mf:
                if (not (self.hedge or self.allow_degraded)
                        or len(exclude | masked) + 1 >= pool_n):
                    for p in batch:
                        p.future._fail(mf)
                    raise
                exclude = exclude | {mf.member_idx}
                with self._lock:
                    self.stats["hedges"] += 1
                    self.stats["hedged_requests"] += len(batch)
                self._event_to(job.events, tick, "hedge", member=mf.member_idx,
                               reqs=[p.seq for p in batch],
                               exclude=sorted(exclude))
            except HostFailure as hf:
                dead = frozenset(hf.member_idxs)
                survivors_left = len(exclude | masked | dead) < pool_n
                # `dead <= masked` means no progress: a host that keeps
                # failing without newly killing members would retry forever
                if (not (self.hedge or self.allow_degraded) or not dead
                        or not survivors_left or dead <= masked):
                    for p in batch:
                        p.future._fail(hf)
                    raise
                masked = masked | dead
                with self._lock:
                    self.stats["host_hedges"] += 1
                    self.stats["hedged_requests"] += len(batch)
                self._event_to(job.events, tick, "host_hedge",
                               host=hf.host_id, members=sorted(dead),
                               reqs=[p.seq for p in batch],
                               masked=sorted(masked))
            except Exception as exc:
                # the batch is already popped; resolve every sibling future
                # with the cause instead of leaving them pending forever
                for p in batch:
                    p.future._fail(exc)
                raise
        self._event_to(job.events, tick, "dispatch",
                       reqs=[p.seq for p in batch], size=len(batch),
                       bucket=self.ladder.batch_bucket(len(batch)),
                       exclude=sorted(exclude), masked=sorted(masked))
        n_degraded = sum(1 for r in responses if r.degraded)
        if self.allow_degraded and n_degraded:
            # partial-ensemble settlement: the batch served on survivors,
            # so the rolling ε window charges it against the survivors'
            # full cost (what the re-targeted budget actually constrained)
            # rather than a full-pool cost nothing could have spent
            self._event_to(
                job.events, tick, "degraded",
                reqs=[p.seq for p in batch],
                missing=sorted(set().union(
                    *(r.missing_members for r in responses))),
                realized=float(sum(r.realized_cost for r in responses)),
                survivor_full=float(sum(r.survivor_cost for r in responses)),
                # the batch that actually settled (the survivor retry) —
                # hedged attempts that never served report no padding
                padded=self.ladder.batch_bucket(len(batch)) - len(batch))
        ledger_rows = []
        for p, response in zip(batch, responses):
            missed = (p.deadline_tick is not None and tick > p.deadline_tick)
            if missed:
                p.future.deadline_missed = True
            p.future._set(response)
            # full-ensemble cost backed out of the realized fraction keeps
            # the ledger exact for any policy without a second cost pass;
            # degraded batches settle against the survivors' full cost
            # instead (gated on allow_degraded so legacy ledgers are
            # byte-stable)
            if self.allow_degraded and response.degraded:
                full = response.survivor_cost
            else:
                full = (response.realized_cost / response.cost_fraction
                        if response.cost_fraction > 0 else 0.0)
            ledger_rows.append((tick, response.realized_cost, full))
            if missed:
                self._event_to(job.events, tick, "miss", req=p.seq,
                               deadline=p.deadline_tick)
            self._event_to(job.events, tick, "complete", req=p.seq,
                           latency_ticks=tick - p.arrive_tick,
                           missed=missed, text_digest=_digest(response.text))
        with self._lock:
            self.stats["degraded_responses"] += n_degraded
            self.stats["deadline_misses"] += sum(
                1 for p in batch if p.future.deadline_missed)
            # padding is charged once per *served* dispatch, in this
            # settlement block that runs exactly once per batch — never
            # inside the retry loop, where a hedged re-serve would charge
            # the same rows again (per-attempt padding lives in the
            # engine dispatcher's own stats, where it belongs)
            self.stats["padded_rows"] += (
                self.ladder.batch_bucket(len(batch)) - len(batch))
            self.stats["dispatched_batches"] += 1
            self.stats["dispatched_requests"] += len(batch)
            self._ledger.extend(ledger_rows)
            # entries older than the window can never matter again — prune
            # so the ledger stays O(window), not O(session)
            floor = tick - self._window_ticks()
            self._ledger = [e for e in self._ledger if e[0] > floor]
            # inter-dispatch gap EWMA: the deadline-aware admission's
            # service-time estimate (first dispatch seeds the clock only)
            if self._last_dispatch_tick is not None and self.admission:
                gap = float(tick - self._last_dispatch_tick)
                a = self.admission.service_alpha
                self._service_ewma = (
                    gap if self._service_ewma is None
                    else a * gap + (1.0 - a) * self._service_ewma)
            self._last_dispatch_tick = tick
