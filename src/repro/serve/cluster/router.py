"""Placement-aware routing of member generation across hosts.

:class:`ClusterRouter` is a :class:`~repro.serve.backends.MemberBackend`
wrapper: the engine's per-member generation calls arrive here, the
router resolves the member's *primary* (first alive) replica host from
the :class:`~repro.serve.cluster.placement.PlacementPlan`, installs that
host's mesh rules for the duration of the call, and forwards to the
inner backend.  A live backend keys each member's compiled buckets and
decode caches by that host's mesh, so a member's weights and caches sit
on its host's devices and a program traced under one host's rules never
runs on another; :meth:`warm` compiles them per host.  On a logical-only
plan (no devices) every host shares one set.

Fan-out (``fanout=True``) turns the router from a routing table into a
concurrent executor fabric: one batch's generation calls are *planned*
sequentially on the serving thread (routing, per-host dispatch counts,
and injected-failure consumption advance in exactly the order the
sequential path would produce them), then the per-host shards execute
concurrently on a :class:`~repro.serve.cluster.worker.HostExecutorPool`
— one bounded-queue worker thread per live host.  Because the plan pass
is sequential and each host's executor runs its shard FIFO, fan-out may
change wall-clock but never outputs: traces and responses are
byte-identical to sequential routing (pinned per preset scenario by the
chaos suite).  The one documented asymmetry: a *real* (non-injected)
mid-shard fault aborts only its own shard, so sibling shards may consume
inner-backend call counters the aborting sequential path would not have
reached — injected schedules, which are resolved at planning time, never
hit this.

Failure semantics (the whole-host extension of PR 3's hedged retry):

* an injected or real host fault surfaces as
  :class:`~repro.serve.backends.HostFailure` carrying the host id;
* the router marks the host dead in the plan (and retires its executor).
  Members with a replica on a surviving host **fail over inside the
  router** — the batch re-serves on the surviving placement and the
  caller never sees the fault;
* members left with no surviving replica re-raise the ``HostFailure``
  with ``member_idxs`` filled in, and the Scheduler re-serves the batch
  with those members masked out of the knapsack
  (``EnsembleServer.serve_requests(masked_members=...)``).

Recovery makes death non-final: ``host_recovery`` schedules the logical
tick at which a dead host is healthy again, and tick-driven maintenance
(:meth:`maintain`, called by the Scheduler with in-flight shards
drained) re-admits it once a ``probation_ticks`` window has elapsed —
routing returns to the revived primary, and the Scheduler stops
pre-masking its members.  ``rebalance=True`` additionally re-places
members that lost replica redundancy onto the least-loaded surviving
hosts at the next maintenance pass.

Installing a :class:`~repro.serve.cluster.health.HealthMonitor`
(``health=``) upgrades recovery from scheduled to *observed*: the
maintenance pass runs the monitor's deterministic liveness probes,
whose circuit breakers mark hosts dead on consecutive probe failures
(no dispatch has to explode first) and revive them through half-open
probes with exponential backoff — strictly faster than schedule-driven
revival, which must additionally sit out its probation window.

Grey failures — hosts alive but slow — get two defenses.
``host_stragglers`` + ``hedge_stragglers=True`` is the *deterministic*
one: dispatch indices scheduled as stragglers are re-routed at
consume time to an alive replica (the replica's dispatch counter
advances too), identically in sequential and fan-out routing, so
hedged traces stay byte-identical.  ``shard_deadline_s`` is the
*wall-clock* one (fan-out only): a shard that misses its deadline is
cancelled and its unfinished calls re-served on replica hosts
(earliest completion wins — a late original result is byte-identical
anyway).  Like real mid-shard faults, wall-clock hedges bypass
dispatch counters; injected schedules never hit this path.

Host-level failure *injection* lives here too (``host_failures``): the
schedule is keyed on per-host dispatch counts — the n-th generation call
routed to host *h* raises — so a traffic scenario that kills a host is
exactly replayable, like the member-level
:class:`~repro.serve.backends.FailureInjector`.
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import threading
from typing import Dict, List, Optional, Sequence, Tuple

from repro.serve.backends import (
    GenerationCall,
    HostFailure,
    MaxNewTokens,
    MemberBackend,
    MemberFailure,
)
from repro.serve.cluster.health import HealthMonitor
from repro.serve.cluster.placement import PlacementPlan
from repro.serve.cluster.worker import HostExecutorPool
from repro.sharding.api import axis_rules

# The host a generation call is executing on, visible to the wrapped
# backend (set around every inner.generate).  Host-aware test/bench
# wrappers (e.g. a straggler floor that slows one host's wall clock
# without touching the logical trace) key on this.
_CURRENT_HOST: contextvars.ContextVar[Optional[int]] = contextvars.ContextVar(
    "repro_serve_current_host", default=None)


def current_dispatch_host() -> Optional[int]:
    """The placement host of the generation call running on this thread,
    or None outside a routed call."""
    return _CURRENT_HOST.get()


@dataclasses.dataclass
class _PlannedCall:
    """One generation call after the routing plan pass: the host is
    pinned (execution must not re-resolve it) and the dispatch index is
    already consumed from the host's injection schedule."""

    order: int  # position in the batch's call list (== member order)
    call: GenerationCall
    host: int
    dispatch_idx: int


@dataclasses.dataclass
class ClusterRouter:
    """Routes member generation through a placement plan.

    ``host_failures`` maps a host id to the 0-based *dispatch indices*
    (that host's n-th routed generation call, counted over the router's
    lifetime) that raise :class:`HostFailure` instead of generating.
    ``host_recovery`` maps a host id to the logical ticks at which it
    recovers (consumed in order — a host can die, revive, and die
    again); ``probation_ticks`` delays each re-admission past the
    recovery tick.  ``fanout=True`` executes per-host shards
    concurrently on a :class:`HostExecutorPool`.

    ``health`` installs a :class:`HealthMonitor` whose probes run inside
    the maintenance pass (probe-opened deaths and half-open revivals —
    use it *instead of* ``host_recovery``, whose schedule it replaces).
    ``host_stragglers`` maps a host id to the dispatch indices that are
    grey-slow on it; with ``hedge_stragglers=True`` those dispatches
    re-route to an alive replica at consume time.  ``shard_deadline_s``
    bounds each fan-out shard's wall-clock service; a late shard is
    cancelled and hedged onto replica hosts."""

    inner: MemberBackend
    plan: PlacementPlan
    host_failures: Dict[int, Sequence[int]] = dataclasses.field(
        default_factory=dict)
    fanout: bool = False
    executor_capacity: int = 8
    host_recovery: Dict[int, Sequence[int]] = dataclasses.field(
        default_factory=dict)
    probation_ticks: int = 0
    rebalance: bool = False
    health: Optional[HealthMonitor] = None
    host_stragglers: Dict[int, Sequence[int]] = dataclasses.field(
        default_factory=dict)
    hedge_stragglers: bool = False
    shard_deadline_s: Optional[float] = None
    record_audit: bool = False
    stats: Dict[str, int] = dataclasses.field(default_factory=lambda: {
        "dispatches": 0, "failovers": 0, "host_faults": 0,
        "fanout_batches": 0, "shards": 0, "revivals": 0, "rebalanced": 0,
        "straggler_hedges": 0, "stragglers_unhedged": 0, "shard_hedges": 0,
        "probes": 0, "probe_deaths": 0, "probe_revivals": 0})
    # (host, member, dispatch_idx, host_was_dead) per routed dispatch —
    # the chaos property suite's no-dead-dispatch evidence
    audit: List[Tuple[int, int, int, bool]] = dataclasses.field(
        default_factory=list)
    _host_calls: Dict[int, int] = dataclasses.field(default_factory=dict)
    _recovered: Dict[int, int] = dataclasses.field(default_factory=dict)
    _faults_maintained: int = 0  # host_faults already seen by maintain()
    _lock: threading.Lock = dataclasses.field(
        default_factory=threading.Lock, repr=False)
    _pool: Optional[HostExecutorPool] = dataclasses.field(
        default=None, repr=False)

    def __post_init__(self):
        if self.plan.n_members != self.inner.num_members():
            raise ValueError(
                f"plan places {self.plan.n_members} members but the backend "
                f"serves {self.inner.num_members()}")
        if self.health is not None and self.health.plan is not self.plan:
            raise ValueError(
                "health monitor must observe the router's own plan")
        if self.fanout:
            self._pool = HostExecutorPool(capacity=self.executor_capacity)

    # -- MemberBackend protocol -----------------------------------------
    def num_members(self) -> int:
        return self.inner.num_members()

    def generate(self, member_idx: int, records: Sequence,
                 max_new_tokens: MaxNewTokens) -> List[str]:
        while True:
            try:
                routed = self._consume_routed(member_idx)
                if routed is None:
                    # unroutable: every replica host is dead.  The engine
                    # should have masked this member out before generating;
                    # reaching here means the death happened mid-batch.
                    raise HostFailure(
                        next(iter(self.plan.placements[member_idx].hosts)),
                        member_idxs=(member_idx,))
                return self._run(routed[0], member_idx, records,
                                 max_new_tokens)
            except HostFailure as hf:
                if hf.member_idxs:
                    raise  # already escalated (unroutable / stranded)
                newly_dead = self._absorb_host_fault(hf.host_id)
                if not newly_dead and self.plan.primary_host(member_idx) is not None:
                    # every member on the dead host has a surviving
                    # replica — fail over and re-serve this sub-batch on
                    # the new primary, invisibly to the caller
                    with self._lock:
                        self.stats["failovers"] += 1
                    continue
                raise HostFailure(hf.host_id, member_idxs=tuple(newly_dead),
                                  cause=hf.cause) from hf.cause

    def _consume_routed(self, member_idx: int) -> Optional[Tuple[int, int]]:
        """Resolve the member's primary host and consume its dispatch
        index (raising any injected fault).  When straggler hedging is
        armed and this dispatch index is grey-slow on its host, re-route
        to the first alive replica and consume *its* dispatch index too
        — the hedge is part of the deterministic consume order, so
        sequential and fan-out routing hedge (and trace) identically.
        Returns ``(host, dispatch_idx)``, or None when unroutable."""
        host = self.plan.primary_host(member_idx)
        if host is None:
            return None
        k = self._consume_dispatch(host, member_idx)
        if k in tuple(self.host_stragglers.get(host, ())):
            if not self.hedge_stragglers:
                with self._lock:
                    self.stats["stragglers_unhedged"] += 1
            else:
                alt = self.plan.replica_host(member_idx, avoid=(host,))
                if alt is None:
                    with self._lock:
                        self.stats["stragglers_unhedged"] += 1
                else:
                    with self._lock:
                        self.stats["straggler_hedges"] += 1
                    k = self._consume_dispatch(alt, member_idx)
                    host = alt
        return host, k

    def _consume_dispatch(self, host: int, member_idx: int) -> int:
        """Advance the host's dispatch counter (raising its injected
        failure if this index is scheduled) — the single point every
        routed generation call, sequential or fanned out, passes through
        in deterministic order."""
        with self._lock:
            k = self._host_calls.get(host, 0)
            self._host_calls[host] = k + 1
            self.stats["dispatches"] += 1
            if self.record_audit:
                self.audit.append(
                    (host, member_idx, k, host in self.plan.dead_hosts))
        if k in tuple(self.host_failures.get(host, ())):
            raise HostFailure(host, cause=RuntimeError(
                f"injected host failure: host {host}, dispatch {k}"))
        return k

    def _host_rules(self, member_idx: int, host: int):
        """Context installing the host's mesh rules for this member (a
        no-op on a logical-only plan)."""
        rules = self.plan.member_rules(member_idx, host=host)
        return axis_rules(rules) if rules is not None else contextlib.nullcontext()

    def _run(self, host: int, member_idx: int, records: Sequence,
             max_new_tokens: MaxNewTokens) -> List[str]:
        """The actual inner generate, under the pinned host's mesh rules."""
        token = _CURRENT_HOST.set(host)
        try:
            with self._host_rules(member_idx, host):
                return self.inner.generate(member_idx, records, max_new_tokens)
        finally:
            _CURRENT_HOST.reset(token)

    def _absorb_host_fault(self, host_id: int) -> List[int]:
        """Mark a faulted host dead and retire its executor; returns the
        members the death newly leaves with no surviving replica (empty
        means every affected member can fail over)."""
        newly_dead = self.plan.mark_host_dead(host_id)
        with self._lock:
            self.stats["host_faults"] += 1
        if self._pool is not None:
            self._pool.retire(host_id)
        return newly_dead

    # -- fan-out ---------------------------------------------------------
    def generate_many(self, calls: Sequence[GenerationCall]
                      ) -> List[List[str]]:
        """Serve one batch's member generation calls, fanning per-host
        shards out to the executor pool when ``fanout=True``.

        The contract mirrors the engine's sequential loop exactly:
        results come back in call order; a failed member raises
        :class:`MemberFailure`; a host death that strands members raises
        :class:`HostFailure` with ``member_idxs`` — after every call the
        sequential path would have completed has completed."""
        if not self.fanout or self._pool is None or len(calls) <= 1:
            return [self._sequential_call(c) for c in calls]
        planned, escalation = self._plan_batch(calls)
        results = self._execute_shards(planned)
        if escalation is not None:
            raise escalation
        return [results[i] for i in range(len(calls))]

    def _sequential_call(self, call: GenerationCall) -> List[str]:
        try:
            return self.generate(call.member_idx, call.records,
                                 call.max_new_tokens)
        except (MemberFailure, HostFailure):
            raise
        except Exception as exc:
            raise MemberFailure(call.member_idx, exc) from exc

    def _plan_batch(self, calls: Sequence[GenerationCall]
                    ) -> Tuple[List[_PlannedCall], Optional[HostFailure]]:
        """Sequential routing pass: resolve every call's host and consume
        dispatch indices (and injected failures) in exactly the order the
        sequential path would.  Returns the executable prefix plus the
        escalation that truncated it, if any — calls past an escalation
        are never dispatched, matching sequential abort semantics."""
        planned: List[_PlannedCall] = []
        for order, call in enumerate(calls):
            j = call.member_idx
            while True:
                try:
                    routed = self._consume_routed(j)
                except HostFailure as hf:
                    newly_dead = self._absorb_host_fault(hf.host_id)
                    if not newly_dead and self.plan.primary_host(j) is not None:
                        with self._lock:
                            self.stats["failovers"] += 1
                        continue  # fail over: re-plan this call
                    return planned, HostFailure(
                        hf.host_id, member_idxs=tuple(newly_dead),
                        cause=hf.cause)
                if routed is None:
                    first = next(iter(self.plan.placements[j].hosts))
                    return planned, HostFailure(first, member_idxs=(j,))
                planned.append(_PlannedCall(order, call, routed[0], routed[1]))
                break
        return planned, None

    def _execute_shards(self, planned: List[_PlannedCall]
                        ) -> Dict[int, List[str]]:
        """Run the planned calls, one concurrent shard per host.  A shard
        aborts at its first failing call; after joining every shard the
        earliest failure (in call order — the one sequential routing
        would have hit first) is re-raised with member attribution.
        Absorbable host faults (every affected member keeps a surviving
        replica) are healed in place: the faulted call AND the aborted
        shard tail re-serve on their new primaries before returning."""
        shards: Dict[int, List[_PlannedCall]] = {}
        for p in planned:
            shards.setdefault(p.host, []).append(p)
        with self._lock:
            self.stats["fanout_batches"] += 1
            self.stats["shards"] += len(shards)

        def shard_fn(shard: List[_PlannedCall], done: Dict[int, List[str]]):
            # `done` is shared with the joining thread so a deadline
            # hedge can see (and keep) whatever the straggling shard
            # already produced; dict item writes are atomic under the GIL
            for p in shard:
                try:
                    done[p.order] = self._run(p.host, p.call.member_idx,
                                              p.call.records,
                                              p.call.max_new_tokens)
                except BaseException as exc:
                    return (p.order, p.call.member_idx, exc)
            return None

        results: Dict[int, List[str]] = {}
        errors: List[Tuple[int, int, BaseException]] = []
        pending = []
        for host, shard in sorted(shards.items()):
            done: Dict[int, List[str]] = {}
            if host in self.plan.dead_hosts:
                # the host died later in the planning pass, after these
                # earlier dispatches were already consumed (sequential
                # routing would have run them pre-death too).  Run the
                # shard on the serving thread: submitting would silently
                # respawn an executor the death already retired.
                err = shard_fn(shard, done)
                results.update(done)
                if err is not None:
                    errors.append(err)
            else:
                pending.append((shard, done, self._pool.submit(
                    host, lambda s=shard, d=done: shard_fn(s, d))))
        for shard, done, f in pending:
            try:
                err = f.result(timeout=self.shard_deadline_s)
            except TimeoutError:
                # straggling shard: cancel (drops it if still queued;
                # best-effort if running) and re-serve its unfinished
                # calls on replica hosts.  Earliest completion wins —
                # a late original result is byte-identical, so keeping
                # whichever landed first never changes outputs.
                f.cancel()
                with self._lock:
                    self.stats["shard_hedges"] += 1
                err = self._hedge_shard(shard, done)
            results.update(done)
            if err is not None:
                errors.append(err)
        for order, j, exc in sorted(errors, key=lambda e: e[0]):
            if isinstance(exc, HostFailure):
                newly_dead = self._absorb_host_fault(exc.host_id)
                if not newly_dead and self.plan.primary_host(j) is not None:
                    with self._lock:
                        self.stats["failovers"] += 1
                    continue  # healed below with the aborted shard tail
                raise HostFailure(exc.host_id, member_idxs=tuple(newly_dead),
                                  cause=exc.cause) from exc.cause
            if isinstance(exc, MemberFailure):
                raise exc
            raise MemberFailure(j, exc) from exc
        # every fault was absorbable: re-serve the faulted calls and the
        # aborted shard tails on their new primaries.  _sequential_call
        # keeps the contract — a generic error here surfaces as
        # MemberFailure(j), so the Scheduler hedges one member instead of
        # failing every sibling future.
        for p in planned:
            if p.order not in results:
                results[p.order] = self._sequential_call(p.call)
        return results

    def _hedge_shard(self, shard: List[_PlannedCall],
                     done: Dict[int, List[str]]
                     ) -> Optional[Tuple[int, int, BaseException]]:
        """Re-serve a timed-out shard's unfinished calls on replica
        hosts (falling back to the original when no replica is alive),
        inline on the serving thread.  Wall-clock hedges carry the same
        documented real-fault asymmetry as mid-shard aborts: they bypass
        dispatch counters, so injected schedules are never double-fired.
        The straggler keeps running; ``setdefault`` lets the earliest
        completion win."""
        for p in shard:
            if p.order in done:
                continue
            alt = self.plan.replica_host(p.call.member_idx, avoid=(p.host,))
            target = p.host if alt is None else alt
            try:
                res = self._run(target, p.call.member_idx, p.call.records,
                                p.call.max_new_tokens)
            except BaseException as exc:
                return (p.order, p.call.member_idx, exc)
            done.setdefault(p.order, res)
        return None

    # -- recovery maintenance --------------------------------------------
    def _next_revive_tick(self, host_id: int) -> Optional[int]:
        """The tick at which the host's next scheduled recovery (plus
        probation) completes, or None when none remains."""
        ticks = tuple(self.host_recovery.get(host_id, ()))
        consumed = self._recovered.get(host_id, 0)
        if consumed >= len(ticks):
            return None
        return ticks[consumed] + self.probation_ticks

    def maintenance_pending(self, now: int) -> bool:
        """Whether :meth:`maintain` might change placement state at this
        tick.  Deliberately computed from *static* schedule state only
        (unconsumed recovery entries whose tick has arrived; rebalance
        armed) — never from live host health, which an in-flight async
        batch may still be about to change.  The Scheduler drains
        (``join``) exactly when this answers True, then lets
        :meth:`maintain` decide precisely on the drained state, so sync
        and async modes make identical maintenance decisions at
        identical ticks."""
        if self.health is not None and self.health.probe_due(now):
            return True  # probe_due is pure in (tick, interval): static
        for h in self.host_recovery:
            t = self._next_revive_tick(h)
            if t is not None and now >= t:
                return True
        if not self.rebalance:
            return False
        # rebalance can only newly apply after a host fault: pending while
        # the static failure schedule still has unfired entries (true in
        # both dispatch modes regardless of worker progress — a stale
        # counter read only errs toward True), or while a fault maintain()
        # has not yet seen awaits handling.  A healthy fleet with its
        # schedule exhausted never pays the drain barrier.
        with self._lock:
            faults, calls = self.stats["host_faults"], dict(self._host_calls)
        if faults > self._faults_maintained:
            return True
        return any(any(k >= calls.get(h, 0) for k in tuple(ks))
                   for h, ks in self.host_failures.items())

    def maintain(self, now: int) -> List[dict]:
        """Apply due revivals and rebalances; returns trace-ready event
        dicts.  MUST be called with no shards in flight (the Scheduler
        joins first) — migration never races generation.  A recovery
        entry whose tick arrives while its host is alive (never died, or
        already revived) is consumed silently: recovery ticks are
        absolute scenario time, not death-relative."""
        events: List[dict] = []
        if self.health is not None and self.health.probe_due(now):
            probe_events = self.health.run_probes(now)
            for ev in probe_events:
                kind = ev["event"]
                with self._lock:
                    if kind == "probe":
                        self.stats["probes"] += 1
                    elif kind == "probe_death":
                        self.stats["probe_deaths"] += 1
                    elif kind == "probe_revive":
                        self.stats["probe_revivals"] += 1
                        self.stats["revivals"] += 1
                if kind == "probe_death" and self._pool is not None:
                    self._pool.retire(ev["host"])
            events.extend(probe_events)
        for h in sorted(self.host_recovery):
            t = self._next_revive_tick(h)
            if t is None or now < t:
                continue
            self._recovered[h] = self._recovered.get(h, 0) + 1
            if h not in self.plan.dead_hosts:
                continue  # moot: nothing to revive at its scheduled tick
            restored = self.plan.revive_host(h)
            with self._lock:
                self.stats["revivals"] += 1
            events.append({"event": "revive", "host": h,
                           "recovered": restored,
                           "probation": self.probation_ticks})
        if self.rebalance:
            for j, h in self.plan.rebalance():
                with self._lock:
                    self.stats["rebalanced"] += 1
                events.append({"event": "rebalance", "member": j, "host": h})
            with self._lock:
                self._faults_maintained = self.stats["host_faults"]
        return events

    def dead_members(self) -> List[int]:
        """Members with no surviving replica — the Scheduler snapshots
        this once per batch at dispatch time (an atomic read under the
        plan's lock) and pre-masks them out of the knapsack, so only the
        batch in flight at the fault pays a retry."""
        return self.plan.dead_members()

    def close(self) -> None:
        """Stop the fan-out executor threads (no-op in sequential mode)."""
        if self._pool is not None:
            self._pool.close()

    # -- optional protocol hooks forward to the wrapped backend ----------
    def warm(self, shapes: Sequence,
             members: Optional[Sequence[int]] = None) -> None:
        """Warm each member once per distinct host mesh it is placed on,
        under that host's rules: a member-level backend keys its compiled
        programs and caches by host, so warming outside the rules would
        compile programs no routed call uses."""
        warm = getattr(self.inner, "warm", None)
        if not callable(warm):
            return
        for j in range(self.plan.n_members) if members is None else members:
            meshes = set()
            for host in self.plan.placements[j].hosts:
                mesh = self.plan.host_mesh(host)
                if mesh in meshes:
                    continue
                meshes.add(mesh)
                with self._host_rules(j, host):
                    warm(shapes, members=(j,))

    def compiles(self) -> int:
        compiles = getattr(self.inner, "compiles", None)
        return compiles() if callable(compiles) else 0

    # -- introspection ---------------------------------------------------
    def split_by_host(self, member_idxs: Sequence[int]
                      ) -> Dict[Optional[int], Tuple[int, ...]]:
        """Group members by the host their generation would route to —
        the per-placement sub-batches of one scheduler batch.  ``None``
        keys members that cannot route (all replicas dead)."""
        out: Dict[Optional[int], List[int]] = {}
        for j in member_idxs:
            out.setdefault(self.plan.primary_host(j), []).append(j)
        return {h: tuple(js) for h, js in out.items()}
