"""Cluster-serving suite: placement, async dispatch, host-failure hedging.

Runs in the scenario tier (``-m scenario``) and, additionally, as the CI
``cluster`` job (``-m cluster``) under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` so the per-host
mesh path is exercised on real (forced) multi-device CPU.  Everything
here also passes on one device — placement then runs logical-only with
identical routing.

Pinned properties:

* **sync/async byte-equivalence** — every preset scenario's async trace
  (and responses) is byte-identical to its ``sync=True`` trace;
* **host-failure determinism** — the host-outage re-serve is exactly
  replayable, and its responses equal the offline engine path with the
  dead members masked (knapsack re-solved over the survivors);
* **placement invariance** — routing a batch through *any* member→host
  assignment yields identical fused outputs (property test);
* **deadline-aware admission** — the predicted-queue-delay shed follows
  a hand-computed golden trace;
* **wall-clock capture/replay** — a captured run re-drives a fresh
  scheduler to byte-identical responses.
"""

import dataclasses
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro import configs
from repro.core import build_predictor, make_policy
from repro.data import DEFAULT_POOL, generate_dataset, query_cost_matrix
from repro.models import build_model
from repro.serve import (
    AdmissionControl,
    CancelledShard,
    ClusterRouter,
    DispatchWorker,
    EnsembleRequest,
    EnsembleServer,
    HealthMonitor,
    HostExecutorPool,
    HostFailure,
    InboxFull,
    PlacementPlan,
    RequestShed,
    Scheduler,
    TrafficSimulator,
    preset_scenarios,
    requests_from_records,
)

pytestmark = [pytest.mark.scenario, pytest.mark.cluster]

N_POOL = len(DEFAULT_POOL)
RECORDS = generate_dataset(12, seed=3)


@pytest.fixture(scope="module")
def stack():
    pred = build_predictor(num_models=N_POOL)
    pp = pred.init(jax.random.key(0))
    fuser = build_model(configs.get("gen-fuser"))
    fp = fuser.init(jax.random.key(1))
    return pred, pp, fuser, fp


def _server(stack, policy="modi", **kwargs):
    pred, pp, fuser, fp = stack
    return EnsembleServer(DEFAULT_POOL, make_policy(policy, **kwargs),
                          pred, pp, fuser, fp)


def _sched(stack, sync=True, **kwargs):
    kwargs.setdefault("max_batch_size", 4)
    kwargs.setdefault("max_wait_ticks", 2)
    return Scheduler(_server(stack, budget=0.2), sync=sync, **kwargs)


# ---------------------------------------------------------------------------
# PlacementPlan
# ---------------------------------------------------------------------------


def test_auto_placement_balances_and_covers():
    plan = PlacementPlan.auto(DEFAULT_POOL, n_hosts=4)
    placed = sorted(j for h in range(4) for j in plan.members_on_host(h))
    assert placed == list(range(N_POOL))  # every member placed exactly once
    load = plan.host_load()
    # greedy balance: no host carries more than ~2x the lightest
    assert max(load.values()) <= 2 * min(load.values())


def test_auto_placement_replicas_on_distinct_hosts():
    plan = PlacementPlan.auto(DEFAULT_POOL, n_hosts=4, replicas=2)
    for p in plan.placements:
        assert len(set(p.hosts)) == 2
    # one host down: every member keeps a replica
    assert plan.mark_host_dead(0) == []
    assert plan.dead_members() == []


def test_mark_host_dead_reports_newly_unroutable_members():
    plan = PlacementPlan.round_robin(N_POOL, 4)
    lost = plan.mark_host_dead(1)
    assert lost == [j for j in range(N_POOL) if j % 4 == 1]
    assert plan.primary_host(lost[0]) is None
    assert sorted(plan.alive_members() + lost) == list(range(N_POOL))
    plan.revive()
    assert plan.dead_members() == []


def test_placement_plan_validates():
    with pytest.raises(ValueError):
        PlacementPlan.auto(DEFAULT_POOL, n_hosts=0)
    with pytest.raises(ValueError):
        PlacementPlan.auto(DEFAULT_POOL, n_hosts=2, replicas=3)


@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs 8 forced host devices (CI cluster job)")
def test_placement_builds_real_host_meshes():
    devices = jax.devices()[:8]
    plan = PlacementPlan.auto(DEFAULT_POOL, n_hosts=4, devices=devices)
    for h in range(4):
        mesh = plan.host_mesh(h)
        assert mesh is not None and mesh.devices.size == 2
    rules = plan.member_rules(0)
    assert rules is not None and rules.mesh.axis_names == ("data", "model")


# ---------------------------------------------------------------------------
# Sync/async byte-equivalence on every preset scenario
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", sorted(preset_scenarios()))
def test_async_trace_matches_sync_trace(stack, name):
    scenario = preset_scenarios(n_requests=12)[name]
    sync_rep = TrafficSimulator(_sched(stack, sync=True), scenario,
                                RECORDS).run()
    sched = _sched(stack, sync=False)
    try:
        async_rep = TrafficSimulator(sched, scenario, RECORDS).run()
    finally:
        sched.close()
    assert async_rep.trace == sync_rep.trace
    assert async_rep.stats == sync_rep.stats
    assert ([r.text if r else None for r in async_rep.responses]
            == [r.text if r else None for r in sync_rep.responses])
    assert async_rep.latency_ticks == sync_rep.latency_ticks


def test_async_submit_returns_before_batch_serves(stack):
    """A full policy group enqueues its batch; submit must come back with
    the batch still unserved (the worker picks it up afterwards)."""
    sched = _sched(stack, sync=False, max_batch_size=2, max_wait_ticks=10)
    try:
        blocker = threading.Event()
        inner = sched.server.backend
        orig = inner.generate

        def slow_generate(j, records, caps):
            blocker.wait(10.0)
            return orig(j, records, caps)

        inner.generate = slow_generate
        futs = [sched.submit(EnsembleRequest(query=r.query, record=r))
                for r in RECORDS[:2]]
        # inline trigger fired (queue drained) but service is blocked
        assert sched.pending == 0
        assert not any(f.done() for f in futs)
        blocker.set()
        sched.join()
        assert all(f.done() for f in futs)
    finally:
        sched.close()


def test_async_engine_error_surfaces_at_result(stack):
    sched = Scheduler(_server(stack, budget=0.2), max_batch_size=2,
                      max_wait_ticks=10, sync=False, hedge=False)
    try:
        inner = sched.server.backend

        def boom(j, records, caps):
            raise RuntimeError("backend down")

        inner.generate = boom
        futs = [sched.submit(EnsembleRequest(query=r.query, record=r))
                for r in RECORDS[:2]]
        sched.join()
        for f in futs:
            with pytest.raises(RuntimeError, match="backend down"):
                f.result(timeout=5.0)
    finally:
        sched.close()


def test_dispatch_worker_backpressure():
    started = threading.Event()
    release = threading.Event()

    def slow(job):
        started.set()
        release.wait(10.0)

    w = DispatchWorker(slow, capacity=1)
    try:
        w.submit("a")
        assert started.wait(5.0)
        w.submit("b")  # fills the inbox while "a" is in service
        with pytest.raises(InboxFull):
            w.try_submit("c")
        assert w.full()
        release.set()
        w.join()
        assert w.processed == 2
    finally:
        release.set()
        w.close()


# ---------------------------------------------------------------------------
# Worker lifecycle: submit/close races, executor pool, shard cancellation
# ---------------------------------------------------------------------------


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 100_000))
def test_try_submit_vs_close_interleaving_never_strands_jobs(seed):
    """Race ``try_submit`` against ``close()`` under random
    interleavings: a job the worker ACCEPTED (try_submit returned
    without raising) must always end up either processed or handed to
    ``on_orphan`` — never silently dropped into a closed inbox, never a
    hung future — and submits after close fail loudly."""
    rng = np.random.default_rng(seed)
    pre_delays = rng.random(8) * 1e-3
    close_delay = float(rng.random()) * 2e-3
    served, orphans, accepted = [], [], []
    w = DispatchWorker(served.append, capacity=4, on_orphan=orphans.append)
    start = threading.Barrier(2)

    def produce():
        start.wait()
        for i, d in enumerate(pre_delays):
            try:
                w.try_submit(i)
            except (InboxFull, RuntimeError):
                continue  # backpressure or closed: the caller was told
            accepted.append(i)
            time.sleep(d)

    t = threading.Thread(target=produce)
    t.start()
    start.wait()
    time.sleep(close_delay)
    w.close()
    t.join(5.0)
    assert not t.is_alive()
    # every accepted job is accounted for exactly once
    assert sorted(served + orphans) == sorted(accepted)
    assert w.orphaned == len(orphans)
    with pytest.raises(RuntimeError, match="closed"):
        w.submit("late")
    with pytest.raises(RuntimeError, match="closed"):
        w.try_submit("late")


def test_host_executor_pool_close_is_idempotent_and_final():
    pool = HostExecutorPool(capacity=2)
    f = pool.submit(0, lambda: 41 + 1)
    assert f.result(timeout=5.0) == 42
    assert pool.spawned == 1
    pool.close()
    pool.close()  # idempotent: second close is a no-op, not an error
    assert pool.closed
    # a post-close submit must refuse loudly instead of lazily respawning
    # an executor thread nothing will ever join
    with pytest.raises(RuntimeError, match="closed"):
        pool.submit(0, lambda: None)
    assert pool.spawned == 1  # the rejected submit respawned nothing
    assert pool.live_hosts() == []


def test_shard_future_cancellation_semantics():
    pool = HostExecutorPool(capacity=4)
    try:
        release = threading.Event()
        blocker = pool.submit(0, lambda: (release.wait(10.0), "first")[1])
        queued = pool.submit(0, lambda: "ran")
        assert queued.cancel()  # still queued behind the blocker
        assert queued.cancelled()
        release.set()
        with pytest.raises(CancelledShard):
            queued.result(timeout=5.0)
        assert blocker.result(timeout=5.0) == "first"
        assert not blocker.cancel()  # already resolved: cancel refuses
    finally:
        pool.close()


def test_result_timeout_records_event_and_stays_resolvable(stack):
    """result(timeout=) expiring while the batch is in flight raises
    TimeoutError, leaves a "timeout" trace event (an abandoned wait used
    to be silent), and keeps the future resolvable: a later result()
    returns normally once the batch lands."""
    sched = _sched(stack, sync=False, max_batch_size=2, max_wait_ticks=10)
    release = threading.Event()
    try:
        inner = sched.server.backend
        orig = inner.generate

        def slow_generate(j, records, caps):
            release.wait(10.0)
            return orig(j, records, caps)

        inner.generate = slow_generate
        futs = [sched.submit(EnsembleRequest(query=r.query, record=r))
                for r in RECORDS[:2]]
        with pytest.raises(TimeoutError, match="not served within"):
            futs[0].result(timeout=0.05)
        timeouts = [e for e in sched.events if e["event"] == "timeout"]
        assert len(timeouts) == 1
        assert timeouts[0]["req"] == 0 and timeouts[0]["waited_s"] == 0.05
        assert sched.stats["result_timeouts"] == 1
        release.set()
        sched.join()
        assert futs[0].result(timeout=5.0).text  # still resolvable
        assert futs[1].result(timeout=5.0).text
    finally:
        release.set()
        sched.close()


def test_health_monitor_backoff_probation_and_flaky_probe():
    """Breaker mechanics in isolation: two consecutive probe failures
    open host 0 (members stranded), failed half-open probes back off
    exponentially (2 → 4 → capped 4), and the first clean probe after
    the underlying health returns revives it.  A single flaky probe on
    host 1 stays under the threshold and never opens anything."""
    plan = PlacementPlan.round_robin(N_POOL, 2)
    hm = HealthMonitor(plan, probe_interval=1, probe_failures=2,
                       probe_faults={0: (0, 1, 2, 3, 4), 1: (2,)},
                       recovery={0: (1,)}, backoff_ticks=2, backoff_cap=4)
    trace = []
    for now in range(1, 15):
        trace.extend((now, ev) for ev in hm.run_probes(now))

    deaths = [(t, e) for t, e in trace if e["event"] == "probe_death"]
    assert deaths == [(2, {"event": "probe_death", "host": 0, "failures": 2,
                           "stranded": [0, 2, 4, 6]})]
    half_open = [(t, e["ok"]) for t, e in trace
                 if e["event"] == "probe" and e["half_open"]]
    assert half_open == [(4, False), (6, False), (10, False), (14, True)]
    revives = [(t, e) for t, e in trace if e["event"] == "probe_revive"]
    assert revives == [(14, {"event": "probe_revive", "host": 0,
                             "recovered": [0, 2, 4, 6], "after_probes": 6})]
    assert plan.dead_hosts == set()
    assert hm.state(0) == "closed"
    # host 1's isolated flaky probe: trace-visible, below threshold
    flaky = [(t, e["probe"]) for t, e in trace
             if e.get("host") == 1 and e["event"] == "probe" and not e["ok"]]
    assert flaky == [(3, 2)]
    assert not any(e["event"] == "probe_death" and e["host"] == 1
                   for _, e in trace)


# ---------------------------------------------------------------------------
# Host failure: hedging, masked knapsack re-solve, determinism
# ---------------------------------------------------------------------------


def test_host_outage_reserves_on_survivors_and_masks_knapsack(stack):
    scenario = preset_scenarios(n_requests=12)["host-outage"]
    sched = _sched(stack)
    report = TrafficSimulator(sched, scenario, RECORDS).run()
    assert report.served == report.n
    assert report.stats["host_hedges"] == 1

    hedge = next(e for e in report.trace if e["event"] == "host_hedge")
    dead = set(hedge["members"])
    assert dead  # the outage actually killed unreplicated members
    router = sched.server.backend
    assert isinstance(router, ClusterRouter)
    assert set(router.dead_members()) == dead

    # every response after the fault selects no dead member
    hedged_and_later = [i for i in range(report.n) if i >= min(hedge["reqs"])]
    for i in hedged_and_later:
        assert not report.responses[i].mask[sorted(dead)].any()

    # the hedged batch equals the offline path with the dead members
    # masked (knapsack re-solved over survivors, not post-hoc excluded)
    offline = _server(stack, budget=0.2).serve_requests(
        [report.requests[i] for i in hedge["reqs"]],
        masked_members=frozenset(dead))
    for i, resp in zip(hedge["reqs"], offline):
        assert report.responses[i].text == resp.text
        assert (report.responses[i].mask == resp.mask).all()

    # requests fully served before the fault match the plain offline path
    before = [i for i in range(report.n) if i < min(hedge["reqs"])]
    plain = _server(stack, budget=0.2).serve_requests(
        [report.requests[i] for i in before])
    for i, resp in zip(before, plain):
        assert report.responses[i].text == resp.text


def test_host_outage_trace_replays_identically(stack):
    scenario = preset_scenarios(n_requests=12)["host-outage"]

    def run_once():
        return TrafficSimulator(_sched(stack), scenario, RECORDS).run()

    a, b = run_once(), run_once()
    assert a.trace == b.trace
    assert a.stats == b.stats


def test_replicated_placement_absorbs_host_death(stack):
    """With replicas=2 every member survives one host's death: the router
    fails over internally, no HostFailure escapes, no knapsack re-solve.
    llm-blender selects every member, so some generation is guaranteed to
    route to the failing host and trip the injection."""
    server = _server(stack, policy="llm-blender")
    plan = PlacementPlan.auto(DEFAULT_POOL, n_hosts=4, replicas=2)
    server.backend = ClusterRouter(server.backend, plan=plan,
                                   host_failures={0: (0,)})
    sched = Scheduler(server, max_batch_size=4, max_wait_ticks=2)
    futs = [sched.submit(EnsembleRequest(query=r.query, record=r))
            for r in RECORDS[:8]]
    sched.flush()
    texts = [f.result().text for f in futs]
    assert sched.stats["host_hedges"] == 0
    assert server.backend.stats["failovers"] >= 1
    baseline = _server(stack, policy="llm-blender").serve_requests(
        requests_from_records(RECORDS[:8]))
    assert texts == [r.text for r in baseline]


def test_total_outage_fails_batch_but_resolves_futures(stack):
    """Every host dying leaves nothing to hedge onto: the batch fails,
    futures resolve with the cause (never hang) — and batches formed
    AFTER the total outage fail with a clear error rather than handing
    the engine an empty pool (regression: they used to die on an
    IndexError deep in selection)."""
    server = _server(stack, budget=0.2)
    plan = PlacementPlan.round_robin(N_POOL, 2)
    server.backend = ClusterRouter(server.backend, plan=plan,
                                   host_failures={0: (0, 1, 2, 3),
                                                  1: (0, 1, 2, 3)})
    sched = Scheduler(server, max_batch_size=2, max_wait_ticks=10)
    futs = []
    with pytest.raises(HostFailure):
        for r in RECORDS[:2]:
            futs.append(sched.submit(EnsembleRequest(query=r.query, record=r)))
    assert sched.last_submitted is not None and sched.last_submitted.done()
    with pytest.raises(HostFailure):
        sched.last_submitted.result()

    late = []
    with pytest.raises(RuntimeError, match="no servable pool members"):
        for r in RECORDS[2:4]:
            late.append(sched.submit(EnsembleRequest(query=r.query, record=r)))
    assert sched.last_submitted.done()
    with pytest.raises(RuntimeError, match="no servable pool members"):
        sched.last_submitted.result()


def test_async_result_after_close_resolves_instead_of_hanging(stack):
    """Regression: result() on a queued request after close() used to pop
    the batch, fail the worker submit, and leave every future pending
    forever.  It must resolve the futures with the closed-worker cause."""
    sched = _sched(stack, sync=False, max_batch_size=8, max_wait_ticks=10)
    f1 = sched.submit(EnsembleRequest(query=RECORDS[0].query,
                                      record=RECORDS[0]))
    f2 = sched.submit(EnsembleRequest(query=RECORDS[1].query,
                                      record=RECORDS[1]))
    sched.close()
    with pytest.raises(RuntimeError, match="closed"):
        f1.result(timeout=5.0)
    assert f2.done()
    with pytest.raises(RuntimeError, match="closed"):
        f2.result(timeout=5.0)


def test_engine_masked_members_resolve_knapsack_over_survivors(stack):
    """masked_members re-targets ε at the survivors' full-ensemble cost —
    the policy solves over the surviving columns, not the full matrix
    with columns struck out afterwards."""
    server = _server(stack, budget=0.2)
    reqs = requests_from_records(RECORDS[:8])
    masked = frozenset({1, 7})
    via_mask = server.serve_requests(reqs, masked_members=masked)
    alive = [j for j in range(N_POOL) if j not in masked]
    for r in via_mask:
        assert not r.mask[sorted(masked)].any()
    # the engine's masked solve == the policy run on the reduced matrices
    records = [req.resolve_record() for req in reqs]
    r_hat = server.predict_quality([r.query for r in records])
    costs = query_cost_matrix(DEFAULT_POOL, records)
    reduced = np.asarray(make_policy("modi", budget=0.2).select(
        jnp.asarray(r_hat[:, alive]), jnp.asarray(costs[:, alive])))
    expect = np.zeros((len(reqs), N_POOL), bool)
    expect[:, alive] = reduced
    got = np.stack([r.mask for r in via_mask])
    assert (got == expect).all()
    # and the ε budget now binds on the survivors' full-ensemble cost
    survivors_total = costs[:, alive].sum(axis=1)
    realized = np.asarray([r.realized_cost for r in via_mask])
    single_min = costs[:, alive].min(axis=1)  # cheapest-survivor fallback floor
    assert (realized <= np.maximum(0.2 * survivors_total, single_min) + 1e-6).all()


# ---------------------------------------------------------------------------
# Placement-permutation property: routing never changes outputs
# ---------------------------------------------------------------------------


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 10_000), n_hosts=st.sampled_from([2, 3, 4, 5]))
def test_any_placement_permutation_is_output_invariant(seed, n_hosts):
    """Routing a batch through ANY member→host assignment (not just the
    balanced placer's) yields fused outputs identical to the unrouted
    engine — placement decides where generation runs, never what it says."""
    stack = _PROPERTY_STACK
    rng = np.random.default_rng(seed)
    base = PlacementPlan.round_robin(N_POOL, n_hosts)
    plan = PlacementPlan(
        hosts=base.hosts,
        placements=[
            dataclasses.replace(p, hosts=(int(rng.integers(0, n_hosts)),))
            for p in base.placements
        ],
    )
    server = _server(stack, budget=0.2)
    server.backend = ClusterRouter(server.backend, plan=plan)
    routed = server.serve_requests(requests_from_records(RECORDS[:4]))
    assert [r.text for r in routed] == _PROPERTY_BASELINE


_PROPERTY_STACK = None
_PROPERTY_BASELINE = None


@pytest.fixture(autouse=True)
def _property_stack(stack):
    """The hypothesis shim drives tests without pytest fixtures — stage the
    module stack (and the unrouted baseline) for the property test."""
    global _PROPERTY_STACK, _PROPERTY_BASELINE
    _PROPERTY_STACK = stack
    if _PROPERTY_BASELINE is None:
        _PROPERTY_BASELINE = [
            r.text for r in _server(stack, budget=0.2).serve_requests(
                requests_from_records(RECORDS[:4]))
        ]
    yield


# ---------------------------------------------------------------------------
# Deadline-aware admission: golden trace for the new shed reason
# ---------------------------------------------------------------------------


def test_deadline_aware_admission_golden_trace(stack):
    """max_batch_size=2, deadline_aware on.  Ticks are hand-computed:

    * tick 0 — two requests fill a batch and dispatch inline.  First
      dispatch seeds the gap clock only (EWMA still empty).
    * ticks 1-2 — clock advances, nothing queued.
    * tick 2 — two more requests dispatch inline: gap = 2 ticks, EWMA=2.
    * submit A (deadline_ticks=1): predicted delay = EWMA 2.0 × 1 batch
      ahead = 2.0 > 1 → shed, reason ``deadline``.
    * submit B (deadline_ticks=4): 2.0 <= 4 → admitted and queued.
    """
    sched = Scheduler(
        _server(stack, budget=0.2), max_batch_size=2, max_wait_ticks=10,
        admission=AdmissionControl(deadline_aware=True))
    recs = generate_dataset(6, seed=11)
    for r in recs[:2]:
        sched.submit(EnsembleRequest(query=r.query, record=r))
    assert sched.predicted_queue_delay() == 0.0  # no gap observed yet
    sched.tick()
    sched.tick()
    for r in recs[2:4]:
        sched.submit(EnsembleRequest(query=r.query, record=r))
    assert sched.predicted_queue_delay() == 2.0

    shed_f = sched.submit(EnsembleRequest(query=recs[4].query, record=recs[4],
                                          deadline_ticks=1))
    assert shed_f.shed()
    with pytest.raises(RequestShed, match="predicted queue delay"):
        shed_f.result()
    ok_f = sched.submit(EnsembleRequest(query=recs[5].query, record=recs[5],
                                        deadline_ticks=4))
    assert not ok_f.done() and sched.pending == 1

    assert sched.stats["shed"] == 1
    shed_events = [e for e in sched.events if e["event"] == "shed"]
    assert shed_events == [{
        "tick": 2, "event": "shed", "req": 4, "reason": "deadline",
        "predicted_delay": 2.0, "deadline_ticks": 1,
    }]
    sched.flush()
    assert ok_f.done()


def test_deadline_aware_ignores_requests_without_deadline(stack):
    sched = Scheduler(
        _server(stack, budget=0.2), max_batch_size=2, max_wait_ticks=10,
        admission=AdmissionControl(deadline_aware=True))
    recs = generate_dataset(3, seed=11)
    for r in recs[:2]:
        sched.submit(EnsembleRequest(query=r.query, record=r))
    sched.tick()
    sched.tick()
    sched.tick()
    for r in recs[:2]:
        sched.submit(EnsembleRequest(query=r.query, record=r))
    assert sched.predicted_queue_delay() == 3.0
    f = sched.submit(EnsembleRequest(query=recs[2].query, record=recs[2]))
    assert not f.shed()  # no deadline, nothing to miss
    sched.flush()


# ---------------------------------------------------------------------------
# Wall-clock capture/replay
# ---------------------------------------------------------------------------


def test_captured_trace_replays_byte_identically(stack):
    scenario = preset_scenarios(n_requests=12)["steady"]
    original = TrafficSimulator(_sched(stack), scenario, RECORDS).run()
    captured = original.captured()
    assert len(captured.wall_ns) == original.n
    assert list(captured.ticks) == original.arrival_ticks
    assert all(b >= a for a, b in zip(captured.wall_ns, captured.wall_ns[1:]))

    replayed = TrafficSimulator.replay(_sched(stack), captured)
    assert [r.text for r in replayed.responses] == [
        r.text for r in original.responses]
    assert replayed.arrival_ticks == original.arrival_ticks
    assert replayed.trace == original.trace


def test_captured_trace_time_scale_compresses_schedule(stack):
    scenario = preset_scenarios(n_requests=12)["steady"]
    captured = TrafficSimulator(_sched(stack), scenario, RECORDS).run().captured()
    fast = TrafficSimulator.replay(_sched(stack), captured, time_scale=4.0)
    assert fast.served == fast.n
    # 4x compression: the wall-derived schedule spans well under the
    # original's logical span
    assert max(fast.arrival_ticks) <= max(captured.ticks)
    # and replaying the same capture at the same scale is deterministic
    again = TrafficSimulator.replay(_sched(stack), captured, time_scale=4.0)
    assert again.arrival_ticks == fast.arrival_ticks
    assert [r.text for r in again.responses] == [r.text for r in fast.responses]


# ---------------------------------------------------------------------------
# Diurnal load curve (scenario-tier coverage for the new preset)
# ---------------------------------------------------------------------------


def test_diurnal_scenario_miss_and_shed_rates(stack):
    """The diurnal curve with a 30% urgent (deadline 0) mix stresses the
    fleet both ways: without admission the trough stragglers dispatch a
    tick late and MISS; with deadline-aware admission those same hopeless
    requests SHED at arrival instead, and served requests never miss.
    Rates are pinned to bands (not exact counts) so unrelated scheduler
    tweaks don't churn them."""
    scenario = dataclasses.replace(
        preset_scenarios(n_requests=24)["diurnal"],
        mix=((0.7, {}), (0.3, {"deadline_ticks": 0, "priority": 1})))
    records = generate_dataset(24, seed=3)

    plain = TrafficSimulator(
        Scheduler(_server(stack, budget=0.2), max_batch_size=4,
                  max_wait_ticks=2),
        scenario, records).run()
    assert plain.served == plain.n  # best-effort serves everything...
    assert 0.0 < plain.deadline_miss_rate <= 0.3  # ...but peak clumps miss

    aware = TrafficSimulator(
        Scheduler(_server(stack, budget=0.2), max_batch_size=4,
                  max_wait_ticks=2,
                  admission=AdmissionControl(deadline_aware=True)),
        scenario, records).run()
    assert aware.served + aware.stats["shed"] == aware.n  # nothing hangs
    assert 0.0 < aware.shed_rate <= 0.5  # the hopeless requests shed...
    assert aware.deadline_miss_rate == 0.0  # ...and served ones never miss


def test_diurnal_arrivals_are_deterministic_and_follow_curve():
    proc = preset_scenarios()["diurnal"].arrivals
    a = proc.arrival_ticks(48, np.random.default_rng(0))
    b = proc.arrival_ticks(48, np.random.default_rng(7))
    assert a == b  # rng-free: the curve is the schedule
    assert all(x <= y for x, y in zip(a, a[1:]))
    # arrivals clump at the peak: the busiest period-window holds more
    # than an even share
    period = proc.period
    counts = np.bincount(np.asarray(a) // period)
    assert counts.max() > len(a) / max(len(counts), 1)


def test_live_members_compile_and_live_on_their_host():
    """A live member's weights, decode caches and compiled buckets belong
    to the host the router placed it on: warming under each host's rules
    builds exactly one dispatcher per (member, host mesh), every array it
    holds sits on that host's devices, routed calls reuse it without a
    compile, and the text equals an unrouted backend's."""
    from repro.serve import LiveLMBackend, LiveMember

    cfg = configs.get("smollm-360m").reduced(dtype="float32")
    model = build_model(cfg)
    members = [LiveMember(DEFAULT_POOL[j], model, model.init(jax.random.key(j)))
               for j in range(2)]
    devices = jax.devices()
    n_hosts = min(2, len(devices))
    plan = PlacementPlan.round_robin(2, n_hosts, devices=devices[:n_hosts])
    live = LiveLMBackend(members)
    router = ClusterRouter(live, plan=plan)
    router.warm([(1, 4)])
    meshes = {j: plan.host_mesh(plan.primary_host(j)) for j in range(2)}
    assert set(live._dispatchers) == {(j, meshes[j]) for j in range(2)}
    for (j, mesh), d in live._dispatchers.items():
        want = set(mesh.devices.flat)
        leaves = jax.tree.leaves(d.params) + [
            leaf for e in d._entries.values() for leaf in jax.tree.leaves(e.cache)]
        assert leaves and all(leaf.devices() == want for leaf in leaves)
    warm_compiles = live.compiles()
    recs = RECORDS[:1]
    plain = LiveLMBackend(members)
    for j in range(2):
        assert router.generate(j, recs, 4) == plain.generate(j, recs, 4)
    assert live.compiles() == warm_compiles
