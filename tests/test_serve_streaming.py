"""Streaming / token-level continuous batching suite (CI: the scenario
job runs it via ``-m "scenario or streaming"``).

Pins the acceptance properties of the persistent in-flight decode state:

* **offline equivalence** — rows decoded through the
  :class:`StreamingEncDecBatcher` (and through a streaming Scheduler end
  to end) are byte-identical to the batch-boundary path;
* **prefix stability** — every streamed :class:`StreamEvent` carries a
  token tuple that extends the previous event's and a text that is a
  string prefix of the final fused text;
* **mid-decode join** — requests submitted while earlier rows are still
  decoding join at the next step with zero new compiles once the rungs
  are warm, without perturbing co-resident rows;
* **sync/async byte-equivalence** — the ``streaming`` preset scenario
  produces identical traces, stats, and texts in both modes;

plus the fast-path bugfix regressions that ride along this PR:
``result(timeout=)`` racing its own resolution, ``_take_count`` clamping
to the ladder's top rung (no steady-state recompile when
``max_batch_size`` exceeds it), and ``padded_rows`` counted once per
served dispatch even when the batch pays a hedged retry.
"""

import threading

import jax
import numpy as np
import pytest

from repro import configs
from repro.core import build_predictor, make_policy
from repro.data import DEFAULT_POOL, TOKENIZER, generate_dataset
from repro.models import build_model
from repro.serve import (
    BucketLadder,
    EnsembleServer,
    FailureInjector,
    Scheduler,
    StreamingEncDecBatcher,
    TrafficSimulator,
    greedy_generate_encdec,
    preset_scenarios,
    requests_from_records,
    spans,
)

pytestmark = pytest.mark.streaming


@pytest.fixture(scope="module")
def fuser():
    model = build_model(configs.get("gen-fuser"))
    return model, model.init(jax.random.key(1))


@pytest.fixture(scope="module")
def stack():
    pred = build_predictor(num_models=len(DEFAULT_POOL))
    pp = pred.init(jax.random.key(0))
    fuser = build_model(configs.get("gen-fuser"))
    fp = fuser.init(jax.random.key(1))
    return pred, pp, fuser, fp


def _server(stack, policy="modi", **kwargs):
    pred, pp, fuser, fp = stack
    return EnsembleServer(DEFAULT_POOL, make_policy(policy, **kwargs),
                          pred, pp, fuser, fp)


RECORDS = generate_dataset(12, seed=3)
LADDER = BucketLadder(batch=(1, 2, 4), new_tokens=(8, 16), prompt=(32,))


def _enc(texts, enc_seq=32):
    return TOKENIZER.pad_batch([TOKENIZER.encode(t) for t in texts], enc_seq)


def _assert_row_matches_direct(tokens, direct_row):
    """A streamed row equals the batch-boundary reference: the emitted
    tokens are the reference's leading tokens, anything the eviction
    skipped is trailing pad, and the decoded text is identical."""
    tokens = list(tokens)
    np.testing.assert_array_equal(np.asarray(tokens),
                                  np.asarray(direct_row[:len(tokens)]))
    assert (np.asarray(direct_row[len(tokens):]) == TOKENIZER.pad_id).all()
    assert TOKENIZER.decode(tokens) == TOKENIZER.decode(list(direct_row))


# ---------------------------------------------------------------------------
# Batcher: offline equivalence + token-order / prefix monotonicity
# ---------------------------------------------------------------------------


def test_batcher_matches_offline_greedy(fuser):
    model, params = fuser
    batcher = StreamingEncDecBatcher(model, params, enc_seq=32, capacity=4,
                                     ladder=LADDER)
    enc = _enc(["fuse this", "and this", "third row", "fourth entry"])
    done, snaps = {}, {i: [] for i in range(4)}
    batcher.submit(
        enc, [8, 8, 8, 8],
        on_token=lambda i, toks: snaps[i].append(tuple(toks)),
        on_done=lambda i, toks: done.__setitem__(i, list(toks)))
    batcher.pump()
    assert batcher.idle and sorted(done) == [0, 1, 2, 3]
    direct = np.asarray(greedy_generate_encdec(model, params, enc, max_new=8))
    for i in range(4):
        _assert_row_matches_direct(done[i], direct[i])
        # token-order property: each emission extends the previous one,
        # and the last snapshot is exactly the settled row
        for a, b in zip(snaps[i], snaps[i][1:]):
            assert b[:len(a)] == a
        assert snaps[i][-1] == tuple(done[i])
    # one rung in play: prefill + join + the capacity-shaped step
    assert batcher.compiles == 3
    assert batcher.stats["evicted"] == 4


def test_batcher_mid_decode_join_zero_recompiles(fuser):
    """Join/leave mid-decode golden trace: a second wave submitted while
    the first is mid-decode joins at the next step with zero new compiles,
    and neither wave's bytes depend on the co-resident rows."""
    model, params = fuser
    batcher = StreamingEncDecBatcher(model, params, enc_seq=32, capacity=4,
                                     ladder=LADDER)
    batcher.warm([2])
    warm_compiles = batcher.compiles
    assert warm_compiles == 3  # prefill(2) + join(2) + step

    enc_a = _enc(["first wave row", "second row here"])
    enc_b = _enc(["late arrival one", "late two"])
    done, trace = {}, []

    def _on_done(off):
        return lambda i, toks: (done.__setitem__(off + i, list(toks)),
                                trace.append(("done", off + i)))

    batcher.submit(enc_a, [8, 8], on_done=_on_done(0))
    mid = batcher.pump(steps=3)
    assert mid == 3 and batcher.in_flight == 2
    snap_a = {i: list(done.get(i, [])) for i in range(2)}
    batcher.submit(enc_b, [8, 8], on_done=_on_done(2))  # join mid-decode
    assert batcher.in_flight == 4  # admitted into the free slots
    batcher.pump()

    assert batcher.compiles == warm_compiles  # THE acceptance gate: 0 new
    assert batcher.idle and sorted(done) == [0, 1, 2, 3]
    assert batcher.stats["joins"] == 2 and batcher.stats["evicted"] == 4
    # first wave completes before the late wave (equal caps, 3-step lead):
    # the golden eviction order is deterministic
    assert trace == [("done", 0), ("done", 1), ("done", 2), ("done", 3)]
    assert not snap_a[0] and not snap_a[1]  # still in flight at the join

    direct_a = np.asarray(greedy_generate_encdec(model, params, enc_a, max_new=8))
    direct_b = np.asarray(greedy_generate_encdec(model, params, enc_b, max_new=8))
    for i in range(2):
        _assert_row_matches_direct(done[i], direct_a[i])
        _assert_row_matches_direct(done[2 + i], direct_b[i])


# ---------------------------------------------------------------------------
# Scheduler end-to-end: streamed prefixes ⊂ final fused text, byte equality
# ---------------------------------------------------------------------------


def test_stream_prefix_stability_and_final_equality(stack):
    server = _server(stack, budget=0.2)
    sched = Scheduler(server, max_batch_size=4, stream=True, stream_capacity=4)
    reqs = requests_from_records(RECORDS[:4])
    futs = [sched.submit(r) for r in reqs]
    baseline = _server(stack, budget=0.2).serve_requests(reqs)
    for f, base in zip(futs, baseline):
        events = list(f.stream())
        assert events and events[-1].final
        final = events[-1].response
        assert final is not None and final.text == base.text
        assert (final.mask == base.mask).all()
        assert final.realized_cost == base.realized_cost
        prev = ()
        for ev in events[:-1]:
            assert not ev.final and ev.response is None
            assert ev.tokens[:len(prev)] == prev  # monotone token growth
            prev = ev.tokens
            # streamed text is a *string* prefix of the final fused text
            # (decode_capped strips trailing incomplete UTF-8)
            assert final.text.startswith(ev.text)
        assert f.ttft_s is not None and f.ttft_s >= 0.0
    assert sched.stats["stream_tokens"] > 0


def test_ttft_counts_queue_wait_and_each_decode_step_has_one_span(stack):
    """TTFT runs from submit, so it holds the request's queue wait; every
    served batch records one ``serve.fuser.step`` span per decode step."""
    server = _server(stack, budget=0.2)
    sched = Scheduler(server, max_batch_size=4, stream=True, stream_capacity=4,
                      sync=False)
    spans.enable()
    try:
        futs = [sched.submit(r) for r in requests_from_records(RECORDS[:8])]
        sched.flush()
        streamed = {f.seq: [ev for ev in f.stream(timeout=300) if not ev.final]
                    for f in futs}
        sched.close()
    finally:
        spans.disable()
    recs = spans.records()
    by_id = {r["id"]: r for r in recs}
    requests = {r["attrs"]["req"]: r for r in recs if r["name"] == "serve.request"}
    for f in futs:
        req = requests[f.seq]
        queue_wait_ns = req["attrs"]["service_ns"] - req["start_ns"]
        assert queue_wait_ns >= 0 and f.ttft_s * 1e9 >= queue_wait_ns
    steps_by_batch = {}
    for r in recs:
        if r["name"] == "serve.fuser.step":
            up = r
            while up["name"] != "serve.batch":
                up = by_id[up["parent"]]
            steps_by_batch[up["attrs"]["batch"]] = steps_by_batch.get(up["attrs"]["batch"], 0) + 1
    assert len(steps_by_batch) == 2
    for b, n_steps in steps_by_batch.items():
        rows = [f.seq for f in futs if requests[f.seq]["attrs"]["batch"] == b]
        # the batch drains before the next is served: it decodes as many
        # steps as its longest row streamed tokens
        assert n_steps == max(len(streamed[seq]) for seq in rows)
    assert sum(steps_by_batch.values()) == server.stream_fuser().stats["steps"]


def test_streaming_preset_sync_async_byte_equivalence(stack):
    """The ``streaming`` preset in both scheduler modes: identical trace,
    stats (incl. stream_tokens), texts, and latencies — and both equal the
    offline non-streaming batch path."""
    scenario = preset_scenarios(n_requests=12)["streaming"]
    assert scenario.streaming  # the preset actually exercises the path
    sync_rep = TrafficSimulator(
        Scheduler(_server(stack, budget=0.2), max_batch_size=4,
                  max_wait_ticks=2), scenario, RECORDS).run()
    sched = Scheduler(_server(stack, budget=0.2), max_batch_size=4,
                      max_wait_ticks=2, sync=False)
    try:
        async_rep = TrafficSimulator(sched, scenario, RECORDS).run()
    finally:
        sched.close()
    assert async_rep.trace == sync_rep.trace
    assert async_rep.stats == sync_rep.stats
    assert sync_rep.stats["stream_tokens"] > 0
    assert ([r.text if r else None for r in async_rep.responses]
            == [r.text if r else None for r in sync_rep.responses])
    assert async_rep.latency_ticks == sync_rep.latency_ticks

    assert sync_rep.served == sync_rep.n
    offline = _server(stack, budget=0.2).serve_requests(sync_rep.requests)
    assert ([r.text for r in sync_rep.responses] == [r.text for r in offline])


# ---------------------------------------------------------------------------
# Bugfix regressions riding along this PR
# ---------------------------------------------------------------------------


class _ExpiredWait:
    """Event stand-in whose wait() always reports expiry — the future's
    batch resolves (sync dispatch inside result()) while the wait claims
    to have timed out, which is exactly the race being pinned."""

    def __init__(self):
        self._flag = False

    def set(self):
        self._flag = True

    def is_set(self):
        return self._flag

    def wait(self, timeout=None):
        return False


def test_result_timeout_race_with_own_resolution(stack):
    """result(timeout=) whose wait expires concurrently with the batch
    landing must return the response, not raise — and must not spuriously
    bump result_timeouts or write a timeout trace event."""
    sched = Scheduler(_server(stack, budget=0.2), max_batch_size=4)
    fut = sched.submit(requests_from_records(RECORDS[:1])[0])
    fut._resolved = _ExpiredWait()
    resp = fut.result(timeout=0.001)
    assert resp.text == _server(stack, budget=0.2).serve_requests(
        requests_from_records(RECORDS[:1]))[0].text
    assert sched.stats["result_timeouts"] == 0
    assert not any(e.get("event") == "timeout"
                   for e in sched.events if isinstance(e, dict))


def test_result_timeout_still_raises_when_unresolved(stack):
    """The legitimate-timeout side of the race fix: an actually-unserved
    future still raises, records the abandoned wait, and stays resolvable
    once the batch lands."""
    sched = Scheduler(_server(stack, budget=0.2), max_batch_size=2,
                      sync=False)
    try:
        blocker = threading.Event()
        inner = sched.server.backend
        orig = inner.generate

        def slow_generate(j, records, caps):
            blocker.wait(10.0)
            return orig(j, records, caps)

        inner.generate = slow_generate
        futs = [sched.submit(r) for r in requests_from_records(RECORDS[:2])]
        with pytest.raises(TimeoutError):
            futs[0].result(timeout=0.05)
        assert sched.stats["result_timeouts"] == 1
        blocker.set()
        assert futs[0].result(timeout=10.0).text  # later call resolves
    finally:
        sched.close()


def test_take_count_clamps_to_top_ladder_rung(stack):
    """max_batch_size above the ladder's top rung must never produce a
    batch beyond that rung (each one would compile a brand-new bucket in
    steady state); the remainder dispatches as a follow-on batch."""
    lad = BucketLadder(batch=(1, 2, 4))
    server = _server(stack, budget=0.2)
    sched = Scheduler(server, max_batch_size=8, ladder=lad)
    assert sched._take_count(8, 8) == 4  # forced past the top rung: clamped
    assert sched._take_count(8, 0) == 4
    assert sched._take_count(5, 2) == 4
    assert sched._take_count(3, 3) == 3  # padded up to the enclosing rung

    server.warm([(2, server.max_new_tokens), (4, server.max_new_tokens)])
    c0 = server.generate_compiles()["total"]
    reqs = requests_from_records(generate_dataset(6, seed=7))
    futs = [sched.submit(r) for r in reqs]
    sched.flush()  # forces all 6: clamp -> batch of 4 + follow-on of 2
    texts = [f.result().text for f in futs]
    assert sched.stats["dispatched_batches"] == 2
    assert sched.stats["dispatched_requests"] == 6
    assert server.generate_compiles()["total"] == c0  # zero new compiles
    offline = _server(stack, budget=0.2).serve_requests(reqs)
    assert texts == [r.text for r in offline]


def test_hedged_retry_counts_padding_once(stack):
    """padded_rows is charged once per *served* dispatch: a batch that
    pays a hedged retry must not double-count its padding."""
    reqs = requests_from_records(RECORDS[:3])
    probe = _server(stack, budget=0.2).serve_requests(reqs)
    member = int(np.flatnonzero(probe[0].mask)[0])  # guaranteed selected
    server = _server(stack, budget=0.2)
    server.backend = FailureInjector(server.backend, failures={member: (0,)})
    sched = Scheduler(server, max_batch_size=4)
    futs = [sched.submit(r) for r in reqs]
    sched.flush()
    for f in futs:
        f.result()
    assert sched.stats["hedges"] == 1  # the injection fired
    assert sched.stats["dispatched_batches"] == 1
    # 3 rows -> rung 4: one padding row, counted once — not once per attempt
    assert sched.stats["padded_rows"] == 1
