"""Static-shape serving fast path: bucketed jit dispatch, donated decode
caches, compile-cache behaviour through the Scheduler, and the
backtrack-free bitmask knapsack."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import configs
from repro.core import build_predictor, make_policy
from repro.core.knapsack import knapsack_reference, knapsack_select
from repro.data import DEFAULT_POOL, TOKENIZER, generate_dataset
from repro.models import build_model
from repro.serve import (
    BucketLadder,
    DecoderGenerateDispatcher,
    EncDecGenerateDispatcher,
    EnsembleServer,
    Scheduler,
    greedy_generate,
    greedy_generate_encdec,
    requests_from_records,
    spans,
)


@pytest.fixture(scope="module")
def decoder():
    cfg = configs.get("smollm-360m").reduced(dtype="float32")
    model = build_model(cfg)
    return model, model.init(jax.random.key(0))


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


# ---------------------------------------------------------------------------
# Bucket ladder
# ---------------------------------------------------------------------------


def test_bucket_ladder_rounding_and_growth():
    ladder = BucketLadder(batch=(1, 2, 4), new_tokens=(8, 32), prompt=(96,))
    assert ladder.batch_bucket(1) == 1
    assert ladder.batch_bucket(3) == 4
    assert ladder.batch_bucket(4) == 4
    assert ladder.batch_bucket(5) == 8  # beyond the ladder -> next pow2
    assert ladder.new_bucket(9) == 32
    assert ladder.prompt_bucket(96) == 96
    assert ladder.prompt_bucket(97) == 128


# ---------------------------------------------------------------------------
# Dispatcher correctness: padding + donated-cache reuse must be invisible
# ---------------------------------------------------------------------------


def test_decoder_dispatch_matches_direct(decoder):
    model, params = decoder
    dispatch = DecoderGenerateDispatcher(model, params)
    prompts = TOKENIZER.pad_batch(
        [TOKENIZER.encode("hello there", bos=True),
         TOKENIZER.encode("hi", bos=True),
         TOKENIZER.encode("a much longer prompt here", bos=True)], 30)
    fast = dispatch(prompts, max_new=5)  # b=3 -> bucket 4; s=30 -> 32; new 5 -> 8
    direct = greedy_generate(model, params, prompts, max_new=5)
    assert fast.shape == direct.shape == (3, 5)
    np.testing.assert_array_equal(fast, direct)


def test_decoder_dispatch_cache_reuse_is_clean(decoder):
    """Second same-bucket call reuses the donated cache; stale state from the
    first generation must not leak into the second."""
    model, params = decoder
    dispatch = DecoderGenerateDispatcher(model, params)
    a = TOKENIZER.pad_batch([TOKENIZER.encode("first query words", bos=True)], 16)
    b = TOKENIZER.pad_batch([TOKENIZER.encode("second", bos=True)], 16)
    dispatch(a, max_new=6)
    second = dispatch(b, max_new=6)
    np.testing.assert_array_equal(second, greedy_generate(model, params, b, max_new=6))
    assert dispatch.compiles == 1  # same bucket both times


def test_encdec_dispatch_matches_direct_and_reuses(fuser):
    model, params = fuser
    dispatch = EncDecGenerateDispatcher(model, params)
    enc = TOKENIZER.pad_batch(
        [TOKENIZER.encode("fuse this"), TOKENIZER.encode("and this"),
         TOKENIZER.encode("third row")], 16)
    first = dispatch(enc, max_new=5)
    np.testing.assert_array_equal(
        first, greedy_generate_encdec(model, params, enc, max_new=5))
    # same bucket again (3 -> batch bucket 4), fresh content, cache reused
    enc2 = TOKENIZER.pad_batch(
        [TOKENIZER.encode("other stuff"), TOKENIZER.encode("more"),
         TOKENIZER.encode("rows")], 16)
    again = dispatch(enc2, max_new=5)
    np.testing.assert_array_equal(
        again, greedy_generate_encdec(model, params, enc2, max_new=5))
    assert dispatch.compiles == 1


def test_dispatch_mega_batch_bypasses_buckets(decoder):
    """Batches beyond the top ladder rung (one-shot offline evals) run at
    their exact shape instead of padding to the next power of two and
    pinning an oversized donated cache."""
    model, params = decoder
    dispatch = DecoderGenerateDispatcher(
        model, params, ladder=BucketLadder(batch=(2,), new_tokens=(8,), prompt=(16,)))
    prompts = TOKENIZER.pad_batch(
        [TOKENIZER.encode(f"q{i}", bos=True) for i in range(3)], 12)
    out = dispatch(prompts, max_new=4)  # 3 > top rung 2 -> direct path
    np.testing.assert_array_equal(
        out, greedy_generate(model, params, prompts, max_new=4))
    assert dispatch.stats["direct_calls"] == 1
    assert dispatch.buckets == []  # no oversized bucket entry was cached


def test_dispatch_zero_recompiles_across_sizes(decoder):
    model, params = decoder
    dispatch = DecoderGenerateDispatcher(
        model, params, ladder=BucketLadder(batch=(4,), new_tokens=(8,), prompt=(16,)))
    for b in (2, 3, 4):
        prompts = TOKENIZER.pad_batch(
            [TOKENIZER.encode(f"q{i}", bos=True) for i in range(b)], 12)
        out = dispatch(prompts, max_new=4)
        assert out.shape == (b, 4)
    assert dispatch.compiles == 1
    assert dispatch.stats["calls"] == 3


def test_dispatch_warm_precompiles(fuser):
    model, params = fuser
    dispatch = EncDecGenerateDispatcher(model, params)
    dispatch.warm([(2, 16, 8)])
    assert dispatch.compiles == 1
    dispatch(
        TOKENIZER.pad_batch([TOKENIZER.encode("hello"), TOKENIZER.encode("hi")], 16),
        max_new=8,
    )
    assert dispatch.compiles == 1  # warm covered the (2, 16, 8) bucket


# ---------------------------------------------------------------------------
# Compile-cache behaviour through the Scheduler (acceptance criterion)
# ---------------------------------------------------------------------------


def test_scheduler_compiles_generate_once_across_micro_batches(stack):
    """Three consecutive differently-sized micro-batches that share one
    bucket must compile the generate callables exactly once: the second
    and third batches trigger zero new compilations."""
    pred, pp, fuser, fp = stack
    server = EnsembleServer(
        DEFAULT_POOL, make_policy("modi", budget=0.2), pred, pp, fuser, fp,
        bucket_ladder=BucketLadder(batch=(4,), new_tokens=(32,)),
    )
    sched = Scheduler(server, max_batch_size=4, max_wait_ticks=1)
    recs = generate_dataset(9, seed=21)
    counts = []
    for size, start in ((4, 0), (3, 4), (2, 7)):
        futures = [
            sched.submit(r)
            for r in requests_from_records(recs[start:start + size])
        ]
        sched.flush()
        for f in futures:
            f.result()
        counts.append(server.generate_compiles()["total"])
    assert counts[0] == 1  # first batch compiles the bucket
    assert counts[1] == counts[0]  # zero new compilations
    assert counts[2] == counts[0]


def test_server_warm_shapes_precompile(stack):
    pred, pp, fuser, fp = stack
    server = EnsembleServer(
        DEFAULT_POOL, make_policy("modi", budget=0.2), pred, pp, fuser, fp,
        warm_shapes=[(2, 32)],
    )
    assert server.generate_compiles()["total"] == 1
    assert server.predict_compiles == 1  # warm also built the predictor's rung
    server.serve_requests(requests_from_records(generate_dataset(2, seed=5)))
    assert server.generate_compiles()["total"] == 1  # bucket already warm
    assert server.predict_compiles == 1


# ---------------------------------------------------------------------------
# The predictor: one jitted program per batch rung
# ---------------------------------------------------------------------------


def test_predictor_compiles_once_per_rung(stack):
    """Batches of 8, 8, 3 and 4 rows build two predictor programs (rungs 8
    and 4), and a rung's repeated call fires no JAX compile event."""
    pred, pp, fuser, fp = stack
    server = EnsembleServer(DEFAULT_POOL, make_policy("modi", budget=0.2),
                            pred, pp, fuser, fp)
    queries = [r.query for r in generate_dataset(8, seed=23)]
    events = []

    def on_event(event, **kwargs):
        if event in spans.COUNTED_EVENTS:
            events.append(event)

    def on_duration(event, duration, **kwargs):
        if event in spans.TIMED_EVENTS:
            events.append(event)

    fired, counts = [], []
    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        for b in (8, 8, 3, 4):
            del events[:]
            assert server.predict_quality(queries[:b]).shape == (b, len(DEFAULT_POOL))
            fired.append(list(events))
            counts.append(server.predict_compiles)
    finally:
        jax.monitoring.unregister_event_listener(on_event)
        jax.monitoring.unregister_event_duration_listener(on_duration)
    assert counts == [1, 1, 2, 2]
    assert fired[1] == [] and fired[3] == []  # the rung's program is reused
    assert server.generate_compiles()["total"] == 0  # the generate paths' count


@pytest.mark.parametrize("b", [1, 3, 8])
def test_predict_quality_matches_eager_apply(stack, b):
    """Padding a batch to its rung leaves the real rows' scores as the
    eager, unpadded predictor gives them, up to float32 rounding (the
    compiled program fuses differently: scores near 0 differ by ~1e-7)."""
    pred, pp, fuser, fp = stack
    server = EnsembleServer(DEFAULT_POOL, make_policy("modi", budget=0.2),
                            pred, pp, fuser, fp)
    queries = [r.query for r in generate_dataset(b, seed=29)]
    toks = TOKENIZER.batch_encode(queries, server.max_query_len, cls=True)
    want = np.asarray(pred.apply(pp, jnp.asarray(toks)))
    got = server.predict_quality(queries)
    assert got.shape == want.shape == (b, len(DEFAULT_POOL))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_predictor_program_traces_highest_precision(stack):
    pred, pp, fuser, fp = stack
    server = EnsembleServer(DEFAULT_POOL, make_policy("modi", budget=0.2),
                            pred, pp, fuser, fp)
    tokens = jnp.zeros((8, server.max_query_len), jnp.int32)
    text = server._predict_fn.lower(pp, tokens).as_text()
    assert "dot_general" in text and "HIGHEST" in text


# ---------------------------------------------------------------------------
# Member-token cap plumbing (satellites: no hidden 64-token truncation,
# no double encode round trip)
# ---------------------------------------------------------------------------


def test_member_texts_respect_per_request_cap(stack):
    pred, pp, fuser, fp = stack
    server = EnsembleServer(DEFAULT_POOL, make_policy("llm-blender"),
                            pred, pp, fuser, fp)
    rec = generate_dataset(1, seed=17)[0]
    resp = server.serve_requests(
        requests_from_records([rec], max_new_tokens=6))[0]
    assert all(t is None or len(TOKENIZER.encode(t)) <= 6
               for t in resp.member_texts)


def test_long_member_outputs_not_truncated_at_64(stack):
    """The old fusion path hardcoded a 64-token member cap; responses longer
    than 64 tokens must now survive into fusion intact."""
    from repro.data.mixinstruct import DOMAIN_NAMES, Record

    pred, pp, fuser, fp = stack
    server = EnsembleServer(DEFAULT_POOL, make_policy("llm-blender"),
                            pred, pp, fuser, fp, max_new_tokens=128)
    rec = Record(query="summarize the plan",
                 reference="the quick brown fox jumps over the lazy dog " * 3,
                 domain=DOMAIN_NAMES[0], domain_id=0)
    assert len(rec.reference.encode()) > 64
    resp = server.serve_requests(requests_from_records([rec]))[0]
    longest = max(len(TOKENIZER.encode(t))
                  for t in resp.member_texts if t is not None)
    assert longest > 64  # would have been clamped to 64 before

    capped = EnsembleServer(DEFAULT_POOL, make_policy("llm-blender"),
                            pred, pp, fuser, fp, max_new_tokens=128,
                            max_member_tokens=16)
    assert capped.max_member_tokens == 16


def test_sim_backend_truncates_per_row():
    from repro.serve import SimBackend

    sim = SimBackend(DEFAULT_POOL, seed=3)
    recs = generate_dataset(3, seed=9)
    texts = sim.generate(2, recs, [4, 8, 64])
    assert all(len(TOKENIZER.encode(t)) <= c for t, c in zip(texts, [4, 8, 64]))
    # int cap still accepted (protocol compatibility)
    uniform = sim.generate(2, recs, 8)
    assert all(len(TOKENIZER.encode(t)) <= 8 for t in uniform)


def test_decode_capped_never_inflates_past_cap():
    """Cutting a multi-byte UTF-8 char must not fabricate U+FFFD (3 bytes on
    re-encode): truncated texts stay within the token cap even for
    non-ASCII content."""
    for text, cap in [("café au lait", 4), ("中日한", 4), ("naïve", 3),
                      ("🎉party", 2), ("plain ascii", 5)]:
        ids = TOKENIZER.encode(text)
        capped = TOKENIZER.decode_capped(ids, cap)
        assert len(TOKENIZER.encode(capped)) <= cap, (text, cap, capped)
        assert "�" not in capped
        # naive truncate-and-decode overflows for the café case — the bug
    assert len(TOKENIZER.encode(TOKENIZER.decode(TOKENIZER.encode("café")[:4]))) > 4


# ---------------------------------------------------------------------------
# Padding invariance: a request's tokens are independent of rung/position
# ---------------------------------------------------------------------------

_PAD_INV_CACHE = {}


def _pad_inv_dispatcher() -> DecoderGenerateDispatcher:
    # built lazily (not a fixture: the hypothesis shim's @given wraps the
    # test into a zero-arg runner), shared across examples so each bucket
    # compiles exactly once
    if "d" not in _PAD_INV_CACHE:
        cfg = configs.get("smollm-360m").reduced(dtype="float32")
        model = build_model(cfg)
        _PAD_INV_CACHE["d"] = DecoderGenerateDispatcher(
            model, model.init(jax.random.key(2)))
    return _PAD_INV_CACHE["d"]


@settings(max_examples=8, deadline=None)
@given(
    extra=st.integers(0, 4),  # batch sizes 1..5 -> bucket rungs 1, 2, 4, 8
    pos=st.integers(0, 4),
    seed=st.integers(0, 2**20),
)
def test_bucketed_dispatch_padding_invariant(extra, pos, seed):
    """A row's generated tokens are identical regardless of which bucket
    rung the batch pads to and which batch position the row occupies:
    batch-of-k at position p == batch-of-1, across rungs."""
    dispatch = _pad_inv_dispatcher()
    rng = np.random.default_rng(seed)
    words = ["alpha", "beta", "gamma", "delta", "echo", "fox", "golf", "hotel"]
    queries = [" ".join(rng.choice(words, size=rng.integers(1, 4)))
               for _ in range(extra + 1)]
    pos = min(pos, extra)
    prompts = TOKENIZER.pad_batch(
        [TOKENIZER.encode(q, bos=True) for q in queries], 16)
    full = dispatch(prompts, max_new=6)
    solo = dispatch(prompts[pos:pos + 1], max_new=6)
    np.testing.assert_array_equal(full[pos], solo[0])


# ---------------------------------------------------------------------------
# Bitmask knapsack (satellite: exact selection equivalence + memory bound)
# ---------------------------------------------------------------------------


@settings(max_examples=20, deadline=None)
@given(
    n=st.integers(2, 48),  # past 32 exercises the multi-word (W=2) mask path
    budget=st.integers(1, 64),
    seed=st.integers(0, 2**31 - 1),
)
def test_bitmask_knapsack_selection_matches_reference(n, budget, seed):
    """Selections (not just values) match Algorithm 1 exactly, including the
    ties-keep-not-taken backtrack rule — integer profits force ties."""
    rng = np.random.default_rng(seed)
    profits = rng.integers(1, 5, (1, n)).astype(np.float32)
    costs = rng.integers(1, budget + 8, (1, n)).astype(np.int32)
    sel = np.asarray(knapsack_select(jnp.asarray(profits), jnp.asarray(costs), budget))[0]
    ref = knapsack_reference(
        [{"cost": int(costs[0, i]), "target_score": float(profits[0, i]), "i": i}
         for i in range(n)], budget)
    ref_mask = np.zeros(n, bool)
    ref_mask[[m["i"] for m in ref]] = True
    np.testing.assert_array_equal(sel, ref_mask)


def test_bitmask_knapsack_allocates_no_take_tensor():
    """Peak live state is O(Q·(B+1)) DP+bitmask rows: no intermediate in the
    jaxpr has the [N, Q, B+1] (or [Q, N, B+1]) take-tensor shape."""
    q, n, budget = 4, 12, 48
    bp1 = budget + 1
    jaxpr = jax.make_jaxpr(
        lambda p, c: knapsack_select(p, c, budget)
    )(jnp.zeros((q, n), jnp.float32), jnp.ones((q, n), jnp.int32))
    forbidden = {(n, q, bp1), (q, n, bp1)}

    def walk(jxp):
        for eqn in jxp.eqns:
            for var in eqn.outvars:
                assert tuple(var.aval.shape) not in forbidden, (
                    f"take tensor materialized: {var.aval.shape}")
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
