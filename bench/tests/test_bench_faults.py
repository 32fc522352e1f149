"""With the timed path broken underneath, the run comes out not correct:
once for each fault a serving cell can have."""

import jax.numpy as jnp
import pytest
from bench_tiny import run_tiny


def _stale_state(stack):
    """The fuser's decode step hands back its cache unchanged."""
    stream = stack.server.stream_fuser(stack.cfg["stream_capacity"])
    orig = stream._step

    def step():
        fn = orig()

        def run(params, tok, pos, done, cache):
            emit, tok, pos, done, _ = fn(params, tok, pos, done, cache)
            return emit, tok, pos, done, cache
        return run
    stream._step = step


def _altered_token(stack):
    """One slot's streamed token is changed where the step produces it."""
    stream = stack.server.stream_fuser(stack.cfg["stream_capacity"])
    orig = stream._step

    def step():
        fn = orig()

        def run(params, tok, pos, done, cache):
            emit, tok, pos, done, cache = fn(params, tok, pos, done, cache)
            first = jnp.arange(emit.shape[0]) == 0
            return jnp.where(first, (emit + 1) % 256, emit), tok, pos, done, cache
        return run
    stream._step = step


def _altered_score(stack):
    server = stack.server
    orig = server.predict_quality

    def predict(queries):
        s = orig(queries).copy()
        s[0] += 0.01 * abs(s).max()
        return s
    server.predict_quality = predict


def _altered_mask(stack):
    server = stack.server
    orig = server._select

    def select(*args, **kwargs):
        mask, names = orig(*args, **kwargs)
        mask = mask.copy()
        mask[0, 0] = ~mask[0, 0]
        return mask, names
    server._select = select


def _altered_member_answer(stack):
    server = stack.server
    orig = server._generate_members

    def generate(*args, **kwargs):
        out = orig(*args, **kwargs)
        row = out[0]
        j = next(j for j, t in enumerate(row) if t is not None)
        row[j] = row[j][:-1] + ("x" if row[j][-1:] != "x" else "y") if row[j] else "x"
        return out
    server._generate_members = generate


@pytest.mark.parametrize("fault, number", [
    (_stale_state, "fuse_gap"),
    (_altered_token, "fuse_gap"),
    (_altered_score, "score_err"),
    (_altered_mask, "mask_mismatch"),
    (_altered_member_answer, "member_mismatch"),
], ids=["stale-decode-state", "altered-token", "altered-score", "altered-mask",
        "altered-member-answer"])
def test_fault_makes_the_run_not_correct(fault, number):
    out = run_tiny(seed=21, seconds=1.5, fault=fault)
    assert not out["correct"]
    c = out["checks"][number]
    assert c["value"] > c["limit"], out["checks"]
