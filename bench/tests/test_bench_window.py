"""Window arithmetic: rates over the whole window, gaps over every pair."""

import pytest

from harness import window


def _steady(n_req=4, step=0.01, tokens=50, stall_at=None, stall=0.0):
    events, t = [], 0.0
    for k in range(tokens):
        if stall_at is not None and k == stall_at:
            t += stall
        t += step
        for r in range(n_req):
            events.append((t, r))
    return events


def test_rate_counts_every_token_over_the_whole_window():
    events = _steady()
    times, _ = window.split_window(events, 0.0, 1.0)
    assert window.tokens_per_s(times, 0.0, 1.0) == pytest.approx(200 / 1.0)


def test_rate_drops_when_a_stall_is_injected():
    base, _ = window.split_window(_steady(), 0.0, 0.6)
    stalled, _ = window.split_window(_steady(stall_at=10, stall=0.2), 0.0, 0.6)
    assert window.tokens_per_s(stalled, 0.0, 0.6) < window.tokens_per_s(base, 0.0, 0.6)


def test_gaps_are_taken_over_every_pair_of_every_request():
    _, per = window.split_window(_steady(n_req=3, tokens=5), 0.0, 1.0)
    gaps = window.inter_token_gaps(per, 0.0, 1.0)
    assert len(gaps) == 3 * 4
    assert all(g == pytest.approx(0.01) for g in gaps)


def test_a_stall_shows_in_the_gap_tail():
    _, per = window.split_window(_steady(n_req=2, tokens=30, stall_at=5, stall=0.5), 0.0, 2.0)
    gaps = window.inter_token_gaps(per, 0.0, 2.0)
    assert max(gaps) == pytest.approx(0.51)
    assert window.percentile(gaps, 100) == pytest.approx(0.51)
    assert window.percentile(gaps, 50) == pytest.approx(0.01)


def test_tokens_outside_the_window_are_left_out():
    events = [(0.5, 0), (1.5, 0), (2.5, 0)]
    times, per = window.split_window(events, 1.0, 2.0)
    assert window.tokens_per_s(times, 1.0, 2.0) == 1.0
    assert window.inter_token_gaps(per, 1.0, 2.0) == []


@pytest.mark.parametrize("q, want", [(50, 3), (95, 5), (100, 5), (1, 1)])
def test_percentile_nearest_rank(q, want):
    assert window.percentile([5, 1, 4, 2, 3], q) == want

