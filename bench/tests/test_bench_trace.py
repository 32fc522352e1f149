"""Trace reduction: busy union, idle gaps, and device time by host span,
on a small trace recorded on a TPU v5e and on hand-made events."""

import json
import pathlib

import pytest

from harness import trace

DATA = pathlib.Path(__file__).parent / "data"


def _ev(plane, line, name, start, dur):
    return {"plane": plane, "line": line, "name": name, "start_ns": start, "dur_ns": dur}


def _hand():
    host, dev = "/host:CPU", "/device:TPU:0"
    return [
        _ev(host, "python", "bench.window", 0, 1000),
        _ev(host, "python", "bench.predict", 100, 200),
        _ev(host, "python", "bench.decode", 400, 500),
        _ev(dev, "XLA Ops", "while", 140, 160),  # a loop around the next two
        _ev(dev, "XLA Ops", "dot", 150, 100),  # under predict
        _ev(dev, "XLA Ops", "add", 250, 50),
        _ev(dev, "XLA Ops", "fusion", 450, 200),  # under decode
        _ev(dev, "XLA Modules", "jit_run", 450, 200),
        _ev(dev, "XLA Modules", "jit_run", 700, 100),
        _ev(dev, "XLA Ops", "fusion", 700, 100),
        _ev(dev, "XLA Ops", "late", 950, 100),  # runs past the window's end
    ]


def test_union_and_gaps():
    assert trace.union_ns([(0, 10), (5, 20), (30, 40)], 0, 100) == 30
    assert trace.gaps_ns([(0, 10), (5, 20), (30, 40)], 0, 50) == [(20, 30), (40, 50)]


def test_reduce_hand_made():
    r = trace.reduce(_hand())
    assert r.window_ns == (0, 1000)
    assert r.busy_ns[0] == 160 + 200 + 100 + 50
    assert r.idle_share(0) == pytest.approx(1 - 510 / 1000)
    # the loop's own event is not counted on top of the operations inside it
    assert r.op_ns_by_span == {"bench.predict": 150, "bench.decode": 300, "host": 50}
    assert trace.per_span_program_ms(r, "bench.decode") == (0.0003, 2)
    gaps = [(n, round(s * 1e9)) for n, s in r.breakdown["idle_gaps"]]
    assert gaps == [("host", 150), ("bench.decode", 150), ("host", 140), ("bench.decode", 50)]


def test_device_clock_is_moved_onto_the_hosts():
    host, dev = "/host:CPU", "/device:TPU:0"
    events = [
        _ev(host, "python", "bench.window", 0, 10_000),
        _ev(host, "python", "bench.predict", 1000, 2000),
        _ev(host, "python", "bench.decode", 3000, 4000),
        # the device's clock reads 1500 ns early: its runs look launched
        # before the host span that launched them
        dict(_ev(dev, "XLA Modules", "jit_run", 2000, 800), corr=7),
        _ev(dev, "XLA Ops", "fusion", 2000, 800),
        dict(_ev(host, "runtime", "CompleteCallbacks", 4400, 10), corr=7),
    ]
    r = trace.reduce(events)
    assert r.clock_offset_ns == 1600
    assert r.op_ns_by_span == {"bench.decode": 800}
    assert trace.per_span_program_ms(r, "bench.decode") == (0.0008, 1)


def test_reduce_needs_a_window():
    with pytest.raises(ValueError):
        trace.reduce([e for e in _hand() if e["name"] != "bench.window"])


def test_reduce_recorded_chip_trace():
    rec = json.loads((DATA / "trace_v5e_slice.json").read_text())
    r = trace.reduce(rec["events"])
    want = rec["expected"]
    # the device's clock ran behind the host's; moved onto it, the encoder
    # prefill program starts inside the host span that launched it
    assert r.clock_offset_ns == want["clock_offset_ns"] > 0
    assert r.busy_ns[0] == want["busy_ns"]
    ms, runs = trace.per_span_program_ms(r, "bench.prefill")
    assert runs == want["prefill_runs"] and ms == pytest.approx(want["prefill_ms"])
    (span_start, span_end), = r.spans.named("bench.prefill", 0, 2**62)
    start = [s for name, s, _ in r.modules if name == "bench.prefill"][0]
    assert span_start <= start < span_end
    assert all(op.startswith("bench.prefill:") for op, _ in r.breakdown["device_ops"])
    assert trace.per_span_program_ms(r, "bench.members") is None
