"""The serving path's tracer (``repro.serve.spans``): off means off, spans
nest on their thread, request and batch ids join a request to the spans
of the batch that served it, the ring keeps its capacity, and compiles
are charged to the span that asked for them."""

import dataclasses
import json
import threading

import jax
import jax.numpy as jnp
import pytest

from repro import configs
from repro.core import build_predictor, make_policy
from repro.data import DEFAULT_POOL, generate_dataset
from repro.models import build_model
from repro.serve import EnsembleServer, Scheduler, requests_from_records, spans


@pytest.fixture(autouse=True)
def tracer_off():
    spans.disable()
    yield
    spans.disable()


@pytest.fixture(scope="module")
def stack():
    pred = build_predictor(num_models=len(DEFAULT_POOL))
    pp = pred.init(jax.random.key(0))
    fuser = build_model(configs.get("gen-fuser"))
    fp = fuser.init(jax.random.key(1))
    return pred, pp, fuser, fp


def _serve(stack, n=8, batch=4, sync=False):
    pred, pp, fuser, fp = stack
    server = EnsembleServer(DEFAULT_POOL, make_policy("modi", budget=0.2),
                            pred, pp, fuser, fp)
    sched = Scheduler(server, max_batch_size=batch, stream=True,
                      stream_capacity=batch, sync=sync)
    reqs = [dataclasses.replace(r, max_new_tokens=8)
            for r in requests_from_records(generate_dataset(n, seed=5))]
    futures = [sched.submit(r) for r in reqs]
    sched.flush()
    responses = [f.result(timeout=300) for f in futures]
    sched.close()
    return futures, responses


def _by_id(recs):
    return {r["id"]: r for r in recs}


def _ancestors(rec, by_id):
    out = []
    while rec["parent"] is not None:
        rec = by_id[rec["parent"]]
        out.append(rec)
    return out


def test_off_records_nothing_enters_no_annotation_registers_no_listener(
        stack, monkeypatch):
    touched = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        lambda *a, **k: touched.append(("annotation", a)))
    monkeypatch.setattr(jax.monitoring, "register_event_listener",
                        lambda *a, **k: touched.append(("listener", a)))
    monkeypatch.setattr(jax.monitoring, "register_event_duration_secs_listener",
                        lambda *a, **k: touched.append(("listener", a)))
    with spans.span("serve.outer", rows=2) as outer:
        with spans.span("serve.inner"):
            pass
    req = spans.start("serve.request", req=0)
    req.set(batch=0)
    req.end()
    futures, responses = _serve(stack, n=4)
    assert not spans.TRACER._on
    assert spans.records() == [] and touched == []
    # the spans still time themselves: timing is read from them
    assert outer.seconds >= 0 and req.seconds >= 0
    assert all(r.timing["predict_s"] > 0 and r.timing["fuse_s"] > 0 for r in responses)


def test_spans_nest_with_parent_ids():
    spans.enable()
    with spans.span("serve.a") as a:
        with spans.span("serve.b"):
            with spans.span("serve.c"):
                pass
        with spans.span("serve.d"):
            pass
    other = []

    def elsewhere():
        with spans.span("serve.e"):
            pass
        other.append(threading.get_ident())
    t = threading.Thread(target=elsewhere)
    t.start()
    t.join(timeout=30)
    assert not t.is_alive()
    recs = {r["name"]: r for r in spans.records()}
    assert recs["serve.a"]["parent"] is None
    assert recs["serve.b"]["parent"] == recs["serve.a"]["id"]
    assert recs["serve.c"]["parent"] == recs["serve.b"]["id"]
    assert recs["serve.d"]["parent"] == recs["serve.a"]["id"]
    # another thread's span does not nest under this thread's open span
    assert recs["serve.e"]["parent"] is None and recs["serve.e"]["thread"] == other[0]
    a_rec = recs["serve.a"]
    assert (a_rec["start_ns"], a_rec["end_ns"]) == (a.start_ns, a.end_ns)
    for name in ("serve.b", "serve.c", "serve.d"):
        r = recs[name]
        assert a_rec["start_ns"] <= r["start_ns"] <= r["end_ns"] <= a_rec["end_ns"]


def test_request_and_batch_ids_join_a_request_to_its_batch(stack, tmp_path):
    spans.enable()
    futures, responses = _serve(stack, n=8, batch=4)
    path = tmp_path / "spans.jsonl"
    n = spans.dump(str(path))
    recs = spans.records()
    by_id = _by_id(recs)
    batches = {r["attrs"]["batch"]: r for r in recs if r["name"] == "serve.batch"}
    assert len(batches) == 2 and all(b["attrs"]["rows"] == 4 for b in batches.values())
    requests = {r["attrs"]["req"]: r for r in recs if r["name"] == "serve.request"}
    assert sorted(requests) == [f.seq for f in futures]
    for f, resp in zip(futures, responses):
        req = requests[f.seq]
        batch = batches[req["attrs"]["batch"]]
        assert req["start_ns"] <= req["attrs"]["service_ns"] == batch["start_ns"]
        assert req["attrs"]["service_ns"] <= req["attrs"]["first_token_ns"] <= req["end_ns"]
        # the batch's stage spans are its descendants, and timing is theirs
        stages = {r["name"]: r for r in recs
                  if batch in _ancestors(r, by_id) and r["name"] in (
                      "serve.predict", "serve.select", "serve.members", "serve.fuse")}
        assert set(stages) == {"serve.predict", "serve.select", "serve.members", "serve.fuse"}
        for key, name in (("predict_s", "serve.predict"), ("select_s", "serve.select"),
                          ("generate_s", "serve.members"), ("fuse_s", "serve.fuse")):
            rec = stages[name]
            assert resp.timing[key] == (rec["end_ns"] - rec["start_ns"]) / 1e9
    for name in ("serve.predict.apply", "serve.predict.read", "serve.select.solve",
                 "serve.select.read", "serve.fusion_inputs", "serve.fuser.prefill",
                 "serve.fuser.join", "serve.fuser.step.launch", "serve.fuser.step.read",
                 "serve.fuser.step.emit", "serve.settle"):
        named = [r for r in recs if r["name"] == name]
        assert named, name
        assert all(any(a["name"] == "serve.batch" for a in _ancestors(r, by_id))
                   for r in named), name
    lines = path.read_text().splitlines()
    assert len(lines) == n + 1 and n == len(recs)
    assert len(json.loads(lines[0])["anchors"]) == 2  # at enable and at dump
    assert json.loads(lines[1]) == recs[0]


def test_ring_stays_at_its_capacity(monkeypatch):
    monkeypatch.setattr(spans, "CAPACITY", 5)
    spans.enable()
    for k in range(20):
        with spans.span("serve.x", rows=k):
            pass
    recs = spans.records()
    assert [r["attrs"]["rows"] for r in recs] == [15, 16, 17, 18, 19]


def test_recording_annotates_nested_spans_in_nesting_order(monkeypatch):
    log = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            log.append(("enter", self.name))

        def __exit__(self, *exc):
            log.append(("exit", self.name))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    spans.enable()
    with spans.span("serve.a"):
        with spans.span("serve.b"):
            with spans.span("serve.c"):
                pass
        req = spans.start("serve.request", req=0)  # not nested: no annotation
        with spans.span("serve.d"):
            pass
        req.end()
    assert log == [("enter", "serve.a"), ("enter", "serve.b"), ("enter", "serve.c"),
                   ("exit", "serve.c"), ("exit", "serve.b"), ("enter", "serve.d"),
                   ("exit", "serve.d"), ("exit", "serve.a")]
    assert {r["name"] for r in spans.records()} == {
        "serve.a", "serve.b", "serve.c", "serve.d", "serve.request"}


def test_retraced_jit_charges_its_compiles_to_the_calling_span():
    spans.enable()
    x = jnp.arange(4.0)
    with spans.span("serve.parent"):
        with spans.span("serve.child"):
            for k in range(3):
                # a new closure per call: traced and compiled every time
                jax.jit(lambda v, k=k: v * k + 1.0)(x).block_until_ready()
    spans.disable()
    recs = {r["name"]: r["attrs"] for r in spans.records()}
    child, parent = recs["serve.child"], recs["serve.parent"]
    for key in ("jaxpr_trace_duration", "jaxpr_to_mlir_module_duration",
                "backend_compile_duration"):
        assert child[key] > 0, key
        assert key not in parent, key
    assert "compile_requests_use_cache" not in parent
    # off again: the listener is gone
    from jax._src import monitoring

    assert spans.TRACER._on_event not in monitoring.get_event_listeners()
    assert spans.TRACER._on_duration not in monitoring.get_event_duration_listeners()
