"""The cell's path end to end at a tiny size on the CPU."""

import json

from bench_tiny import run_tiny

from harness import cell


def test_tiny_cell_is_correct_and_reports_its_metrics(capsys):
    out = run_tiny(seed=2**33 + 5)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 16
    assert set(out["metrics"]) == {"fused_tok_s", "itl_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["device"]["platform"] == "cpu"
    cell.print_result(out)
    captured = capsys.readouterr()
    last = json.loads(captured.out.strip().splitlines()[-1])
    assert list(last)[-1] == "checks"
    assert captured.err.strip().splitlines()[-1].startswith("check fuse_gap")


def test_tiny_traced_run_reports_host_side_layers():
    out = run_tiny(seed=11, trace=True)
    assert out["correct"], out["checks"]
    m = out["metrics"]
    assert m["batch_rows_mean.sat"]["value"] == 8.0
    for name in ("predict_ms.sat", "select_ms.sat", "member_gen_ms.sat"):
        assert m[name]["value"] > 0
    assert m["window_compiles.sat"]["value"] >= 1
    # a CPU run writes no device metric: no TPU plane, no peaks
    for name in ("decode_step_roofline.sat", "serve_mfu.sat", "prefill_ms.sat"):
        assert name not in m
    assert "device_ops" in out["breakdown"] and "idle_gaps" in out["breakdown"]


def test_seed_gives_same_requests_and_answers(monkeypatch):
    """Two runs of one seed send the same requests and get the same
    selections and fused tokens; how many a window serves depends on the
    host's speed, so the requests both served are compared."""
    from harness import ensemble

    seen = []
    check = ensemble.check

    def capture(stack, served, *args, **kwargs):
        seen.append(served)
        return check(stack, served, *args, **kwargs)

    monkeypatch.setattr(ensemble, "check", capture)
    run_tiny(seed=3, seconds=1.0)
    run_tiny(seed=3, seconds=1.0)
    a, b = seen
    both = sorted(set(a) & set(b))
    assert len(both) >= 8
    for r in both:
        assert a[r].query == b[r].query
        assert a[r].tokens == b[r].tokens and len(a[r].tokens) == 32
        assert (a[r].mask == b[r].mask).all()
        assert a[r].member_texts == b[r].member_texts
