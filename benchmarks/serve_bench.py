"""Serving-path benchmark: latency and recompile counts through the
continuous-batching Scheduler, exercising the static-shape fast path end
to end (bucketed jit dispatch + donated decode caches in serve.dispatch).

Two modes, both writing ``BENCH_serve.json`` so the perf trajectory
accumulates per PR:

* default — the micro-batch latency probe from PR 2
  (first/steady-state batch latency, compile counters), plus a streaming
  probe through the persistent in-flight decode state reporting
  ``ttft_ms`` (median time-to-first-token) against the batch-boundary
  baseline, ``decode_step_p99_ms`` (from the tracer's
  ``serve.fuser.step`` spans), and the steady-state
  ``generate_compiles`` gate (must stay 0);
* ``--scenario steady|bursty|heavy-tail|failure`` — drive the
  deterministic traffic simulator (:mod:`repro.serve.traffic`) through
  the deadline-aware Scheduler and report p50/p99 request latency,
  deadline-miss rate, shed rate, hedge counts, and steady-state
  recompiles for that scenario.
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro import configs
from repro.core import build_predictor, make_policy
from repro.data import DEFAULT_POOL, generate_dataset
from repro.models import build_model
from repro.serve import (
    AdmissionControl,
    EnsembleServer,
    Scheduler,
    TrafficSimulator,
    preset_scenarios,
    requests_from_records,
    spans,
)


def _build_server(budget: float) -> EnsembleServer:
    pred = build_predictor(num_models=len(DEFAULT_POOL))
    pp = pred.init(jax.random.key(0))
    fuser = build_model(configs.get("gen-fuser"))
    fp = fuser.init(jax.random.key(1))
    return EnsembleServer(DEFAULT_POOL, make_policy("modi", budget=budget),
                          pred, pp, fuser, fp)


def run(n_batches: int = 8, batch_size: int = 4, budget: float = 0.2,
        out_path: str = "BENCH_serve.json", log=print):
    """Micro-batch latency probe (PR 2's metric, kept for trajectory)."""
    server = _build_server(budget)
    scheduler = Scheduler(server, max_batch_size=batch_size)

    records = generate_dataset(n_batches * batch_size, seed=1234)
    per_batch_s = []
    compiles_after_first = None
    for k in range(n_batches):
        reqs = requests_from_records(records[k * batch_size:(k + 1) * batch_size])
        t0 = time.perf_counter()
        futures = [scheduler.submit(r) for r in reqs]
        scheduler.flush()
        for f in futures:
            f.result()
        per_batch_s.append(time.perf_counter() - t0)
        if k == 0:
            compiles_after_first = server.generate_compiles()["total"]
        log(f"serve batch {k}: {per_batch_s[-1]*1e3:8.1f} ms  "
            f"compiles={server.generate_compiles()['total']}")

    steady = float(np.median(per_batch_s[1:])) if n_batches > 1 else per_batch_s[0]

    # --- streaming probe: token-level continuous batching through the
    # persistent in-flight decode state.  TTFT is wall time from submit to
    # a request's first fused token; the batch-boundary baseline only
    # surfaces its first token when the whole batch settles, so its TTFT
    # is the steady-state batch latency measured above.
    stream_server = _build_server(budget)
    stream_sched = Scheduler(stream_server, max_batch_size=batch_size,
                             stream=True, stream_capacity=batch_size)
    fuser = stream_server.stream_fuser(capacity=batch_size)
    ladder = stream_server.bucket_ladder
    fuser.warm(sorted({ladder.batch_bucket(b)
                       for b in range(1, batch_size + 1)}))
    compiles_after_warm = stream_server.generate_compiles()["total"]
    spans.enable()
    ttft_s = []
    for k in range(n_batches):
        reqs = requests_from_records(records[k * batch_size:(k + 1) * batch_size])
        futures = [stream_sched.submit(r) for r in reqs]
        stream_sched.flush()
        for f in futures:
            f.result()
        ttft_s.extend(f.ttft_s for f in futures if f.ttft_s is not None)
    spans.disable()
    step_walls = [(r["end_ns"] - r["start_ns"]) / 1e9 for r in spans.records()
                  if r["name"] == "serve.fuser.step"]
    ttft_ms = float(np.median(ttft_s)) * 1e3 if ttft_s else 0.0
    decode_step_p99_ms = (float(np.percentile(step_walls, 99)) * 1e3
                          if step_walls else 0.0)
    # steady-state recompiles on the streaming path — the continuous-batch
    # acceptance gate (CI fails on > 0)
    stream_compiles = (stream_server.generate_compiles()["total"]
                       - compiles_after_warm)

    result = {
        "batch_size": batch_size,
        "n_batches": n_batches,
        "per_batch_s": per_batch_s,
        "first_batch_s": per_batch_s[0],
        "steady_state_s": steady,
        "per_request_steady_s": steady / batch_size,
        "speedup": per_batch_s[0] / max(steady, 1e-9),
        "compiles_after_first": compiles_after_first,
        "compiles_final": server.generate_compiles()["total"],
        "fuser_buckets": [list(b) for b in server.fuser_dispatch.buckets]
        if server.fuser_dispatch else [],
        "ttft_ms": ttft_ms,
        "ttft_batch_boundary_ms": steady * 1e3,
        "ttft_speedup": (steady * 1e3) / max(ttft_ms, 1e-9),
        "decode_step_p99_ms": decode_step_p99_ms,
        "decode_steps": len(step_walls),
        "generate_compiles": stream_compiles,
        "stream_tokens": stream_sched.stats["stream_tokens"],
        "backend": "sim",
    }
    with open(out_path, "w") as f:
        json.dump(result, f, indent=2)
    log(f"wrote {out_path}: first={result['first_batch_s']*1e3:.1f}ms "
        f"steady={steady*1e3:.1f}ms speedup={result['speedup']:.1f}x "
        f"recompiles_after_warm={result['compiles_final'] - compiles_after_first} "
        f"ttft={ttft_ms:.1f}ms (batch-boundary {steady*1e3:.1f}ms) "
        f"step_p99={decode_step_p99_ms:.2f}ms stream_recompiles={stream_compiles}")
    rows = [
        ("serve_first_batch", result["first_batch_s"] * 1e6,
         f"compile-inclusive b={batch_size}"),
        ("serve_steady_batch", steady * 1e6,
         f"speedup={result['speedup']:.1f}x "
         f"recompiles={result['compiles_final'] - compiles_after_first}"),
        ("serve_stream_ttft", ttft_ms * 1e3,
         f"vs batch-boundary {steady*1e3:.1f}ms "
         f"step_p99={decode_step_p99_ms:.2f}ms "
         f"stream_recompiles={stream_compiles}"),
    ]
    return rows


def run_scenario(scenario_name: str, n_requests: int = 24, batch_size: int = 4,
                 budget: float = 0.2, max_wait_ticks: int = 2,
                 admission_budget: float | None = None,
                 out_path: str = "BENCH_serve.json", log=print):
    """Scenario mode: simulate one named traffic scenario and report the
    serving SLO metrics (p50/p99 latency, deadline-miss rate, shed rate)
    plus steady-state recompile counts."""
    scenarios = preset_scenarios(n_requests=n_requests)
    if scenario_name not in scenarios:
        raise SystemExit(
            f"unknown scenario {scenario_name!r}; pick from "
            f"{', '.join(sorted(scenarios))}")
    scenario = scenarios[scenario_name]
    server = _build_server(budget)
    # warm every rung a scheduler batch can land on, so recompiles measure
    # steady-state behaviour rather than cold-start compiles
    ladder = server.bucket_ladder
    rungs = sorted({ladder.batch_bucket(b) for b in range(1, batch_size + 1)})
    server.warm([(b, server.max_new_tokens) for b in rungs])
    compiles_after_warm = server.generate_compiles()["total"]

    admission = None
    if admission_budget is not None:
        admission = AdmissionControl(window_ticks=max(4, max_wait_ticks * 2),
                                     downgrade_fraction=admission_budget,
                                     downgrade_budget=budget / 2,
                                     shed_fraction=min(1.0, admission_budget * 2))
    scheduler = Scheduler(server, max_batch_size=batch_size,
                          max_wait_ticks=max_wait_ticks, admission=admission)
    records = generate_dataset(max(n_requests, 16), seed=1234)
    t0 = time.perf_counter()
    report = TrafficSimulator(scheduler, scenario, records).run()
    wall = time.perf_counter() - t0

    unresolved = sum(r is None and e is None
                     for r, e in zip(report.responses, report.errors))
    compiles_final = report.compiles["total"]
    result = {
        "scenario": scenario_name,
        "n_requests": report.n,
        "served": report.served,
        "unresolved_futures": unresolved,  # acceptance: must be 0
        "ticks": report.ticks,
        "wall_s": wall,
        **report.latency_percentiles(),
        "deadline_miss_rate": report.deadline_miss_rate,
        "shed_rate": report.shed_rate,
        "hedges": report.stats["hedges"],
        "downgraded": report.stats["downgraded"],
        "dispatched_batches": report.stats["dispatched_batches"],
        "padded_rows": report.stats["padded_rows"],
        "compiles_after_warm": compiles_after_warm,
        "compiles_final": compiles_final,
        "steady_state_recompiles": compiles_final - compiles_after_warm,
        "backend": "sim",
    }
    with open(out_path, "w") as f:
        json.dump(result, f, indent=2)
    log(f"wrote {out_path}: scenario={scenario_name} "
        f"p50={result['p50_latency_s']*1e3:.1f}ms "
        f"p99={result['p99_latency_s']*1e3:.1f}ms "
        f"miss_rate={result['deadline_miss_rate']:.2f} "
        f"shed_rate={result['shed_rate']:.2f} "
        f"recompiles={result['steady_state_recompiles']}")
    return [
        (f"serve_{scenario_name}_p50", result["p50_latency_s"] * 1e6,
         f"p99={result['p99_latency_s']*1e6:.0f}us "
         f"miss={result['deadline_miss_rate']:.2f} "
         f"shed={result['shed_rate']:.2f} "
         f"recompiles={result['steady_state_recompiles']}"),
    ]


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--scenario", type=str, default=None,
                    help="traffic scenario: steady, bursty, heavy-tail, failure")
    ap.add_argument("--n-requests", type=int, default=24)
    ap.add_argument("--batch-size", type=int, default=4)
    ap.add_argument("--budget", type=float, default=0.2)
    ap.add_argument("--max-wait-ticks", type=int, default=2)
    ap.add_argument("--admission-budget", type=float, default=None,
                    help="window downgrade threshold (fraction of full cost)")
    args = ap.parse_args()
    if args.scenario:
        run_scenario(args.scenario, n_requests=args.n_requests,
                     batch_size=args.batch_size, budget=args.budget,
                     max_wait_ticks=args.max_wait_ticks,
                     admission_budget=args.admission_budget)
    else:
        run(batch_size=args.batch_size, budget=args.budget)
