"""Benchmark harness — one entry per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--fast] [--only knapsack,serve]

Prints ``name,us_per_call,derived`` CSV rows.  Table 1 / budget-sweep train
the paper stack on first run (cached in experiments/checkpoints/).

``--only`` selects a comma-separated subset of sections
(knapsack, serve, cluster, table1, sweep, roofline) — the CI bench smoke
job runs ``--fast --only knapsack,serve,cluster`` and uploads the
``BENCH_*.json`` artifacts (BENCH_knapsack.json, BENCH_serve.json,
BENCH_serve_cluster.json) each section writes, so the perf trajectory
accumulates per PR.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

SECTIONS = ("knapsack", "serve", "cluster", "table1", "sweep", "roofline")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true", help="smaller eval sets / training")
    ap.add_argument("--only", type=str, default=None,
                    help=f"comma-separated subset of {', '.join(SECTIONS)}")
    args = ap.parse_args()
    steps = 300 if args.fast else 500
    n1 = 120 if args.fast else 400
    n2 = 60 if args.fast else 200
    selected = set(args.only.split(",")) if args.only else set(SECTIONS)
    unknown = selected - set(SECTIONS)
    if unknown:
        ap.error(f"unknown sections: {', '.join(sorted(unknown))}")
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    rows = []

    if "knapsack" in selected:
        from benchmarks import knapsack_bench

        print("\n### knapsack microbenchmark (paper Algorithm 1)")
        kn_rows = knapsack_bench.run()
        rows += kn_rows
        with open("BENCH_knapsack.json", "w") as f:
            json.dump([{"name": n, "us_per_call": us, "derived": d}
                       for n, us, d in kn_rows], f, indent=2)

    if "serve" in selected:
        from benchmarks import serve_bench

        print("\n### serving fast path (Scheduler latency / recompiles)")
        rows += serve_bench.run(
            n_batches=5 if args.fast else 8, batch_size=4,
        )
        print("\n### traffic scenario (continuous batching under load)")
        rows += serve_bench.run_scenario(
            "bursty", n_requests=16 if args.fast else 32,
            out_path="BENCH_serve_scenario.json",
        )

    if "cluster" in selected:
        from benchmarks import cluster_bench

        print("\n### cluster serving (async dispatch / placement / host failover)")
        rows += cluster_bench.run(n_requests=12 if args.fast else 24)

    if "table1" in selected:
        from benchmarks import table1

        print("\n### Table 1 reproduction")
        t1 = table1.run(n_test=n1, train_steps=steps)
        rows.append(("table1_modi_bartscore", 0.0,
                     f"modi={t1['MODI']['bartscore']:.3f}@{t1['MODI']['cost_frac']:.2f}x "
                     f"blender={t1['LLM-BLENDER']['bartscore']:.3f}@1.0x"))

    if "sweep" in selected:
        from benchmarks import budget_sweep

        print("\n### budget sweep (bi-objective frontier)")
        bs = budget_sweep.run(n_test=n2, train_steps=steps)
        rows.append(("budget_sweep_points", 0.0,
                     " ".join(f"{r['eps']:.2f}:{r['bartscore']:.2f}" for r in bs)))

    if "roofline" in selected:
        from benchmarks import roofline

        print("\n### roofline (from dry-run artifacts)")
        rows += roofline.run()

    print("\nname,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    main()
