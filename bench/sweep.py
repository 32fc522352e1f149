"""Knee sweep: open-loop, evenly spaced arrivals at a few fixed rates, one
process, to find the highest rate a configuration sustains.

    python3 bench/sweep.py --config modi-t5xl-sim --mix sat.eps0.2 \
        --rates 2,3,4,5,6,7 --seconds 20 --seed 1

The mix file gives the budget and answer cap; arrivals are evenly spaced at
each rate, the scheduler is ticked every ``tick_s`` (0.25 s) and a request
waits at most its ``max_wait_ticks`` (4) for a batch to fill.  Prints one
JSON line per rate: offered and completed rates, requests outstanding at
the end of the window and half-way through it, and latency percentiles.
A rate is sustained when the outstanding count does not grow.  The sweep
defines no cell and checks nothing; the cells' runs do that.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

from harness import cell, spec, traffic, window  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--mix", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    bench = spec.load_benchmark()
    cell.find_devices(1, require_chip=True)
    cell.enable_compile_cache(spec.ROOT)
    cfg = spec.load_config(bench, args.config)
    base = traffic.validate(spec.load_traffic(args.mix))
    stack = spec.config_module(args.config).build(cfg, args.seed)
    sched = stack.scheduler()
    # the batch sizes an open loop dispatches: forced partial batches are
    # snapped down to the ladder's rungs; each is warmed on its own, so no
    # size's prefill output is held while the next one runs
    sizes = [r for r in sched.ladder.batch if r <= cfg["max_batch_size"]]
    for n in sizes:
        stack.warm_programs([n])
    stream = cell.Stream(sched)

    def make_request(q):
        return stack.make_request(q, base["epsilon"], base["max_new_tokens"])

    warm = traffic.Queries(args.seed, 0)
    for n in sizes:  # every batch size an open loop can dispatch
        futs = [sched.submit(make_request(q)) for q in warm.take(n)]
        sched.flush()
        sched.join()
        for f in futs:
            f.result(timeout=600)
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        mix = {**base, "kind": "open", "rate_per_s": rate}
        driver = traffic.Driver(mix, args.seed + 1000 * (k + 1), sched.submit, make_request)
        half = {}

        def tick(t_half=time.perf_counter() + args.seconds / 2):
            sched.tick()
            if not half and time.perf_counter() >= t_half:
                half["n"] = sum(not f.done() for f in driver.futures.values())

        t0, t1 = driver.run(args.seconds, tick=tick)
        at_end = sum(not f.done() for f in driver.futures.values())
        sched.flush()
        sched.join()
        first, last = {}, {}
        for t, seq in stream.tokens:
            if seq in driver.futures:
                first.setdefault(seq, t)
                last[seq] = t
        done_in = [s for s in driver.futures if last.get(s, float("inf")) <= t1]
        lat = [last[s] - driver.due[s] for s in done_in]
        ttft = [first[s] - driver.due[s] for s in first if first[s] <= t1]
        print(json.dumps({
            "rate_per_s": rate, "offered": len(driver.futures),
            "completed_per_s": len(done_in) / (t1 - t0),
            "outstanding_half": half.get("n"), "outstanding_end": at_end,
            "latency_p50_s": window.percentile(lat, 50) if lat else None,
            "latency_p90_s": window.percentile(lat, 90) if lat else None,
            "ttft_p90_s": window.percentile(ttft, 90) if ttft else None,
            "generator_late_p95_ms": 1e3 * window.percentile(driver.late_s, 95),
        }), flush=True)
    sched.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
