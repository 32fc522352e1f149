"""Serving driver: stand up the full MODI stack (predictor + knapsack +
pool + GEN-FUSER) and serve MixInstruct-style queries.

    PYTHONPATH=src python -m repro.launch.serve --budget 0.2 --n 16 \
        [--policy modi] [--train-steps 300] [--online] [--trace-spans PATH]

``build_stack`` trains (or randomly inits, for a pipeline demo) the
scorer/fuser/predictor; ``main`` composes the layered serving stack:
the policy is constructed by registry name (``repro.core.make_policy``),
the ``EnsembleServer`` pairs it with a member backend, and ``--online``
routes the queries one at a time through the admission
``repro.serve.Scheduler`` instead of one offline batch — both paths
produce identical responses.
"""

from __future__ import annotations

import argparse

import jax
import numpy as np

from repro import configs
from repro.core import bartscore, build_predictor, make_policy
from repro.data import (
    DEFAULT_POOL,
    TOKENIZER,
    fuser_batches,
    generate_dataset,
    predictor_batches,
    pool_responses,
    query_cost_matrix,
    scorer_batches,
)
from repro.models import build_model
from repro.optim import AdamW
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import cluster_host_devices
from repro.serve import (
    AdmissionControl,
    ClusterRouter,
    EnsembleServer,
    HealthMonitor,
    PlacementPlan,
    RequestShed,
    Scheduler,
    requests_from_records,
    spans,
)
from repro.train import repeat_batches, train
import jax.numpy as jnp


def quality_labels(scorer, scorer_params, recs, responses):
    """BARTScore label matrix [Q, N] under the in-framework scorer."""
    n = len(responses[0])
    out = np.zeros((len(recs), n), np.float32)
    refs = TOKENIZER.pad_batch(
        [TOKENIZER.encode(r.reference, bos=True, eos=True) for r in recs], 32
    )
    mask = (refs != TOKENIZER.pad_id).astype(np.float32)
    for j in range(n):
        # BARTScore conditions on the candidate only (see data.batching)
        cands = TOKENIZER.pad_batch(
            [TOKENIZER.encode(resp[j]) for resp in responses], 64
        )
        out[:, j] = np.asarray(
            bartscore(scorer, scorer_params, jnp.asarray(cands), jnp.asarray(refs), jnp.asarray(mask))
        )
    return out


def build_stack(train_steps: int, seed: int = 0, log=print):
    """Train (or randomly init) scorer, fuser, predictor; return the parts."""
    recs = generate_dataset(3000, seed=seed)
    scorer = build_model(configs.get("bartscore-scorer"))
    scorer_p = scorer.init(jax.random.key(1))
    fuser = build_model(configs.get("gen-fuser"))
    fuser_p = fuser.init(jax.random.key(2))
    predictor = build_predictor(num_models=len(DEFAULT_POOL))
    pred_p = predictor.init(jax.random.key(3))

    if train_steps > 0:
        log(f"[1/4] training BARTScore scorer ({train_steps} steps)")
        scorer_p = train(
            lambda p, b: scorer.loss(p, b), scorer_p,
            repeat_batches(lambda ep: scorer_batches(recs, DEFAULT_POOL, 16, 96, 32, seed=ep)),
            train_steps, optimizer=AdamW(learning_rate=1e-3), log_fn=log,
        ).params
        log(f"[2/4] training GEN-FUSER ({train_steps} steps)")
        fuser_p = train(
            lambda p, b: fuser.loss(p, b), fuser_p,
            repeat_batches(lambda ep: fuser_batches(recs, DEFAULT_POOL, 16, 256, 32, seed=ep)),
            train_steps, optimizer=AdamW(learning_rate=1e-3), log_fn=log,
        ).params
        log("[3/4] labelling member responses with BARTScore")
        lab_recs = recs[:1000]
        responses = pool_responses(DEFAULT_POOL, lab_recs, seed=seed)
        labels = quality_labels(scorer, scorer_p, lab_recs, responses)
        log(f"      label matrix {labels.shape}, per-member mean: "
            + np.array2string(labels.mean(0), precision=2))
        log(f"[4/4] training MODI predictor ({train_steps} steps, Huber d=0.3, Adam 3e-4)")
        pred_p = train(
            lambda p, b, r: predictor.loss(p, b, r), pred_p,
            repeat_batches(lambda ep: predictor_batches(lab_recs, labels, 16, 64, seed=ep)),
            train_steps, optimizer=AdamW(learning_rate=3e-4, b1=0.9, b2=0.98, weight_decay=0.01),
            rng=jax.random.key(7), log_fn=log,
        ).params
    return recs, scorer, scorer_p, fuser, fuser_p, predictor, pred_p


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--budget", type=float, default=0.2, help="epsilon as fraction of full-ensemble cost")
    ap.add_argument("--n", type=int, default=8, help="queries to serve")
    ap.add_argument("--policy", type=str, default="modi", help="selection policy registry name")
    ap.add_argument("--train-steps", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--online", action="store_true",
                    help="serve one request at a time through the admission Scheduler")
    ap.add_argument("--max-batch-size", type=int, default=4, help="scheduler micro-batch size")
    ap.add_argument("--max-wait-ticks", type=int, default=4,
                    help="dispatch a queued request after this many ticks")
    ap.add_argument("--deadline-ticks", type=int, default=None,
                    help="per-request dispatch deadline (EDF batch formation)")
    ap.add_argument("--priority", type=int, default=0,
                    help="request priority (breaks deadline ties; larger = sooner)")
    ap.add_argument("--admission-window", type=int, default=8,
                    help="rolling fleet-budget window, in scheduler ticks")
    ap.add_argument("--admission-downgrade", type=float, default=None,
                    help="window cost fraction past which new requests are "
                         "downgraded to half the per-query budget")
    ap.add_argument("--admission-shed", type=float, default=None,
                    help="window cost fraction past which new requests are shed")
    ap.add_argument("--admission-deadline", action="store_true",
                    help="shed requests whose predicted queue delay already "
                         "exceeds their deadline")
    ap.add_argument("--hosts", type=int, default=None,
                    help="shard the pool over this many placement hosts "
                         "(cluster serving; logical-only when the device "
                         "fleet cannot be split)")
    ap.add_argument("--placement", type=str, default="auto",
                    choices=("auto", "round-robin"),
                    help="member->host placer: greedy cost/VRAM-balanced "
                         "or round-robin")
    ap.add_argument("--replicas", type=int, default=1,
                    help="replica hosts per member (auto placement only; "
                         "replicated members survive a host failure)")
    ap.add_argument("--fanout", action="store_true",
                    help="serve a batch's per-host member shards "
                         "concurrently on per-host executors (outputs are "
                         "byte-identical to sequential routing)")
    ap.add_argument("--probation-ticks", type=int, default=0,
                    help="ticks a recovered host waits past its recovery "
                         "tick before being re-admitted to routing")
    ap.add_argument("--recover", type=str, default=None, metavar="HOST:TICK",
                    help="schedule a dead host's recovery (comma-separated "
                         "host:tick pairs; re-admitted after probation)")
    ap.add_argument("--rebalance", action="store_true",
                    help="re-place members that lost replica redundancy "
                         "onto surviving hosts at the next maintenance tick")
    ap.add_argument("--probe-interval", type=int, default=None,
                    help="run health probes every this many scheduler "
                         "ticks (probe-driven death/revival replaces the "
                         "--recover schedule, which then describes when "
                         "each host's underlying health returns)")
    ap.add_argument("--probe-failures", type=int, default=2,
                    help="consecutive probe failures that open a host's "
                         "circuit breaker (mark it dead)")
    ap.add_argument("--shard-deadline", type=float, default=None,
                    metavar="SECONDS",
                    help="wall-clock deadline per fan-out shard; a late "
                         "shard is cancelled and hedged onto replica hosts")
    ap.add_argument("--hedge", action="store_true",
                    help="re-route grey-slow dispatches to an alive "
                         "replica at consume time (straggler hedging)")
    ap.add_argument("--allow-degraded", action="store_true",
                    help="serve partial-ensemble responses (knapsack over "
                         "the survivors, tagged degraded) when members "
                         "are unavailable, instead of failing the batch")
    ap.add_argument("--async", dest="async_dispatch", action="store_true",
                    help="serve batches on a dispatch worker thread so "
                         "submit never blocks on a batch (--online only)")
    ap.add_argument("--stream", action="store_true",
                    help="token-level continuous batching: fuse through "
                         "the persistent in-flight decode state and print "
                         "tokens as they stream (--online only; final "
                         "responses are byte-identical)")
    ap.add_argument("--stream-capacity", type=int, default=8,
                    help="decode slots in the persistent in-flight batch")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="max rows per prefill call on the streaming path "
                         "(bounds how long a prompt burst can stall joins)")
    ap.add_argument("--trace-spans", type=str, default=None, metavar="PATH",
                    help="record the serving path's spans and write them to "
                         "PATH as JSON lines at exit (README, \"Spans\")")
    args = ap.parse_args()
    enable_compile_cache()
    if args.trace_spans:
        spans.enable()
    try:
        serve(args)
    finally:
        if args.trace_spans:
            n = spans.dump(args.trace_spans)
            print(f"wrote {n} span records to {args.trace_spans}")


def serve(args) -> None:
    """Build the stack and serve ``args.n`` queries as ``main`` parsed them."""
    recs, scorer, scorer_p, fuser, fuser_p, predictor, pred_p = build_stack(
        args.train_steps, args.seed
    )
    server = EnsembleServer(
        DEFAULT_POOL,
        make_policy(args.policy, budget=args.budget),
        predictor, pred_p, fuser, fuser_p,
    )
    if args.hosts:
        groups = cluster_host_devices(args.hosts)
        devices = [d for g in groups for d in g] or None
        if args.placement == "round-robin":
            plan = PlacementPlan.round_robin(len(DEFAULT_POOL), args.hosts,
                                             devices=devices)
        else:
            plan = PlacementPlan.auto(DEFAULT_POOL, args.hosts,
                                      replicas=args.replicas, devices=devices)
        recovery = {}
        if args.recover:
            for pair in args.recover.split(","):
                host, _, tick = pair.partition(":")
                recovery.setdefault(int(host), []).append(int(tick))
        recovery = {h: tuple(sorted(t)) for h, t in recovery.items()}
        health = None
        if args.probe_interval is not None:
            # probe-driven health: the recovery schedule feeds the
            # monitor's half-open probes instead of the router's
            # schedule-driven revival
            health = HealthMonitor(plan,
                                   probe_interval=args.probe_interval,
                                   probe_failures=args.probe_failures,
                                   recovery=recovery)
            recovery = {}
        server.backend = ClusterRouter(
            server.backend, plan=plan, fanout=args.fanout,
            host_recovery=recovery,
            probation_ticks=args.probation_ticks, rebalance=args.rebalance,
            health=health, hedge_stragglers=args.hedge,
            shard_deadline_s=args.shard_deadline)
        print(f"cluster placement ({args.placement}, {args.hosts} hosts"
              + (", fanout" if args.fanout else "") + "):")
        print(plan.describe())
    if args.online:
        # pre-compile every bucket a scheduler batch can map to: early
        # micro-batches dispatch before the queue fills, so sizes
        # 1..max_batch_size all occur, and max_batch_size itself may round
        # up to a rung above it
        rungs = sorted({server.bucket_ladder.batch_bucket(b)
                        for b in range(1, args.max_batch_size + 1)})
        server.warm([(b, server.max_new_tokens) for b in rungs])
    batch = generate_dataset(args.n, seed=args.seed + 999)
    if args.online:
        admission = None
        if (args.admission_downgrade is not None
                or args.admission_shed is not None or args.admission_deadline):
            admission = AdmissionControl(
                window_ticks=args.admission_window,
                downgrade_fraction=args.admission_downgrade,
                downgrade_budget=args.budget / 2,
                shed_fraction=args.admission_shed,
                deadline_aware=args.admission_deadline,
            )
        scheduler = Scheduler(server, max_batch_size=args.max_batch_size,
                              max_wait_ticks=args.max_wait_ticks,
                              admission=admission,
                              sync=not args.async_dispatch,
                              allow_degraded=args.allow_degraded,
                              stream=args.stream,
                              stream_capacity=args.stream_capacity,
                              prefill_chunk=args.prefill_chunk)
        futures = [
            scheduler.submit(req)
            for req in requests_from_records(
                batch, priority=args.priority,
                deadline_ticks=args.deadline_ticks)
        ]
        scheduler.flush()
        scheduler.join()
        out = []
        for f in futures:
            try:
                if args.stream:
                    resp = None
                    for ev in f.stream():
                        if ev.final:
                            resp = ev.response
                        else:
                            print(f"  [req {ev.seq} +{len(ev.tokens)} tok] "
                                  f"{ev.text!r}")
                    out.append(resp)
                else:
                    out.append(f.result())
            except RequestShed:
                out.append(None)
        scheduler.close()
        shed = sum(r is None for r in out)
        kept = [(r, rec) for r, rec in zip(out, batch) if r is not None]
        out = [r for r, _ in kept]
        batch = [rec for _, rec in kept]
        responses = [r.text for r in out]
        fractions = [r.cost_fraction for r in out]
        masks = [r.mask for r in out]
        print(f"scheduler: {scheduler.stats}"
              + (f"  ({shed} requests shed by admission control)" if shed else ""))
    else:
        result = server.serve(batch)
        responses, fractions, masks = result.responses, result.cost_fraction, result.mask
    for rec, resp, frac, row in zip(batch, responses, fractions, masks):
        members = [DEFAULT_POOL[j].name for j in range(len(row)) if row[j]]
        print(f"\nQ: {rec.query}\n   ref: {rec.reference}\n   "
              f"{args.policy}({frac:.0%} cost, {members}): {resp!r}")
    mean_frac = (f"{np.mean(fractions):.3f}" if fractions is not None and len(fractions)
                 else "n/a (all requests shed)")
    print("\nstats:", server.stats,
          f"\nmean cost fraction: {mean_frac} (budget {args.budget})")


if __name__ == "__main__":
    main()
