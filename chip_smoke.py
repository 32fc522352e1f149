"""Drive the MODI serving path once on a TPU and check what comes out.

    python chip_smoke.py               # one chip: kernel, served stack, logits
    python chip_smoke.py --chips 4     # four chips: the cluster path only

One chip.  The Pallas knapsack runs compiled and must select exactly what
the ``lax`` DP and the take-tensor oracle select.  The predictor and the
GEN-FUSER are built at the published widths of their backbones
(DeBERTa-v3-large, Flan-T5-XL) with random weights from ``--seed``, and
serve seeded requests at ε=0.2 and ε=1.0 through ``EnsembleServer`` and
``Scheduler``, on the batch path and on the streaming path, which must
agree byte for byte.  The fuser's prefill logits on the chip must agree
with the host CPU backend.

Four chips.  Eight live members at SmolLM-360M's widths are placed over
four one-chip hosts and serve through ``ClusterRouter`` with and without
fan-out; responses must agree, and each member's arrays must sit on its
host's chip.

Every phase prints a line; any failed check raises and the exit code is
non-zero.  The last line of a passing run is one JSON object naming the
device.  With no TPU, JAX's CPU backend is refused: the script exits
non-zero before printing any result.  Times printed here come from one
smoke run and are not a benchmark.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

N_MEMBERS = 8  # DEFAULT_POOL
BUDGET_BUCKETS = 256
EPSILONS = (0.2, 1.0)
N_REQUESTS = 16
MAX_BATCH = 8
# relative tolerance (max |chip - cpu| / max |cpu|) for float32 logits under
# "highest" matmul precision.  float32 on both sides after 24 layers differs
# by reduction order only (~1e-6 per op); a single bf16 pass rounds every
# matmul operand to 8 mantissa bits (~4e-3 each), which puts the logits
# ~1e-2 away.  1e-3 sits between the two; the run also prints the bf16-pass
# error on the chip so the margin is visible.
LOGITS_RTOL = 1e-3


class SmokeFailure(RuntimeError):
    pass


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def _say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


# ---------------------------------------------------------------------------
# phases shared by both modes
# ---------------------------------------------------------------------------


def phase_device(chips: int):
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise SmokeFailure(f"needs a TPU; JAX found platform {platform!r}")
    _check(len(devices) >= chips,
           f"--chips {chips} needs {chips} chips, found {len(devices)}")
    kind = devices[0].device_kind
    _say("device", f"{platform} {kind} x{len(devices)}")
    return {"platform": platform, "kind": kind, "count": len(devices)}


def phase_compile_cache() -> None:
    from repro.launch.compile_cache import enable_compile_cache

    _say("cache", f"persistent compilation cache at {enable_compile_cache()}")


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------


def phase_knapsack(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.knapsack import knapsack_select, unpack_selection
    from repro.kernels.knapsack import knapsack_select_pallas, knapsack_select_ref
    from repro.kernels.knapsack.knapsack import knapsack_dp_pallas

    rng = np.random.default_rng(seed)
    for q in (8, 32):
        profits = rng.uniform(0.1, 5.0, (q, N_MEMBERS)).astype(np.float32)
        costs = rng.integers(1, BUDGET_BUCKETS // 2, (q, N_MEMBERS))
        # half the rows tie-heavy: integer profits and few cost levels, so
        # the ties-keep-not-taken rule decides the selection
        half = q // 2
        profits[:half] = rng.integers(1, 4, (half, N_MEMBERS))
        costs[:half] = rng.integers(1, 5, (half, N_MEMBERS)) * 32
        p, c = jnp.asarray(profits), jnp.asarray(costs, jnp.int32)
        hlo = knapsack_dp_pallas.lower(p, c, BUDGET_BUCKETS, 8, False).compile().as_text()
        _check("tpu_custom_call" in hlo, f"Q={q}: compiled knapsack has no tpu_custom_call")
        _, words = knapsack_dp_pallas(p, c, BUDGET_BUCKETS, 8, False)
        kernel = np.asarray(unpack_selection(words, N_MEMBERS))
        lax = np.asarray(knapsack_select(p, c, BUDGET_BUCKETS))
        oracle = np.array(knapsack_select_ref(p, c, BUDGET_BUCKETS))
        _check((kernel == lax).all(), f"Q={q}: Pallas masks differ from lax")
        _check((kernel == oracle).all(), f"Q={q}: Pallas masks differ from the oracle")
        # the serving path resolves the kernel's interpret flag itself;
        # on a TPU it must reach the compiled kernel too
        served = jax.jit(knapsack_select_pallas, static_argnums=2).lower(
            p, c, BUDGET_BUCKETS).compile().as_text()
        _check("tpu_custom_call" in served,
               f"Q={q}: the serving path's knapsack is not the compiled kernel")
        _say("knapsack", f"Q={q} N={N_MEMBERS} budget={BUDGET_BUCKETS}: compiled kernel "
             f"(tpu_custom_call) == lax == oracle on {q} rows, "
             f"{int(kernel.sum())} members selected")


def _published_stack():
    """Predictor and fuser at their backbones' published widths; each cut
    of the published shape is printed with its reason."""
    from repro import configs

    fuser_cfg = dataclasses.replace(
        configs.get("gen-fuser"), name="gen-fuser@flan-t5-xl",
        d_model=2048, num_heads=32, num_kv_heads=32, head_dim=64, d_ff=5120,
        num_layers=12, enc_layers=12)
    pred_cfg = dataclasses.replace(
        configs.get("modi-predictor"), name="modi-predictor@deberta-v3-large",
        d_model=1024, num_heads=16, num_kv_heads=16, head_dim=64, d_ff=4096,
        num_layers=24)
    _say("cut", "gen-fuser: 12 encoder + 12 decoder layers of Flan-T5-XL's "
         "24 + 24; float32 weights of all 48 are ~10.9 GB, and with the "
         "batch-path caches (~0.8 GB per batch-8 bucket) and the streaming "
         "cache they do not fit a 16 GB chip")
    _say("cut", "vocabulary: the byte tokenizer's 512 ids for both models "
         "(Flan-T5 has 32128, DeBERTa-v3 128100)")
    return fuser_cfg, pred_cfg


def _init_on_device(model, key):
    import jax

    return jax.jit(model.init)(key)


def _n_params(params) -> int:
    import jax

    return sum(int(x.size) for x in jax.tree.leaves(params))


def _requests(seed: int):
    from repro.data import generate_dataset
    from repro.serve import requests_from_records

    records = generate_dataset(N_REQUESTS, seed=seed + 999)
    reqs = []
    for eps in EPSILONS:
        reqs += [dataclasses.replace(r, budget=eps)
                 for r in requests_from_records(records)]
    return records, reqs


def _serve(server, reqs, stream: bool):
    from repro.serve import Scheduler

    sched = Scheduler(server, max_batch_size=MAX_BATCH, stream=stream)
    futures = [sched.submit(r) for r in reqs]
    sched.flush()
    out = []
    for f in futures:
        if stream:
            final = [ev.response for ev in f.stream(timeout=600) if ev.final]
            _check(len(final) == 1, f"request {f.seq}: no final stream event")
            out.append(final[0])
        else:
            out.append(f.result(timeout=600))
    _check(all(f.done() for f in futures), "a future did not resolve")
    for key in ("hedges", "host_hedges", "shed", "degraded_responses"):
        _check(sched.stats[key] == 0,
               f"{'stream' if stream else 'batch'} path: {key}={sched.stats[key]}")
    batches = [e["reqs"] for e in sched.events if e["event"] == "dispatch"]
    sched.close()
    return out, batches


def _check_selections(records, reqs, responses, batches) -> dict:
    """Every served batch's masks == lax DP == take-tensor oracle over the
    float64 host bucketing, recomputed from the batch's own predictor
    scores (the α shift depends on the batch); and ε holds."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import EpsilonConstraint, normalize_costs, shift_scores
    from repro.core.selector import ModiPolicy
    from repro.data import DEFAULT_POOL, query_cost_matrix
    from repro.kernels.knapsack import knapsack_select_ref

    costs = query_cost_matrix(DEFAULT_POOL, records)
    worst = {eps: 0.0 for eps in EPSILONS}
    for rows in batches:
        eps = reqs[rows[0]].budget
        _check(all(reqs[i].budget == eps for i in rows), "a batch mixed budgets")
        quality = np.stack([responses[i].predicted_quality for i in rows])
        # the engine hands the policy float32 costs
        c = np.asarray(costs[[i % N_REQUESTS for i in rows]], np.float32)
        served = np.stack([responses[i].mask for i in rows])
        lax = np.asarray(ModiPolicy(EpsilonConstraint(eps, BUDGET_BUCKETS)).select(
            jnp.asarray(quality), jnp.asarray(c)))
        profits, _ = shift_scores(quality)
        c64 = c.astype(np.float64)
        int_costs, _ = normalize_costs(
            c64, eps * c64.sum(axis=1, keepdims=True), BUDGET_BUCKETS)
        oracle = np.array(knapsack_select_ref(
            profits, np.minimum(int_costs, BUDGET_BUCKETS + 1), BUDGET_BUCKETS))
        empty = ~oracle.any(axis=1)
        oracle[empty, np.argmin(c, axis=1)[empty]] = True
        _check((served == lax).all(), f"ε={eps}: served masks differ from lax")
        _check((served == oracle).all(), f"ε={eps}: served masks differ from the oracle")
        full = costs[[i % N_REQUESTS for i in rows]]
        frac = (np.where(served, full, 0.0).sum(1) / full.sum(1))
        _check((frac <= eps).all(), f"ε={eps}: cost fraction {frac.max()} over budget")
        worst[eps] = max(worst[eps], float(frac.max()))
    return worst


def phase_serve(seed: int):
    import jax
    import numpy as np

    from repro.core import build_predictor, make_policy
    from repro.data import DEFAULT_POOL
    from repro.models import build_model
    from repro.serve import EnsembleServer

    fuser_cfg, pred_cfg = _published_stack()
    fuser = build_model(fuser_cfg)
    predictor = build_predictor(len(DEFAULT_POOL), encoder=pred_cfg)
    t0 = time.perf_counter()
    fuser_p = _init_on_device(fuser, jax.random.key(seed))
    pred_p = _init_on_device(predictor, jax.random.key(seed + 1))
    jax.block_until_ready((fuser_p, pred_p))
    _say("stack", f"gen-fuser {_n_params(fuser_p) / 1e9:.3f} B params, predictor "
         f"{_n_params(pred_p) / 1e9:.3f} B params, float32, random init from seed "
         f"{seed} in {time.perf_counter() - t0:.1f} s; members: SimBackend")

    server = EnsembleServer(DEFAULT_POOL, make_policy("modi", impl="pallas"),
                            predictor, pred_p, fuser, fuser_p)
    # as `repro.launch.serve --online` warms: every rung a scheduler batch
    # of 1..max_batch_size maps to; the streaming path's rungs likewise
    rungs = sorted({server.bucket_ladder.batch_bucket(b) for b in range(1, MAX_BATCH + 1)})
    t0 = time.perf_counter()
    server.warm([(b, server.max_new_tokens) for b in rungs])
    server.stream_fuser(MAX_BATCH).warm(rungs)
    warm_s = time.perf_counter() - t0
    compiles = server.generate_compiles()
    predict_programs = server.predict_compiles
    _say("warm", f"rungs {rungs}: {warm_s:.1f} s compiling and warming "
         f"{compiles['total']} generate programs and {predict_programs} predictor "
         "programs (smoke run, not a benchmark)")

    records, reqs = _requests(seed)
    batch, batch_groups = _serve(server, reqs, stream=False)
    stream, _ = _serve(server, reqs, stream=True)
    _check(server.generate_compiles() == compiles,
           f"generate compiled after warm-up: {compiles} -> {server.generate_compiles()}")
    _check(server.predict_compiles == predict_programs,
           f"predictor compiled after warm-up: {predict_programs} -> "
           f"{server.predict_compiles}")
    for i, (a, b) in enumerate(zip(batch, stream)):
        _check(a.text == b.text, f"request {i}: streamed text differs from batch path")
        _check((a.mask == b.mask).all(), f"request {i}: streamed mask differs")
    worst = _check_selections(records, reqs, batch, batch_groups)
    totals = sorted({r.timing["total_s"] for r in batch})
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use", 0)
    _say("serve", f"{len(reqs)} requests (ε {EPSILONS}) in {len(batch_groups)} batches "
         f"of {MAX_BATCH}: streamed == batch byte for byte; masks == lax == oracle; "
         f"max cost fraction {worst}; 0 hedges/shed/degraded; 0 compiles after warm-up")
    _say("serve", f"smoke run, not a benchmark: warm-up {warm_s:.1f} s, per-batch wall "
         f"median {np.median(totals[1:] or totals):.3f} s over batches after the "
         f"first, peak device memory {peak / 2**30:.2f} GiB")
    return fuser, fuser_p


def phase_logits(fuser, fuser_p, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.data.tokenizer import TOKENIZER

    rng = np.random.default_rng(seed + 2)
    enc = np.asarray(rng.integers(0, fuser.cfg.vocab_size, (2, 64)), np.int32)

    def prefill_logits(params, enc_tokens):
        cache = fuser.init_cache(2, 2, enc_seq=enc_tokens.shape[1])
        bos = jnp.full((2, 1), TOKENIZER.bos_id, jnp.int32)
        logits, _ = fuser.prefill(params, bos, cache, enc_tokens=enc_tokens)
        return logits[:, 0]

    cpu = jax.devices("cpu")[0]
    with jax.default_matmul_precision("highest"):
        chip = np.asarray(jax.jit(prefill_logits)(fuser_p, jnp.asarray(enc)))
        ref = np.asarray(jax.jit(prefill_logits)(
            jax.device_put(fuser_p, cpu), jax.device_put(enc, cpu)))
    # the TPU's default for float32 operands, which the model overrides
    # unless the caller sets a precision
    with jax.default_matmul_precision("bfloat16"):
        bf16_pass = np.asarray(jax.jit(prefill_logits)(fuser_p, jnp.asarray(enc)))
    scale = float(np.abs(ref).max())
    err = float(np.abs(chip - ref).max()) / scale
    err_bf16 = float(np.abs(bf16_pass - ref).max()) / scale
    _check(np.isfinite(chip).all() and chip.shape == (2, fuser.cfg.vocab_size),
           f"prefill logits: shape {chip.shape} or non-finite values")
    _say("logits", f"fuser prefill [2 x 64 tokens]: max|chip - cpu| / max|cpu| = "
         f"{err:.2e} at highest precision (tolerance {LOGITS_RTOL:.0e}); the default "
         f"single bf16 pass gives {err_bf16:.2e}")
    _check(err <= LOGITS_RTOL, f"chip logits off the CPU reference by {err:.2e}")
    _check(err_bf16 > LOGITS_RTOL, "tolerance too loose: the bf16 pass passes it")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def _member_config():
    from repro import configs

    _say("cut", "members: SmolLM-360M widths and all 32 layers, bf16; vocabulary "
         "cut to the byte tokenizer's 512 ids (published 49152)")
    return dataclasses.replace(configs.get("smollm-360m"), vocab_size=512)


def phase_cluster(seed: int) -> None:
    import jax
    import numpy as np

    from repro import configs
    from repro.core import build_predictor, make_policy
    from repro.data import DEFAULT_POOL
    from repro.launch.mesh import cluster_host_devices
    from repro.models import build_model
    from repro.serve import ClusterRouter, EnsembleServer, LiveLMBackend, LiveMember, PlacementPlan

    groups = cluster_host_devices(4)
    _check(all(groups), f"cluster_host_devices(4) gave empty groups: {groups}")
    plan = PlacementPlan.auto(DEFAULT_POOL, n_hosts=4, replicas=1,
                              devices=[d for g in groups for d in g])
    _say("placement", plan.describe().replace("\n", "; "))

    model = build_model(_member_config())
    members = []
    for j in range(len(DEFAULT_POOL)):
        (device,) = groups[plan.primary_host(j)]
        with jax.default_device(device):
            params = _init_on_device(model, jax.random.key(seed + 10 + j))
        members.append(LiveMember(DEFAULT_POOL[j], model, params))
    live = LiveLMBackend(members)
    predictor = build_predictor(len(DEFAULT_POOL))
    fuser = build_model(configs.get("gen-fuser"))
    _say("stack", f"{len(members)} members x {_n_params(members[0].params) / 1e6:.0f} M "
         "params; predictor and fuser at the repo's default small configs on chip 0")
    # the full ensemble (LLM-BLENDER): every member generates for every
    # row, so each batch of 8 loads all four chips and each member program
    # runs at one batch rung
    server = EnsembleServer(DEFAULT_POOL, make_policy("llm-blender"), predictor,
                            predictor.init(jax.random.key(seed + 1)), fuser,
                            fuser.init(jax.random.key(seed)),
                            backend=ClusterRouter(live, plan=plan))
    t0 = time.perf_counter()
    server.warm([(MAX_BATCH, server.max_new_tokens)])
    compiles = server.generate_compiles()
    predict_programs = server.predict_compiles
    _say("warm", f"{time.perf_counter() - t0:.1f} s compiling and warming "
         f"{compiles['total']} generate programs and {predict_programs} predictor "
         "programs (smoke run, not a benchmark)")

    _, reqs = _requests(seed)
    reqs = reqs[:N_REQUESTS]
    results = {}
    for fanout in (True, False):
        router = ClusterRouter(live, plan=plan, fanout=fanout)
        server.backend = router
        t0 = time.perf_counter()
        results[fanout], _ = _serve(server, reqs, stream=False)
        wall = time.perf_counter() - t0
        router.close()
        _check(router.stats["failovers"] == 0 and router.stats["host_faults"] == 0,
               f"fanout={fanout}: router stats {router.stats}")
        _say("serve", f"fanout={fanout}: {len(reqs)} requests, full ensemble, "
             f"{router.stats['dispatches']} member dispatches, {wall:.2f} s wall "
             "(smoke run, not a benchmark)")
    _check(server.generate_compiles() == compiles, "generate compiled after warm-up")
    _check(server.predict_compiles == predict_programs, "predictor compiled after warm-up")
    for i, (a, b) in enumerate(zip(results[True], results[False])):
        _check(a.text == b.text and a.member_texts == b.member_texts,
               f"request {i}: fan-out and sequential routing differ")
    for (j, mesh), d in live._dispatchers.items():
        want = set(mesh.devices.flat)
        _check(want == set(groups[plan.primary_host(j)]), f"member {j} on the wrong host")
        leaves = jax.tree.leaves(d.params) + [
            leaf for e in d._entries.values() for leaf in jax.tree.leaves(e.cache)]
        _check(all(leaf.devices() == want for leaf in leaves),
               f"member {j}: arrays off its host's chip")
    in_use = [d.memory_stats()["bytes_in_use"] for d in jax.devices()[:4]]
    weights = np.zeros(4)
    for j, m in enumerate(members):
        weights[plan.primary_host(j)] += sum(x.nbytes for x in jax.tree.leaves(m.params))
    _check(all(b >= w > 0 for b, w in zip(in_use, weights)),
           f"bytes in use {in_use} below the members' weights {weights.tolist()}")
    _say("cluster", "fan-out == sequential byte for byte; each member's weights and "
         "caches on its host's chip; bytes in use per chip (GiB): "
         + ", ".join(f"{b / 2**30:.2f}" for b in in_use))


# ---------------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: kernel, served stack and logits on one chip; "
                         "4: the cluster placement path only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    t0 = time.perf_counter()
    device = phase_device(args.chips)
    phase_compile_cache()
    if args.chips == 4:
        phase_cluster(args.seed)
    else:
        phase_knapsack(args.seed)
        fuser, fuser_p = phase_serve(args.seed)
        phase_logits(fuser, fuser_p, args.seed)
    _say("done", f"{time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
