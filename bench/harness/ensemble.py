"""The MODI serving stack as a configuration file describes it, and the
comparison that decides whether what it served is correct.

``build`` hands the program its models, pool and policy, with weights the
benchmark made from the seed, and returns a :class:`Stack`.  ``check``
compares, for requests served in the window, every layer a request went
through against the plain references:

* ``member_mismatch``: member answers that differ from the simulator's
  (exact, limit 0);
* ``mask_mismatch``: selections that differ from Algorithm 1 run on the
  batch's own predicted scores (exact, limit 0);
* ``eps_violations``: requests whose realized cost exceeds ε of the whole
  pool's cost (exact, limit 0);
* ``score_err``: the largest predictor score error over the sample,
  relative to the largest reference score;
* ``fuse_gap``: over every token served to the sample, the widest gap by
  which its reference logit lies below the reference's best logit.
"""

from __future__ import annotations

import dataclasses
import gc
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from harness import fuser_shape, predictor_shape, reference, weights, yardstick


@dataclasses.dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(np.isfinite(self.value)) and self.value <= self.limit


def _build_weights(keys, spec):
    dtype = jnp.dtype(spec["dtype"])
    return {"fuser": weights.fuser_params(keys, spec["fuser"], dtype, yardstick.EOS_ID),
            "predictor": weights.predictor_params(keys, spec["predictor"], spec["members"],
                                                  dtype, spec["max_rel"])}


@dataclasses.dataclass
class Stack:
    cfg: dict
    seed: int
    server: object
    weights: dict
    pool: List[yardstick.Member]
    make_request: Callable
    scheduler: Callable
    warm_programs: Callable
    spans: Dict[str, tuple]  # span name -> (object, method name)

    def free_program(self) -> None:
        """Drop every reference to the program's state; keep the weights."""
        self.server = None
        self.scheduler = None
        self.warm_programs = None
        self.spans = {}
        gc.collect()


def build(cfg: dict, seed: int, backend_factory: Optional[Callable] = None) -> Stack:
    """The program's server over this configuration, with the benchmark's
    weights in the configuration's ``dtype``.  ``backend_factory(program_pool)``
    may give the members' backend; without it the program's behavioural
    simulator serves them.  The precision the models run at is the
    configuration module's to state and enforce."""
    from repro import configs
    from repro.core import build_predictor, make_policy
    from repro.core.predictor import MAX_REL
    from repro.data.mixinstruct import PoolMemberSpec
    from repro.models import build_model
    from repro.serve import EnsembleRequest, EnsembleServer, Scheduler

    fuser_cfg = dataclasses.replace(
        configs.get("gen-fuser"), name=cfg["name"] + "/fuser",
        d_model=cfg["fuser_d_model"], num_heads=cfg["fuser_num_heads"],
        num_kv_heads=cfg["fuser_num_kv_heads"], head_dim=cfg["fuser_head_dim"],
        d_ff=cfg["fuser_d_ff"], num_layers=cfg["fuser_dec_layers"],
        enc_layers=cfg["fuser_enc_layers"], enc_seq=cfg["fuser_enc_positions"],
        vocab_size=cfg["fuser_vocab_size"], dtype=cfg["dtype"], norm="rmsnorm",
        act="gelu", tie_embeddings=cfg["fuser_tie_word_embeddings"],
        rope_theta=cfg["fuser_rope_theta"],
        norm_eps=cfg["fuser_norm_eps"])
    pred_cfg = dataclasses.replace(
        configs.get("modi-predictor"), name=cfg["name"] + "/predictor",
        d_model=cfg["predictor_d_model"], num_heads=cfg["predictor_num_heads"],
        num_kv_heads=cfg["predictor_num_heads"], head_dim=cfg["predictor_head_dim"],
        d_ff=cfg["predictor_d_ff"], num_layers=cfg["predictor_layers"],
        vocab_size=cfg["predictor_vocab_size"], dtype=cfg["dtype"], norm="layernorm",
        act="gelu",
        norm_eps=cfg["predictor_norm_eps"])
    if MAX_REL != cfg["predictor_position_buckets"]:
        raise ValueError(f"the program's predictor buckets relative positions to {MAX_REL}, "
                         f"the configuration states {cfg['predictor_position_buckets']}")
    pool = yardstick.pool_from_config(cfg["pool"])
    program_pool = [PoolMemberSpec(m.name, m.params_b, m.n_layer, m.d_model, m.competence)
                    for m in pool]
    fuser = build_model(fuser_cfg)
    predictor = build_predictor(len(pool), encoder=pred_cfg)
    spec = {"dtype": cfg["dtype"], "fuser": fuser_shape(cfg),
            "predictor": predictor_shape(cfg), "members": len(pool), "max_rel": MAX_REL}
    w = weights.make(seed, _build_weights, spec)
    key = jax.random.key(0)
    weights.check_layout(w["fuser"], jax.eval_shape(fuser.init, key), "fuser")
    weights.check_layout(w["predictor"], jax.eval_shape(predictor.init, key), "predictor")
    backend = backend_factory(program_pool) if backend_factory else None
    policy = make_policy("modi", budget=1.0, buckets=cfg["budget_buckets"],
                         impl=cfg["knapsack"])
    server = EnsembleServer(
        program_pool, policy, predictor, w["predictor"], fuser, w["fuser"],
        backend=backend, max_query_len=cfg["max_query_len"],
        max_fusion_len=cfg["max_fusion_len"], sim_seed=sim_seed(seed))
    capacity = cfg["stream_capacity"]

    def make_request(q: yardstick.Query, eps: float, max_new: int):
        from repro.data.mixinstruct import Record

        rec = Record(q.query, q.reference, q.domain, q.domain_id)
        return EnsembleRequest(query=q.query, record=rec, budget=eps, max_new_tokens=max_new)

    def scheduler():
        return Scheduler(server, max_batch_size=cfg["max_batch_size"], stream=True,
                         sync=False, stream_capacity=capacity)

    def warm_programs(batch_sizes: Sequence[int]) -> None:
        server.stream_fuser(capacity).warm(sorted(set(batch_sizes)))

    stream = server.stream_fuser(capacity)
    spans = {"bench.engine": (server, "serve_requests_stream"),
             "bench.predict": (server, "predict_quality"),
             "bench.select": (server, "_select"),
             "bench.members": (server, "_generate_members"),
             "bench.prefill": (stream, "submit"),
             "bench.decode": (stream, "pump")}
    return Stack(cfg, seed, server, w, pool, make_request, scheduler, warm_programs, spans)


def sim_seed(seed: int) -> int:
    return seed % 2**31


# ---------------------------------------------------------------------------
# the comparison
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Served:
    """What the program gave back for one request."""

    query: yardstick.Query
    eps: float
    tokens: List[int]  # fused tokens as streamed
    mask: np.ndarray
    scores: np.ndarray
    member_texts: List[Optional[str]]
    cap: int  # the request's answer cap, which also caps member answers


def _blocks(n: int, size: int):
    for lo in range(0, n, size):
        yield lo, min(lo + size, n)


def reference_scores(stack: Stack, queries: Sequence[yardstick.Query],
                     mm: reference.Matmul, rows: int = 8) -> np.ndarray:
    cfg = stack.cfg
    toks = np.stack([yardstick.predictor_tokens(q.query, cfg["max_query_len"]) for q in queries])
    out = []
    for lo, hi in _blocks(len(toks), rows):
        block = np.zeros((rows, toks.shape[1]), np.int32)
        block[:hi - lo] = toks[lo:hi]
        s = reference.predictor_scores(stack.weights["predictor"], jnp.asarray(block), mm=mm,
                                       eps=cfg["predictor_norm_eps"],
                                       max_rel=cfg["predictor_position_buckets"])
        out.append(np.asarray(s)[:hi - lo])
    return np.concatenate(out)


def reference_logits(stack: Stack, prompts: np.ndarray, tokens: np.ndarray,
                     mm: reference.Matmul, rows: int = 8) -> np.ndarray:
    """Teacher-forced fuser logits [R, T, V] over BOS + served tokens."""
    cfg = stack.cfg
    dec = np.concatenate([np.full((len(tokens), 1), yardstick.BOS_ID, np.int32),
                          tokens[:, :-1]], axis=1)
    out = []
    for lo, hi in _blocks(len(prompts), rows):
        enc = np.zeros((rows, prompts.shape[1]), np.int32)
        d = np.zeros((rows, dec.shape[1]), np.int32)
        enc[:hi - lo], d[:hi - lo] = prompts[lo:hi], dec[lo:hi]
        logits = reference.fuser_logits(stack.weights["fuser"], jnp.asarray(enc),
                                        jnp.asarray(d), mm=mm, eps=cfg["fuser_norm_eps"],
                                        theta=cfg["fuser_rope_theta"])
        out.append(np.asarray(logits)[:hi - lo])
    return np.concatenate(out)


def check(stack: Stack, served: Dict[int, Served], batches: List[List[int]],
          sample: Sequence[int], limits: Dict[str, float], ref: reference.Matmul,
          control: Optional[reference.Matmul] = None) -> List[Check]:
    """The numbers that decide ``correct``, the references computed with
    ``ref``.  With ``control``, the plain reference computed with that
    lower precision stands in for the program's predictor and fuser, and
    is read against the reference the same way."""
    cfg, pool = stack.cfg, stack.pool
    mask_bad = eps_bad = member_bad = 0
    for rows in batches:
        rows = [r for r in rows if r in served]
        if not rows:
            continue
        qs = [served[r].query for r in rows]
        costs = yardstick.cost_matrix(pool, qs)
        eps = served[rows[0]].eps
        want = reference.knapsack_masks(np.stack([served[r].scores for r in rows]),
                                        costs.astype(np.float32), eps, cfg["budget_buckets"])
        for k, r in enumerate(rows):
            got = served[r].mask
            mask_bad += int((got != want[k]).any())
            frac = costs[k][got].sum() / costs[k].sum()
            fallback = got.sum() == 1 and costs[k][got][0] == costs[k].min() and (
                costs[k].min() / costs[k].sum() > eps)
            eps_bad += int(frac > eps * (1 + 1e-9) and not fallback)
            for j, m in enumerate(pool):
                expect = (yardstick.sim_member_text(sim_seed(stack.seed), j, m,
                                                    served[r].query, served[r].cap)
                          if got[j] else None)
                member_bad += int(served[r].member_texts[j] != expect)

    sample = [r for r in sample if r in served]
    queries = [served[r].query for r in sample]
    ref_scores = reference_scores(stack, queries, ref)
    if control is not None:
        got_scores = reference_scores(stack, queries, control)
    else:
        got_scores = np.stack([served[r].scores for r in sample])
    score_err = float(np.abs(got_scores - ref_scores).max() / np.abs(ref_scores).max())

    lengths = np.asarray([len(served[r].tokens) for r in sample])
    width = int(lengths.max())
    tokens = np.zeros((len(sample), width), np.int32)
    for k, r in enumerate(sample):
        tokens[k, :lengths[k]] = served[r].tokens
    # the reference builds each prompt from its own simulated member answers
    prompts = np.stack([
        yardstick.fusion_prompt(
            served[r].query.query,
            [yardstick.sim_member_text(sim_seed(stack.seed), j, m, served[r].query,
                                       served[r].cap)
             for j, m in enumerate(pool) if served[r].mask[j]],
            cfg["max_query_len"], served[r].cap, cfg["max_fusion_len"])
        for r in sample])
    logits = reference_logits(stack, prompts, tokens, ref)
    control_logits = (reference_logits(stack, prompts, tokens, control)
                      if control is not None else None)
    gaps = reference.served_token_gaps(logits, tokens, lengths, control_logits)
    return [
        Check("member_mismatch", float(member_bad), limits["member_mismatch"]),
        Check("mask_mismatch", float(mask_bad), limits["mask_mismatch"]),
        Check("eps_violations", float(eps_bad), limits["eps_violations"]),
        Check("score_err", score_err, limits["score_err"]),
        Check("fuse_gap", float(gaps.max()) if gaps.size else float("nan"), limits["fuse_gap"]),
    ]

