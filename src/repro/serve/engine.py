"""MODI ensemble serving engine (paper §2.3 end-to-end).

The engine is the composition point of four layers, each replaceable on
its own:

* request surface — :class:`repro.serve.api.EnsembleRequest` /
  :class:`EnsembleResponse` (per-request budget, policy, generation length);
* selection — any :class:`repro.core.SelectionPolicy`, constructed by
  name through :func:`repro.core.make_policy`, resolved **per request**
  and grouped so each distinct (policy, budget) runs one vectorized
  ``select`` over its rows;
* member generation — a :class:`repro.serve.backends.MemberBackend`
  (behavioural simulator or live JAX LMs), batched per member over the
  rows that selected it;
* fusion — GEN-FUSER greedy decoding over the selected responses.

Pipeline per admission micro-batch:
    1. predictor scores the query for every pool member  (r_hat [B, N])
    2. Kaplan costs c_i · t_i(q) per member              (costs [B, N])
    3. per-request policy (MODI = ε-constrained knapsack) (mask [B, N])
    4. backend generates for the selected members
    5. GEN-FUSER fuses the selected responses into the final answer
    6. cost accounting: realized FLOPs vs the full-ensemble (LLM-BLENDER)

``serve(records)`` is the offline batch entry point (Table-1 benchmark);
``serve_requests(requests)`` is the request-level path the
:class:`repro.serve.scheduler.Scheduler` drives for online traffic.
Both produce identical outputs for identical inputs.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.epsilon import EpsilonConstraint
from repro.core.fusion import build_fusion_batch
from repro.core.predictor import QualityPredictor
from repro.core.selector import SelectionPolicy, make_policy, realized_cost_fraction
from repro.data.mixinstruct import PoolMemberSpec, Record, query_cost_matrix
from repro.data.tokenizer import TOKENIZER
from repro.models.encdec import EncDecLM
from repro.serve import spans
from repro.serve.api import EnsembleRequest, EnsembleResponse, requests_from_records
from repro.serve.backends import (
    GenerationCall,
    HostFailure,
    LiveLMBackend,
    LiveMember,
    MemberBackend,
    MemberFailure,
    SimBackend,
)
from repro.serve.dispatch import (
    BucketLadder,
    EncDecGenerateDispatcher,
    StreamingEncDecBatcher,
)
from repro.serve.generate import greedy_generate_encdec


@dataclasses.dataclass
class _BatchPlan:
    """Everything ``serve_requests`` computes before fusion, so the batch
    and streaming paths share one pre-fusion pipeline (predict → select →
    member generation) and one settlement path, and can only diverge in
    *how* fusion tokens are produced — never in what they are."""

    records: List[Record]
    queries: List[str]
    r_hat: np.ndarray  # [B, N]
    costs: np.ndarray  # [B, N]
    mask: np.ndarray  # [B, N]
    policy_names: List[str]
    dropped: frozenset
    max_new_per_row: List[int]
    member_out: List[List[Optional[str]]]
    start_ns: int  # perf_counter_ns at the batch's start: timing's total_s
    # the stage spans, whose durations fill EnsembleResponse.timing
    predict: spans.Span
    select: spans.Span
    members: spans.Span


@dataclasses.dataclass
class ServeResult:
    """Batch-level view of a served record list (offline evaluation)."""

    responses: List[str]
    mask: np.ndarray  # [B, N] selections
    cost_fraction: np.ndarray  # [B] realized / full-ensemble cost
    member_responses: List[List[Optional[str]]]  # [B][N] (None if unselected)
    predicted_quality: np.ndarray  # [B, N]


class EnsembleServer:
    def __init__(
        self,
        pool: Sequence[PoolMemberSpec],
        policy: SelectionPolicy,
        predictor: QualityPredictor,
        predictor_params: dict,
        fuser: EncDecLM,
        fuser_params: dict,
        live_members: Optional[Sequence[LiveMember]] = None,
        backend: Optional[MemberBackend] = None,
        max_query_len: int = 96,
        max_fusion_len: int = 512,
        max_new_tokens: int = 32,
        max_member_tokens: Optional[int] = None,
        sim_seed: int = 0,
        fast_generate: bool = True,
        bucket_ladder: Optional[BucketLadder] = None,
        warm_shapes: Optional[Sequence[Tuple[int, int]]] = None,
    ):
        self.pool = list(pool)
        self.policy = policy
        self.predictor = predictor
        self.predictor_params = predictor_params
        # the predictor as one jitted program per batch rung: its jit cache
        # keys on the padded token shape, so each rung compiles once (on
        # first use or in warm) and every later batch reuses it.  The
        # weights are an argument, never baked-in constants.
        self._predict_fn = jax.jit(predictor.apply)
        self.fuser = fuser
        self.fuser_params = fuser_params
        ladder = bucket_ladder or BucketLadder()
        # the Scheduler reads this to target batch sizes that land on
        # already-compiled rungs (continuous batch formation)
        self.bucket_ladder = ladder
        if backend is None:
            if live_members is not None:
                backend = LiveLMBackend(list(live_members), max_query_len=max_query_len,
                                        fast=fast_generate, ladder=ladder)
            else:
                backend = SimBackend(self.pool, seed=sim_seed)
        if backend.num_members() != len(self.pool):
            raise ValueError(
                f"backend serves {backend.num_members()} members but the pool "
                f"has {len(self.pool)}"
            )
        self.backend = backend
        self.max_query_len = max_query_len
        self.max_fusion_len = max_fusion_len
        self.max_new_tokens = max_new_tokens
        # cap on member-response tokens entering fusion; None = never truncate
        # below a row's own max_new cap (the old behaviour hardcoded 64)
        self.max_member_tokens = max_member_tokens
        self.fuser_dispatch: Optional[EncDecGenerateDispatcher] = (
            EncDecGenerateDispatcher(fuser, fuser_params, ladder=ladder)
            if fast_generate else None
        )
        # lazily-built continuous-batching fuser for the streaming path
        self._stream_fuser: Optional[StreamingEncDecBatcher] = None
        if warm_shapes:
            self.warm(warm_shapes)
        self.stats: Dict[str, float] = {
            "queries": 0, "batches": 0, "flops": 0.0, "full_flops": 0.0,
        }

    # ------------------------------------------------------------------
    def warm(self, shapes: Sequence[Tuple[int, int]]) -> None:
        """Pre-compile generate buckets for (batch, max_new) shapes, and the
        predictor's program for each batch's rung, so the first admission
        micro-batches don't pay the compile.  Backends opt in by exposing
        ``warm(shapes)`` (optional protocol hook — see LiveLMBackend);
        backends without one have nothing to compile."""
        for rung in sorted({self.bucket_ladder.batch_bucket(b) for b, _ in shapes}):
            self.predict_quality([""] * rung)
        if self.fuser_dispatch is not None:
            self.fuser_dispatch.warm(
                [(b, self.max_fusion_len, n) for b, n in shapes]
            )
        backend_warm = getattr(self.backend, "warm", None)
        if callable(backend_warm):
            backend_warm(shapes)

    def generate_compiles(self) -> Dict[str, int]:
        """Live XLA compile counts on the generate fast paths (0 when the
        corresponding path is disabled or has not run).  Backends report
        theirs through an optional ``compiles()`` hook."""
        fuser = self.fuser_dispatch.compiles if self.fuser_dispatch else 0
        backend_compiles = getattr(self.backend, "compiles", None)
        members = backend_compiles() if callable(backend_compiles) else 0
        stream = self._stream_fuser.compiles if self._stream_fuser else 0
        return {"fuser": fuser, "members": members, "stream": stream,
                "total": fuser + members + stream}

    @property
    def predict_compiles(self) -> int:
        """Predictor programs built: one per batch rung served or warmed."""
        return self._predict_fn._cache_size()

    # ------------------------------------------------------------------
    def predict_quality(self, queries: List[str]) -> np.ndarray:
        """[B, N] predicted quality.  Rows pad up to the batch's rung with
        pad-only queries; the predictor has no cross-row operation, so the
        padding cannot reach real rows, and is sliced off."""
        toks = TOKENIZER.batch_encode(queries, self.max_query_len, cls=True)
        b = len(toks)
        padded = np.full((self.bucket_ladder.batch_bucket(b), toks.shape[1]),
                         TOKENIZER.pad_id, np.int32)
        padded[:b] = toks
        with spans.span("serve.predict.apply"):
            r_hat = self._predict_fn(self.predictor_params, jnp.asarray(padded))
        with spans.span("serve.predict.read"):
            return np.asarray(r_hat)[:b]

    # ------------------------------------------------------------------
    def _policy_key(self, req: EnsembleRequest) -> Tuple:
        """Hashable group key that fully determines the resolved policy.

        A request naming a policy gets a fresh registry construction; a
        request overriding only the budget (or other fields) keeps every
        other knob of the server's configured policy instance."""
        if req.policy is not None:
            kwargs = dict(req.policy_kwargs or {})
            if req.budget is not None:
                kwargs["budget"] = req.budget
            return (req.policy, tuple(sorted(kwargs.items())))
        changes = dict(req.policy_kwargs or {})
        if req.budget is not None:
            eps = getattr(self.policy, "eps", None)
            if isinstance(eps, EpsilonConstraint):
                changes["eps"] = EpsilonConstraint(req.budget, eps.buckets)
            # budget-insensitive default policy: the override is a no-op
        if not changes:
            return ("__default__",)
        return ("__default__", tuple(sorted(changes.items())))

    def _build_policy(self, key: Tuple) -> SelectionPolicy:
        """Construct the policy a :meth:`_policy_key` describes (once per group)."""
        if key == ("__default__",):
            return self.policy
        name, items = key
        if name == "__default__":
            return dataclasses.replace(self.policy, **dict(items))
        return make_policy(name, **dict(items))

    def _select(self, requests: List[EnsembleRequest], r_hat: np.ndarray,
                costs: np.ndarray,
                masked_members: frozenset = frozenset(),
                ) -> Tuple[np.ndarray, List[str]]:
        """[B, N] mask + per-request policy name, grouping rows that share a
        resolved policy so each policy is built and vector-selected once.

        ``masked_members`` (dead hosts' members) re-solves budget-aware
        policies over the surviving columns only: the knapsack sees the
        survivors' costs and an ε budget over the survivors' full-ensemble
        cost, instead of wasting budget headroom on members that cannot
        serve.  Policies without an ε constraint (and index-keyed
        baselines, whose indices address the full pool) run on the full
        matrix; the caller's exclusion guard strips dead members from
        their masks afterwards."""
        b, n = r_hat.shape
        groups: Dict[Tuple, Tuple[SelectionPolicy, List[int]]] = {}
        for i, req in enumerate(requests):
            key = self._policy_key(req)
            if key not in groups:
                groups[key] = (self._build_policy(key), [])
            groups[key][1].append(i)
        mask = np.zeros((b, n), bool)
        names = [""] * b
        alive = np.asarray([j for j in range(n) if j not in masked_members],
                           dtype=np.intp)
        for policy, rows in groups.values():
            resolve_masked = (
                bool(masked_members)
                and isinstance(getattr(policy, "eps", None), EpsilonConstraint)
            )
            cols = alive if resolve_masked else slice(None)
            with spans.span("serve.select.solve", rows=len(rows)):
                picked = policy.select(jnp.asarray(r_hat[rows][:, cols]),
                                       jnp.asarray(costs[rows][:, cols]))
            with spans.span("serve.select.read"):
                picked = np.asarray(picked)
            if resolve_masked:
                sub = np.zeros((len(rows), n), bool)
                sub[:, alive] = picked
            else:
                sub = picked
            for local, i in enumerate(rows):
                mask[i] = sub[local]
                names[i] = policy.name
        return mask, names

    # ------------------------------------------------------------------
    def _generate_members(self, records: List[Record], mask: np.ndarray,
                          max_new_per_row: List[int]) -> List[List[Optional[str]]]:
        """[B][N] texts, batched per member over its selected rows.

        Per-row token caps travel to the backend, which owns truncation
        (see backends.MemberBackend): each returned text is already at
        most its row's cap, so no re-tokenization happens here.  Caps are
        per row, never per micro-batch, so texts cannot depend on which
        other rows share the batch.

        A backend exposing ``generate_many(calls)`` (optional protocol
        hook — the cluster router's fan-out seam) receives the whole
        batch's calls at once so per-host shards can generate
        concurrently; it owns the same failure attribution this loop
        applies, and its results are order- and byte-identical to the
        sequential path."""
        b, n = mask.shape
        out: List[List[Optional[str]]] = [[None] * n for _ in range(b)]
        calls: List[GenerationCall] = []
        call_rows: List[np.ndarray] = []
        for j in range(n):
            rows = np.flatnonzero(mask[:, j])
            if rows.size == 0:
                continue
            calls.append(GenerationCall(
                j, tuple(records[i] for i in rows),
                tuple(max_new_per_row[i] for i in rows)))
            call_rows.append(rows)
        many = getattr(self.backend, "generate_many", None)
        if callable(many):
            texts_per_call = many(calls)
        else:
            texts_per_call = []
            for call in calls:
                try:
                    texts_per_call.append(self.backend.generate(
                        call.member_idx, list(call.records),
                        list(call.max_new_tokens)))
                except (MemberFailure, HostFailure):
                    # already attributed (member-level, or a whole placement
                    # host via the cluster router) — let the Scheduler hedge
                    raise
                except Exception as exc:
                    # attribute the fault to the member so the Scheduler can
                    # hedge onto the survivors instead of failing the batch
                    raise MemberFailure(call.member_idx, exc) from exc
        for call, rows, texts in zip(calls, call_rows, texts_per_call):
            for i, text in zip(rows, texts):
                out[i][call.member_idx] = text
        return out

    def _apply_exclusions(self, mask: np.ndarray, costs: np.ndarray,
                          exclude_members: frozenset) -> np.ndarray:
        """Zero excluded members out of the selection; rows left empty fall
        back to the cheapest *surviving* member so every query still gets
        an answer (the same guard ModiPolicy applies for an over-tight ε).
        Used by the Scheduler's hedged retry after a MemberFailure."""
        excl = sorted(exclude_members)
        if not excl:
            return mask
        n = mask.shape[1]
        if not all(0 <= j < n for j in excl):
            raise ValueError(f"exclude_members {excl} out of range for pool of {n}")
        if len(excl) >= n:
            raise ValueError("cannot exclude every pool member")
        mask = mask.copy()
        mask[:, excl] = False
        empty = ~mask.any(axis=1)
        if empty.any():
            alive_costs = costs.copy()
            alive_costs[:, excl] = np.inf
            cheapest = np.argmin(alive_costs, axis=1)
            mask[np.flatnonzero(empty), cheapest[empty]] = True
        return mask

    def _fusion_inputs(self, queries: List[str],
                       member_out: List[List[Optional[str]]],
                       mask: np.ndarray, max_new: int) -> np.ndarray:
        """Encoder tokens [B, max_fusion_len] for the GEN-FUSER — shared by
        the batch-boundary and streaming fusion paths, so both decode the
        very same prompt."""
        with spans.span("serve.fusion_inputs", rows=len(queries)):
            b, n = mask.shape
            # member texts are pre-truncated to their row's max_new cap; the
            # fusion-side cap only narrows further if explicitly configured
            cap = max_new if self.max_member_tokens is None else self.max_member_tokens
            flat = [
                (i, j, text)
                for i, row in enumerate(member_out)
                for j, text in enumerate(row)
                if text is not None
            ]
            resp_tokens = np.full((b, n, cap), TOKENIZER.pad_id, np.int32)
            if flat:
                # one batched tokenizer call over flat index arrays instead of a
                # [B, N] Python grid of encode+assign steps
                ii = np.fromiter((f[0] for f in flat), np.intp, len(flat))
                jj = np.fromiter((f[1] for f in flat), np.intp, len(flat))
                resp_tokens[ii, jj] = TOKENIZER.pad_batch(
                    [TOKENIZER.encode(f[2]) for f in flat], cap
                )
            q_tokens = TOKENIZER.batch_encode(queries, self.max_query_len)
            return build_fusion_batch(
                q_tokens, resp_tokens, mask, TOKENIZER.sep_id, self.max_fusion_len,
                TOKENIZER.pad_id,
            )

    def _fuse(self, queries: List[str], member_out: List[List[Optional[str]]],
              mask: np.ndarray, max_new: int) -> np.ndarray:
        fuse_in = self._fusion_inputs(queries, member_out, mask, max_new)
        if self.fuser_dispatch is not None:
            return self.fuser_dispatch(fuse_in, max_new)
        return greedy_generate_encdec(
            self.fuser, self.fuser_params, fuse_in, max_new=max_new
        )

    # ------------------------------------------------------------------
    def serve_requests(
        self,
        requests: List[EnsembleRequest],
        exclude_members: frozenset = frozenset(),
        masked_members: frozenset = frozenset(),
    ) -> List[EnsembleResponse]:
        """Serve one admission micro-batch of requests (the Scheduler's path).

        ``exclude_members`` drops those pool members from every request's
        selection *after* the policy runs (hedged retry around a down
        member); requests whose selection never touched the excluded
        members produce byte-identical responses with or without the
        exclusion.  ``masked_members`` (members dead with their placement
        host — see :class:`~repro.serve.backends.HostFailure`) goes
        further: budget-aware policies re-solve their knapsack over the
        surviving members only, so the ε budget re-targets the survivors'
        full-ensemble cost instead of carrying dead members' costs."""
        if not requests:
            return []
        plan = self._plan_batch(requests, exclude_members, masked_members)

        max_new = max(plan.max_new_per_row)
        with spans.span("serve.fuse") as fuse:
            fused = self._fuse(plan.queries, plan.member_out, plan.mask, max_new)

        row_tokens = [fused[i, :plan.max_new_per_row[i]]
                      for i in range(len(requests))]
        return self._settle(plan, row_tokens, fuse)

    def _plan_batch(self, requests: List[EnsembleRequest],
                    exclude_members: frozenset,
                    masked_members: frozenset) -> _BatchPlan:
        """Pre-fusion pipeline (predict → select → member generation),
        shared verbatim by the batch-boundary and streaming paths."""
        start_ns = time.perf_counter_ns()
        records = [req.resolve_record() for req in requests]
        queries = [r.query for r in records]

        with spans.span("serve.predict", rows=len(queries),
                        rung=self.bucket_ladder.batch_bucket(len(queries))) as predict:
            r_hat = self.predict_quality(queries)

        costs = query_cost_matrix(self.pool, records)
        masked = frozenset(masked_members)
        dropped = frozenset(exclude_members) | masked
        with spans.span("serve.select", rows=len(queries)) as select:
            mask, policy_names = self._select(requests, r_hat, costs,
                                              masked_members=masked)
            if dropped:
                mask = self._apply_exclusions(mask, costs, dropped)

        max_new_per_row = [
            self.max_new_tokens if req.max_new_tokens is None else req.max_new_tokens
            for req in requests
        ]
        with spans.span("serve.members", rows=len(queries)) as members:
            member_out = self._generate_members(records, mask, max_new_per_row)
        return _BatchPlan(
            records=records, queries=queries, r_hat=r_hat, costs=costs,
            mask=mask, policy_names=policy_names, dropped=dropped,
            max_new_per_row=max_new_per_row, member_out=member_out,
            start_ns=start_ns, predict=predict, select=select, members=members,
        )

    def _settle(self, plan: _BatchPlan, row_tokens: Sequence,
                fuse: spans.Span) -> List[EnsembleResponse]:
        """Cost accounting + response assembly over per-row fused tokens
        (a ``[row_new]`` slice from the batch path, or the exact emitted
        sequence from the streaming path — both decode to the same text).
        ``timing`` holds the stage spans' durations, and ``total_s`` the
        batch's from its start to its cost accounting."""
        with spans.span("serve.settle", rows=len(plan.records)):
            mask, costs, dropped = plan.mask, plan.costs, plan.dropped
            frac = np.asarray(realized_cost_fraction(jnp.asarray(mask), jnp.asarray(costs)))
            realized = np.sum(np.where(mask, costs, 0.0), axis=1)
            # full-ensemble cost over the servable members only — the base a
            # degraded batch settles against (ε re-targeted the survivors)
            servable = np.asarray([j not in dropped for j in range(costs.shape[1])])
            survivor_cost = np.sum(np.where(servable, costs, 0.0), axis=1)
            timing = {
                "predict_s": plan.predict.seconds, "select_s": plan.select.seconds,
                "generate_s": plan.members.seconds, "fuse_s": fuse.seconds,
                "total_s": (time.perf_counter_ns() - plan.start_ns) / 1e9,
            }

            self.stats["queries"] += len(plan.records)
            self.stats["batches"] += 1
            self.stats["flops"] += float(realized.sum())
            self.stats["full_flops"] += float(np.sum(costs))

            responses = []
            for i in range(len(plan.records)):
                responses.append(EnsembleResponse(
                    text=TOKENIZER.decode(row_tokens[i]),
                    member_texts=plan.member_out[i],
                    mask=mask[i],
                    realized_cost=float(realized[i]),
                    cost_fraction=float(frac[i]),
                    predicted_quality=plan.r_hat[i],
                    policy_name=plan.policy_names[i],
                    timing=dict(timing),
                    degraded=bool(dropped),
                    missing_members=tuple(sorted(dropped)),
                    survivor_cost=float(survivor_cost[i]),
                ))
            return responses

    # ------------------------------------------------------------------
    def stream_fuser(self, capacity: int = 8,
                     prefill_chunk: Optional[int] = None,
                     ) -> StreamingEncDecBatcher:
        """The continuous-batching fuser, built on first use.  ``capacity``
        and ``prefill_chunk`` only apply to that first construction — the
        in-flight state is persistent, so later callers share it."""
        if self._stream_fuser is None:
            self._stream_fuser = StreamingEncDecBatcher(
                self.fuser, self.fuser_params, enc_seq=self.max_fusion_len,
                capacity=capacity, ladder=self.bucket_ladder,
                prefill_chunk=prefill_chunk,
            )
        return self._stream_fuser

    def serve_requests_stream(
        self,
        requests: List[EnsembleRequest],
        on_token=None,
        exclude_members: frozenset = frozenset(),
        masked_members: frozenset = frozenset(),
        capacity: int = 8,
        prefill_chunk: Optional[int] = None,
    ) -> List[EnsembleResponse]:
        """:meth:`serve_requests` with token-level continuous fusion: the
        GEN-FUSER decodes through the persistent :meth:`stream_fuser`
        batch, firing ``on_token(i, tokens_so_far)`` after every decode
        step of row ``i``.  Final responses are byte-identical to
        :meth:`serve_requests` — fusion prompts come from the same
        :meth:`_fusion_inputs`, the step body is the batch scan's body,
        and rows are independent, so co-residency (which rows share a
        decode step) cannot leak into any row's bytes.

        Rows whose cap exceeds the stream fuser's ``max_new_cap`` (or a
        server built with ``fast_generate=False``) fall back to the
        batch-boundary path for the whole micro-batch: ``on_token`` then
        fires once per row with the final tokens, so streaming consumers
        degrade to one coarse event rather than an error."""
        if not requests:
            return []
        plan = self._plan_batch(requests, exclude_members, masked_members)
        max_new = max(plan.max_new_per_row)

        fuser = (self.stream_fuser(capacity, prefill_chunk)
                 if self.fuser_dispatch is not None else None)
        if fuser is None or max_new > fuser.max_new_cap:
            with spans.span("serve.fuse") as fuse:
                fused = self._fuse(plan.queries, plan.member_out, plan.mask, max_new)
            row_tokens = [fused[i, :plan.max_new_per_row[i]]
                          for i in range(len(requests))]
            if on_token is not None:
                for i, toks in enumerate(row_tokens):
                    on_token(i, [int(t) for t in toks])
            return self._settle(plan, row_tokens, fuse)

        done_tokens: Dict[int, List[int]] = {}
        errors: List[BaseException] = []
        with spans.span("serve.fuse") as fuse:
            fuse_in = self._fusion_inputs(plan.queries, plan.member_out,
                                          plan.mask, max_new)
            fuser.submit(
                fuse_in, list(plan.max_new_per_row),
                on_token=on_token,
                on_done=lambda i, toks: done_tokens.__setitem__(i, toks),
                on_error=lambda i, exc: errors.append(exc),
            )
            fuser.pump()
        if errors:
            raise errors[0]
        row_tokens = [done_tokens[i] for i in range(len(requests))]
        return self._settle(plan, row_tokens, fuse)

    # ------------------------------------------------------------------
    def serve(self, records: List[Record],
              exclude_members: frozenset = frozenset()) -> ServeResult:
        """Offline batch entry point: one micro-batch over all records."""
        n = len(self.pool)
        out = self.serve_requests(requests_from_records(records),
                                  exclude_members=exclude_members)
        if not out:
            return ServeResult(
                responses=[],
                mask=np.zeros((0, n), bool),
                cost_fraction=np.zeros(0),
                member_responses=[],
                predicted_quality=np.zeros((0, n), np.float32),
            )
        return ServeResult(
            responses=[r.text for r in out],
            mask=np.stack([r.mask for r in out]),
            cost_fraction=np.asarray([r.cost_fraction for r in out]),
            member_responses=[r.member_texts for r in out],
            predicted_quality=np.stack([r.predicted_quality for r in out]),
        )
