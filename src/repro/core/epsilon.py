"""The bi-objective problem and its ε-constraint reduction (paper §2.1-2.2).

Objectives over a subset H of the pool M:
    max  Σ_{m∈H} r(m, q)            (quality, Eq. 2)
    min  Σ_{m∈H} c_i · t_i(q)       (cost, Eq. 1)

ε-constraint (Haimes & Wismer 1971): fix a per-query budget ε on cost and
maximize quality subject to it — a 0/1 knapsack (Eq. 3).  Sweeping ε traces
the Pareto frontier of the bi-objective problem.
"""

from __future__ import annotations

import dataclasses
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cost import normalize_costs
from repro.core.knapsack import knapsack_select, shift_scores


@dataclasses.dataclass(frozen=True)
class EpsilonConstraint:
    """A per-query FLOPs budget, expressed as in the paper's experiments:
    a fraction of the cost of an LLM-BLENDER response (= querying the whole
    pool)."""

    fraction: float  # of full-ensemble cost
    buckets: int = 256  # DP cost discretization

    def budget_flops(self, query_costs: np.ndarray) -> float:
        return float(self.fraction * np.sum(query_costs))


def select_under_budget(
    quality: jax.Array,  # [Q, N] predicted scores (may be negative, BARTScore-like)
    costs_flops: jax.Array,  # [Q, N] per-query FLOPs
    eps: EpsilonConstraint,
    impl: str = "lax",
) -> jax.Array:
    """MODI's selection step: alpha-shift scores, bucketize costs, knapsack.

    ``impl`` picks the bitmask-DP backend: ``"lax"`` (batched jittable
    loop, the serving default) or ``"pallas"`` (the VMEM-resident TPU
    kernel in ``repro.kernels.knapsack``).  Both produce identical
    selections.  Costs are bucketed on the host in float64
    (:func:`repro.core.cost.normalize_costs`), so every backend solves the
    same integer knapsack; the DP itself runs on the device."""
    quality = jnp.asarray(quality, jnp.float32)
    profits, _ = shift_scores(quality)
    costs_flops = np.asarray(costs_flops, np.float64)
    budget_flops = eps.fraction * np.sum(costs_flops, axis=1, keepdims=True)  # [Q,1]
    int_costs, _ = normalize_costs(costs_flops, budget_flops, eps.buckets)
    # any weight above the budget is equally infeasible; the cap keeps a
    # tiny ε from overflowing int32
    int_costs = jnp.asarray(np.minimum(int_costs, eps.buckets + 1), jnp.int32)
    if impl == "pallas":
        from repro.kernels.knapsack import knapsack_select_pallas

        return knapsack_select_pallas(profits, int_costs, eps.buckets)
    if impl != "lax":
        raise ValueError(f"unknown knapsack impl {impl!r}; expected 'lax' or 'pallas'")
    return knapsack_select(profits, int_costs, eps.buckets)


def pareto_sweep(
    quality: np.ndarray,  # [N] true or predicted per-model scores for one query
    costs: np.ndarray,  # [N] FLOPs
    fractions: Sequence[float] = tuple(np.linspace(0.05, 1.0, 20)),
    buckets: int = 256,
) -> List[Tuple[float, float, np.ndarray]]:
    """ε-sweep for one query: [(cost_fraction, total_quality, mask)] —
    the achievable quality-cost frontier (paper §2.2 motivation)."""
    out = []
    q = jnp.asarray(quality)[None, :]
    c = jnp.asarray(costs, jnp.float32)[None, :]
    # dominance is judged on the alpha-shifted profits the knapsack
    # optimizes (Eq. 4) — raw BARTScores are negative, so the raw sum would
    # spuriously rank the empty set above every selection.
    profits = np.asarray(shift_scores(jnp.asarray(quality))[0])
    for frac in fractions:
        eps = EpsilonConstraint(fraction=float(frac), buckets=buckets)
        mask = np.asarray(select_under_budget(q, c, eps))[0]
        total_q = float(np.sum(np.where(mask, profits, 0.0)))
        total_c = float(np.sum(np.where(mask, costs, 0.0)) / max(np.sum(costs), 1e-9))
        out.append((total_c, total_q, mask))
    # keep non-dominated
    frontier = []
    best = -np.inf
    for tc, tq, m in sorted(out, key=lambda t: (t[0], -t[1])):
        if tq > best:
            frontier.append((tc, tq, m))
            best = tq
    return frontier
