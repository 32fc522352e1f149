"""Kaplan et al. (2020) inference-cost model (paper §2.1).

    c_forward ≈ 2·N + 2·n_layer·n_ctx·d_model   [FLOPs per token]

where N is non-embedding parameters.  The paper's cost objective is
``sum_i c_i · t_i(q)`` over the selected subset; ``t_i`` maps a query to the
expected token count under model i.  For MoE members we use *activated*
non-embedding parameters (extension noted in DESIGN.md §3).
"""

from __future__ import annotations

import dataclasses
from typing import Mapping, Sequence

import numpy as np

from repro.models.config import ModelConfig


@dataclasses.dataclass(frozen=True)
class CostModel:
    """Per-model FLOPs/token cost, Kaplan-style."""

    name: str
    params_active: int  # activated non-embedding params
    n_layer: int
    d_model: int

    def flops_per_token(self, n_ctx: int) -> float:
        return 2.0 * self.params_active + 2.0 * self.n_layer * n_ctx * self.d_model

    def query_cost(self, n_ctx: int, n_tokens: float) -> float:
        """Total FLOPs to answer a query: tokens generated x cost/token."""
        return self.flops_per_token(n_ctx) * float(n_tokens)


def cost_model_from_config(cfg: ModelConfig) -> CostModel:
    return CostModel(
        name=cfg.name,
        params_active=cfg.active_non_embedding_params(),
        n_layer=cfg.num_layers + (cfg.enc_layers if cfg.is_encoder_decoder else 0),
        d_model=cfg.d_model,
    )


def pool_costs(
    cfgs: Sequence[ModelConfig], n_ctx: int, tokens_per_query: Mapping[str, float] | float
) -> np.ndarray:
    """FLOPs cost vector for one query across a pool."""
    out = []
    for cfg in cfgs:
        cm = cost_model_from_config(cfg)
        t = tokens_per_query if isinstance(tokens_per_query, (int, float)) else tokens_per_query[cfg.name]
        out.append(cm.query_cost(n_ctx, t))
    return np.asarray(out, np.float64)


def normalize_costs(costs: np.ndarray, budget, buckets: int = 256):
    """Discretize FLOPs costs into integer knapsack weights.

    The paper's Algorithm 1 indexes the DP table by integer cost; real FLOP
    counts are ~1e12, so we quantize weights to ``buckets`` levels of the
    budget.  Ceil-rounding keeps the constraint conservative (never exceeds
    the true budget).  ``budget`` is a scalar or broadcasts against
    ``costs`` (one budget per query row, ``[Q, 1]``).  The arithmetic is
    float64 on the host, so the weights do not depend on how a backend
    rounds float32 division: on tie-heavy inputs ``costs / scale`` is often
    an exact integer, and a float32 quotient lands on either side of the
    ceil.  A zero budget (an all-zero cost row) makes every member free.
    Returns (int_costs, int_budget).
    """
    scale = np.asarray(budget, np.float64) / buckets
    scale = np.where(scale > 0, scale, 1.0)
    int_costs = np.ceil(np.asarray(costs, np.float64) / scale).astype(np.int64)
    int_costs = np.maximum(int_costs, 1)
    return int_costs, int(buckets)
