"""Pallas TPU kernel: batched 0/1-knapsack bitmask DP (paper Algorithm 1).

The paper runs its DP once per query on the host; at serving batch sizes the
selection step becomes a per-batch hot spot, so we push the DP onto the TPU:

* one grid program per query *block* — the whole DP row ``dp[0..budget]``
  AND the packed selection row (one 32-bit word per 32 items per
  capacity) for ``BQ`` queries stay resident in VMEM (a few KB each);
* the capacity axis is padded to a whole number of 128-lane vregs, and the
  item loop is the sequential wavefront: the row update
  ``dp'[j] = max(dp[j], dp[j-c] + p)`` and the mask update
  ``mask'[j] = take ? mask[j-c] | (1 << i) : mask[j]`` are fully
  vectorized on the VPU.  The shift by each row's own cost ``c`` is a lane
  rotation (``pltpu.roll``) with the wrapped lanes ``j < c`` masked, because
  Mosaic has no per-row dynamic lane gather;
* costs arrive through scalar prefetch (SMEM), since a rotation amount must
  be a scalar; profits stay a VMEM block and column ``i`` is picked with an
  iota mask instead of a dynamic lane index;
* only the final DP row and the packed selection rows stream out to HBM;
  the wrapper reads the selection at ``j = budget``.  There is no
  ``[N, Q, B+1]`` take tensor and no second backtrack loop — the strict
  improvement test reproduces Algorithm 1's ties-keep-not-taken backtrack
  bit for bit (see ``core.knapsack``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.knapsack import mask_words

NEG_INF = -1e30
LANES = 128


def _kernel(costs_ref, profits_ref, dp_ref, masks_ref, *, n_items: int,
            bp1: int, n_words: int, width: int):
    # costs_ref: SMEM [Qp * N] int32; profits_ref: [BQ, N] f32;
    # dp_ref: [BQ, width] f32; masks_ref: [BQ, W * width] int32
    bq = dp_ref.shape[0]
    base = pl.program_id(0) * (bq * n_items)
    rows = jax.lax.broadcasted_iota(jnp.int32, (bq, width), 0)
    js = jax.lax.broadcasted_iota(jnp.int32, (bq, width), 1)
    items = jax.lax.broadcasted_iota(jnp.int32, (bq, n_items), 1)
    profits = profits_ref[...]

    def item_step(i, carry):
        dp, masks = carry  # dp [BQ, width] f32; masks: W x [BQ, width] int32
        p = jnp.sum(jnp.where(items == i, profits, 0.0), axis=1, keepdims=True)
        c_rows = jnp.zeros((bq, width), jnp.int32)
        shifted_dp = dp
        shifted_masks = list(masks)
        for r in range(bq):
            # a cost above the budget masks every lane <= budget; clamping
            # keeps the rotation inside the padded row
            c = jnp.minimum(costs_ref[base + r * n_items + i], bp1)
            here = rows == r
            c_rows = jnp.where(here, c, c_rows)
            # roll(x, c)[j] = x[j - c]: lane j reads capacity j - c
            shifted_dp = jnp.where(here, pltpu.roll(dp, c, 1), shifted_dp)
            shifted_masks = [jnp.where(here, pltpu.roll(m, c, 1), sm)
                             for m, sm in zip(masks, shifted_masks)]
        cand = jnp.where(js >= c_rows, shifted_dp + p, NEG_INF)
        tk = cand > dp  # strict: ties keep "not taken" (Algorithm 1 backtrack)
        bit = jax.lax.shift_left(jnp.int32(1), i % 32)
        new_masks = tuple(
            jnp.where(tk, sm | jnp.where(i // 32 == w, bit, 0), m)
            for w, (m, sm) in enumerate(zip(masks, shifted_masks))
        )
        return jnp.maximum(dp, cand), new_masks

    dp0 = jnp.zeros((bq, width), jnp.float32)
    masks0 = tuple(jnp.zeros((bq, width), jnp.int32) for _ in range(n_words))
    dp, masks = jax.lax.fori_loop(0, n_items, item_step, (dp0, masks0))
    dp_ref[...] = dp
    for w in range(n_words):
        masks_ref[:, w * width:(w + 1) * width] = masks[w]


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def knapsack_dp_pallas(
    profits: jax.Array,  # [Q, N] float32
    costs: jax.Array,  # [Q, N] int32
    budget: int,
    block_q: int = 8,
    interpret: bool = True,
):
    """Bitmask DP: returns (dp_final [Q, B+1], sel_words [Q, W] uint32)."""
    q, n = profits.shape
    bp1 = budget + 1
    width = -(-bp1 // LANES) * LANES
    w = mask_words(n)
    pad = (-q) % block_q
    if pad:
        profits = jnp.pad(profits, ((0, pad), (0, 0)))
        costs = jnp.pad(costs, ((0, pad), (0, 0)), constant_values=1)
    qp = profits.shape[0]

    dp, masks = pl.pallas_call(
        functools.partial(_kernel, n_items=n, bp1=bp1, n_words=w, width=width),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(qp // block_q,),
            in_specs=[pl.BlockSpec((block_q, n), lambda g, c: (g, 0))],
            out_specs=[
                pl.BlockSpec((block_q, width), lambda g, c: (g, 0)),
                pl.BlockSpec((block_q, w * width), lambda g, c: (g, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((qp, width), jnp.float32),
            jax.ShapeDtypeStruct((qp, w * width), jnp.int32),
        ],
        interpret=interpret,
    )(costs.astype(jnp.int32).reshape(-1), profits.astype(jnp.float32))
    sel = masks.reshape(qp, w, width)[:, :, budget]
    return dp[:q, :bp1], jax.lax.bitcast_convert_type(sel[:q], jnp.uint32)
