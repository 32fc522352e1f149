"""Compile the main path's device programs for a described TPU v5e.

Nothing runs: the TPU compiler installed with JAX compiles for a v5e that
is described, not attached, and refuses what the chip would refuse (an
unaligned slice, a gather Mosaic cannot lower, a program that does not
fit).  The topology is described inside a fixture, never at import: only
one process at a time may load the TPU library, and under several test
workers every worker imports this file.
"""

from __future__ import annotations

import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.kernels.knapsack.knapsack import knapsack_dp_pallas
from repro.models import build_model
from repro.serve.dispatch import StreamingEncDecBatcher


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _sds(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding), tree)


@pytest.mark.parametrize("budget", [64, 256])
@pytest.mark.parametrize("q", [8, 32])
def test_knapsack_kernel_compiles_for_v5e(one_chip, q, budget):
    profits = jax.ShapeDtypeStruct((q, 8), jnp.float32, sharding=one_chip)
    costs = jax.ShapeDtypeStruct((q, 8), jnp.int32, sharding=one_chip)
    compiled = knapsack_dp_pallas.lower(profits, costs, budget, 8, False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fuser_stream_step_compiles_at_published_widths(one_chip):
    """The streaming batcher's capacity-shaped decode step, at Flan-T5-XL
    widths with 1 + 1 layers (depth does not change the per-layer
    program; the layers are a scan)."""
    cfg = dataclasses.replace(
        configs.get("gen-fuser"), d_model=2048, num_heads=32, num_kv_heads=32,
        head_dim=64, d_ff=5120, num_layers=1, enc_layers=1)
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.key(0))
    batcher = StreamingEncDecBatcher(model, params, enc_seq=512, capacity=8,
                                     donate=True)
    args = (params, batcher._tok, batcher._pos, batcher._done, batcher._cache)
    compiled = batcher._step().lower(*_sds(args, one_chip)).compile()
    out = jax.eval_shape(batcher._step(), *args)
    assert out[0].shape == (8,)
    assert compiled.memory_analysis() is not None
