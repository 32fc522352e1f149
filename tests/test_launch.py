"""Launch-layer tests: plans build and lower on a 1x1(x1) host mesh.

(The real 256/512-device dry-run is exercised by repro.launch.dryrun; these
tests validate the plan machinery inside pytest without forcing devices.)
"""

import dataclasses

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from repro import configs
from repro.launch.shapes import (
    INPUT_SHAPES,
    InputShape,
    adapt_config,
    microbatches_for,
    shape_skip_reason,
)
from repro.launch.steps import build_plan
from repro.sharding.api import axis_rules, default_axis_rules

TINY_TRAIN = InputShape("train_tiny", 64, 8, "train")
TINY_PREFILL = InputShape("prefill_tiny", 64, 4, "prefill")
TINY_DECODE = InputShape("decode_tiny", 64, 4, "decode")


@pytest.fixture(scope="module")
def rules():
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    return default_axis_rules(mesh)


@pytest.mark.parametrize("arch", ["smollm-360m", "mamba2-370m", "whisper-base", "deepseek-v3-671b"])
@pytest.mark.parametrize("shape", [TINY_TRAIN, TINY_PREFILL, TINY_DECODE])
def test_plan_lowers_reduced(arch, shape, rules):
    cfg = configs.get(arch).reduced(dtype="float32")
    with axis_rules(rules):
        plan = build_plan(arch, cfg, shape, rules)
        lowered = jax.jit(plan.step_fn).lower(*plan.args_sds)
        assert lowered is not None
        compiled = lowered.compile()
        assert compiled.cost_analysis() is not None


def test_shape_table_matches_spec():
    s = INPUT_SHAPES
    assert (s["train_4k"].seq_len, s["train_4k"].global_batch) == (4096, 256)
    assert (s["prefill_32k"].seq_len, s["prefill_32k"].global_batch) == (32768, 32)
    assert (s["decode_32k"].seq_len, s["decode_32k"].global_batch) == (32768, 128)
    assert (s["long_500k"].seq_len, s["long_500k"].global_batch) == (524288, 1)


def test_long_context_adaptation():
    dense = configs.get("qwen2.5-32b")
    adapted = adapt_config(dense, INPUT_SHAPES["long_500k"])
    assert adapted.sliding_window == 8192
    ssm = configs.get("mamba2-370m")
    assert adapt_config(ssm, INPUT_SHAPES["long_500k"]).sliding_window == 0
    assert shape_skip_reason(configs.get("whisper-base"), INPUT_SHAPES["long_500k"])
    assert shape_skip_reason(dense, INPUT_SHAPES["long_500k"]) is None


def test_microbatches_respect_data_shards():
    assert microbatches_for("deepseek-v3-671b", 16, 256) == 16
    assert microbatches_for("deepseek-v3-671b", 32, 256) == 8
    assert microbatches_for("smollm-360m", 1, 8) == 4


def test_collective_parser():
    from repro.launch.dryrun import parse_collective_bytes

    hlo = """
      %ag = bf16[16,128]{1,0} all-gather(bf16[1,128] %x), dims={0}
      %ar = (f32[4,4]{1,0}, f32[2]{0}) all-reduce(f32[4,4] %a, f32[2] %b)
      %nothing = f32[8] add(f32[8] %p, f32[8] %q)
    """
    by, counts = parse_collective_bytes(hlo)
    assert by["all-gather"] == 16 * 128 * 2
    assert by["all-reduce"] == 4 * 4 * 4 + 2 * 4
    assert counts["all-gather"] == 1 and counts["all-reduce"] == 1


def test_compile_cache_follows_env_var(monkeypatch, tmp_path):
    from repro.launch import compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    prev = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_default_is_fixed_checkout_path(monkeypatch):
    import pathlib

    from repro.launch import compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.compile_cache_dir()
    assert first == compile_cache.compile_cache_dir()
    root = pathlib.Path(__file__).resolve().parents[1]
    assert pathlib.Path(first) == root / ".jax_cache"
