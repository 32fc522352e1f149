"""The control, the plain reference at the next lower precision ("high",
three bf16 passes) in the program's place, comes out not correct; sound
runs of the program, on the same requests, stay inside every limit."""

import pytest
from bench_tiny import run_tiny

from harness import spec

LIMITS = spec.config_module("modi-t5xl-sim").LIMITS


@pytest.mark.parametrize("seed", [5, 2**31 + 11, 3 * 2**32 + 1])
def test_control_fails_where_the_program_passes(seed):
    out = run_tiny(seed=seed, seconds=1.5, control=True)
    assert out["correct"], out["checks"]
    assert out["control_correct"] is False, out["control"]
    failed = [k for k, v in out["control"].items() if not v <= LIMITS[k]]
    assert failed, out["control"]
