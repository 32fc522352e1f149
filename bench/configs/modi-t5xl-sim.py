"""modi-t5xl-sim: GEN-FUSER at Flan-T5-XL widths and the quality predictor
at DeBERTa-v3-large widths on one chip, float32 at "highest" precision,
with the eight pool members answered by the behavioural simulator.

``build`` makes the stack and holds it to the precision the configuration
states; ``check`` compares what it served with the plain references
(``harness.reference``, ``harness.yardstick``) through
``harness.ensemble.check``, and with ``control`` puts the reference at the
next precision below ("high", three bf16 passes) in the program's place.
"""

import jax

from harness import ensemble, reference

# Limits of the numbers compared; PERF.md ("Correctness limits") gives the
# readings on the chip each was set from.  The exact comparisons have
# limit 0.
LIMITS = {
    "member_mismatch": 0.0,
    "mask_mismatch": 0.0,
    "eps_violations": 0.0,
    "score_err": 1e-5,
    "fuse_gap": 1e-3,
}
REFERENCE = reference.Matmul("highest")
CONTROL = reference.Matmul("high")


def build(cfg: dict, seed: int):
    if cfg["dtype"] != "float32" or cfg["matmul_precision"] != "highest":
        raise ValueError("this configuration states float32 at highest precision")
    if jax.config.jax_default_matmul_precision is not None:
        raise RuntimeError("a default matmul precision is set; the models would not "
                           "run at the precision the configuration states")
    return ensemble.build(cfg, seed)


def check(stack, served, batches, sample, control: bool = False):
    return ensemble.check(stack, served, batches, sample, LIMITS, REFERENCE,
                          CONTROL if control else None)
