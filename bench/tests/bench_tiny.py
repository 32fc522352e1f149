"""The cell's own path at a size a CPU test holds."""

import time

CELL = "t5xl-sim.eps0.2.sat"
# the cell's own path, cut to a size a CPU test holds
TINY = dict(fuser_d_model=64, fuser_num_heads=4, fuser_num_kv_heads=4, fuser_head_dim=16,
            fuser_d_ff=128, fuser_enc_layers=2, fuser_dec_layers=2, max_fusion_len=128,
            predictor_d_model=64, predictor_num_heads=4, predictor_head_dim=16,
            predictor_d_ff=128, predictor_layers=2, fuser_vocab_size=1000,
            predictor_vocab_size=600)


def run_tiny(seed=7, seconds=2.0, trace=False, fault=None, control=False, override=None):
    """One run of the cell on the CPU, the harness's look for a chip skipped."""
    from harness import cell

    return cell.run(CELL, seed, seconds, trace, time.time(), require_chip=False,
                    cfg_override={**TINY, **(override or {})}, fault=fault,
                    control=control)
