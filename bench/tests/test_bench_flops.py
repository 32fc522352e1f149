"""Operation and byte counts against hand counts at small shapes."""

import pytest

from harness import flops
from harness.peaks import PEAKS, peaks_for

F = {"d_model": 4, "num_heads": 2, "num_kv_heads": 1, "head_dim": 2, "d_ff": 8,
     "enc_layers": 1, "dec_layers": 2, "vocab_size": 10}
P = {"d_model": 4, "num_heads": 2, "head_dim": 2, "d_ff": 8, "layers": 1}


def test_decode_step_bytes_by_hand():
    # per decoder layer: q,o 4*2*2*2=32; k,v 4*1*2*2=16; cross q,o 32;
    # mlp 3*4*8=96; norms 12 -> 188; two layers 376, head 40, final norm 4
    weights = 2 * 188 + 10 * 4 + 4
    # cross cache: layers 2 * rows 3 * enc 5 * heads 2 * hd 2 * (k, v) 2
    cache = 2 * 3 * 5 * 2 * 2 * 2
    assert flops.fuser_decode_step_bytes(F, rows=3, enc_len=5, dtype_bytes=4) == 4 * (weights + cache)


def test_decode_step_flops_by_hand():
    per_layer = 32 + 16 + 32 + 96  # projections and MLP, no norms
    matmul = 2 * 3 * (2 * per_layer + 10 * 4)
    attn = 4 * 3 * 2 * 2 * 2 * (7 + 1 + 5)  # self over 8 positions, cross over 5
    assert flops.fuser_decode_step_flops(F, rows=3, enc_len=5, pos=7) == matmul + attn


def test_request_flops_add_encoder_cross_and_decode():
    enc = 2 * 6 * 1 * (4 * 4 * 2 * 2 + 3 * 4 * 8) + 4 * 1 * 36 * 2 * 2
    cross = 2 * 6 * 2 * 2 * 4 * 2 * 2
    dec = sum(flops.fuser_decode_step_flops(F, 1, 6, p) for p in range(3))
    assert flops.fuser_request_flops(F, enc_tokens=6, new_tokens=3) == enc + cross + dec


def test_predictor_flops_by_hand():
    t, r = 5, 4
    per_layer = (2 * t * (4 * 4 * 2 * 2 + 3 * 4 * 8) + 2 * r * 2 * 4 * 2 * 2
                 + 2 * t * t * 2 * 2 * 2 + 2 * t * r * 2 * 2 * 2)
    head = 2 * (3 * 16 + 4 * 3)
    assert flops.predictor_request_flops(P, tokens=t, n_members=3, rel_positions=r) == per_layer + head


def test_roofline_names_its_bound():
    peaks = PEAKS["TPU v5 lite"]
    share, bound = flops.roofline_share(0.0, 819e9, 2.0, peaks)
    assert bound == "memory" and share == pytest.approx(50.0)
    share, bound = flops.roofline_share(197e12, 0.0, 1.0, peaks, passes=6)
    assert bound == "compute" and share == pytest.approx(600.0)


@pytest.mark.parametrize("platform, kind", [("cpu", "cpu"), ("tpu", "TPU v99")])
def test_peaks_refuse_what_they_do_not_know(platform, kind):
    with pytest.raises(RuntimeError):
        peaks_for(platform, kind)


def test_v5e_peaks_are_the_published_ones():
    p = peaks_for("tpu", "TPU v5 lite")
    assert (p["bf16_flops_per_s"], p["hbm_bytes_per_s"]) == (197e12, 819e9)
