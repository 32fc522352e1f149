"""Operations and bytes the served work needs, from its shapes alone.

Counts are of multiply-adds as 2 operations, over the tokens that carry
content (padding left out), and of the bytes a step must at least read.
"""

from __future__ import annotations


def _attn_proj(d: int, h: int, kv: int, hd: int) -> int:
    return d * h * hd * 2 + d * kv * hd * 2  # q, o and k, v


def fuser_decode_step_bytes(f: dict, rows: int, enc_len: int, dtype_bytes: int) -> int:
    """Least bytes one decode step over ``rows`` slots must read: every
    decoder weight it multiplies by (self-attention, cross-attention query
    and output, MLP, norms, the output head) and the cached
    cross-attention keys and values of every slot.  The self-attention
    cache is left out, so this is a lower bound."""
    d, h, kv, hd, ff = f["d_model"], f["num_heads"], f["num_kv_heads"], f["head_dim"], f["d_ff"]
    per_layer = _attn_proj(d, h, kv, hd) + 2 * d * h * hd + 3 * d * ff + 3 * d
    weights = f["dec_layers"] * per_layer + f["vocab_size"] * d + d
    cross_cache = f["dec_layers"] * rows * enc_len * h * hd * 2
    return (weights + cross_cache) * dtype_bytes


def fuser_decode_step_flops(f: dict, rows: int, enc_len: int, pos: int) -> int:
    """Operations of one decode step over ``rows`` slots at position ``pos``."""
    d, h, kv, hd, ff = f["d_model"], f["num_heads"], f["num_kv_heads"], f["head_dim"], f["d_ff"]
    per_layer = _attn_proj(d, h, kv, hd) + 2 * d * h * hd + 3 * d * ff
    matmul = 2 * rows * (f["dec_layers"] * per_layer + f["vocab_size"] * d)
    attn = 4 * rows * f["dec_layers"] * h * hd * (pos + 1 + enc_len)
    return matmul + attn


def fuser_request_flops(f: dict, enc_tokens: int, new_tokens: int) -> int:
    """Operations to fuse one request: the encoder over its ``enc_tokens``
    prompt tokens, the cross-attention keys and values, and ``new_tokens``
    decode positions."""
    d, h, hd, ff = f["d_model"], f["num_heads"], f["head_dim"], f["d_ff"]
    enc = 2 * enc_tokens * f["enc_layers"] * (4 * d * h * hd + 3 * d * ff)
    enc += 4 * f["enc_layers"] * enc_tokens * enc_tokens * h * hd
    cross_kv = 2 * enc_tokens * f["dec_layers"] * 2 * d * h * hd
    dec = sum(fuser_decode_step_flops(f, 1, enc_tokens, p) for p in range(new_tokens))
    return enc + cross_kv + dec


def predictor_request_flops(p: dict, tokens: int, n_members: int, rel_positions: int) -> int:
    """Operations to score one query of ``tokens`` tokens (CLS included):
    projections, the three disentangled attention terms, the MLP, the
    relative-position projections and the regression head."""
    d, h, hd, ff = p["d_model"], p["num_heads"], p["head_dim"], p["d_ff"]
    per_layer = 2 * tokens * (4 * d * h * hd + 3 * d * ff)
    per_layer += 2 * rel_positions * 2 * d * h * hd  # relative keys and queries
    per_layer += 2 * tokens * tokens * h * hd * 2  # content-content, values
    per_layer += 2 * tokens * rel_positions * h * hd * 2  # content-position both ways
    head = 2 * (3 * d * d + d * n_members)
    return p["layers"] * per_layer + head


def roofline_share(flops: float, bytes_: float, seconds: float, peaks: dict,
                   passes: int = 1) -> tuple:
    """(share of the least time in %, "memory" or "compute") for work that
    took ``seconds``; ``passes`` MXU passes per operation."""
    t_flops = flops * passes / peaks["bf16_flops_per_s"]
    t_bytes = bytes_ / peaks["hbm_bytes_per_s"]
    least = max(t_flops, t_bytes)
    return 100.0 * least / seconds, ("memory" if t_bytes >= t_flops else "compute")
