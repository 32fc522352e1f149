"""Plain references for what a served request goes through.

Written from the architecture's equations in ``jax.numpy``, float32, with no
cache, batching trick or kernel, and importing nothing of the program:

* the quality predictor (DeBERTa-style disentangled attention encoder with
  the paper's GLU regression head);
* the GEN-FUSER encoder-decoder, teacher-forced over a prompt and the
  tokens that were served;
* the ε-constrained 0/1 knapsack, as the paper's Algorithm 1 in numpy.

Every matrix product goes through :class:`Matmul`.  ``Matmul("highest")``
is the reference.  ``Matmul("high")`` is the control, the next precision
below: on a TPU the MXU's three-pass float32 product
(``Precision.HIGH``); on a CPU, where every float32 product is exact, each
operand split into two bfloat16 parts and the three leading products
summed, which is what the three passes compute.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


class Matmul:
    """``einsum`` at a stated precision: ``"highest"`` or ``"high"``."""

    def __init__(self, mode: str):
        if mode not in ("highest", "high"):
            raise ValueError(f"unknown precision {mode!r}")
        self.mode = mode

    def __call__(self, spec: str, a, b):
        a = jnp.asarray(a, jnp.float32)
        b = jnp.asarray(b, jnp.float32)
        if self.mode == "highest":
            return jnp.einsum(spec, a, b, precision=HIGHEST)
        if jax.default_backend() == "tpu":
            return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGH)
        a_hi = a.astype(jnp.bfloat16).astype(jnp.float32)
        b_hi = b.astype(jnp.bfloat16).astype(jnp.float32)
        a_lo = (a - a_hi).astype(jnp.bfloat16).astype(jnp.float32)
        b_lo = (b - b_hi).astype(jnp.bfloat16).astype(jnp.float32)
        e = functools.partial(jnp.einsum, spec, precision=HIGHEST)
        return e(a_lo, b_hi) + e(a_hi, b_lo) + e(a_hi, b_hi)

    # a hashable identity, so jitted references specialise per precision
    def __hash__(self):
        return hash(self.mode)

    def __eq__(self, other):
        return isinstance(other, Matmul) and other.mode == self.mode


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def gelu(x):
    """tanh-approximated GELU (Flan-T5's ``gelu_new``, DeBERTa's head)."""
    return 0.5 * x * (1.0 + jnp.tanh(np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))


def gated_mlp(mm, p, x):
    return mm("...d,df->...f", gelu(mm("...d,df->...f", x, p["wg"])) * mm("...d,df->...f", x, p["wi"]),
              p["wo"])


def attention(mm, q, k, v, scale, mask=None):
    """q [B,Sq,H,hd], k/v [B,Sk,H,hd] -> [B,Sq,H,hd]; softmax in float32."""
    s = mm("bqhk,bshk->bhqs", q, k) * scale
    if mask is not None:
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return mm("bhqs,bshk->bqhk", p, v)


def rope(x, positions, theta):
    """Rotate-half rotary embedding; x [B,S,H,hd], positions [S]."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions.astype(jnp.float32)[:, None] * freqs  # [S, hd/2]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


# ---------------------------------------------------------------------------
# quality predictor
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("mm", "eps", "max_rel"))
def predictor_scores(params, tokens, *, mm: Matmul, eps: float, max_rel: int):
    """tokens [B, S] (CLS first) -> predicted quality [B, N]."""
    x = params["embed"][tokens].astype(jnp.float32)
    rel = params["rel_embed"]
    s = tokens.shape[1]
    pos = jnp.arange(s)
    delta = jnp.clip(pos[:, None] - pos[None, :], -max_rel, max_rel - 1) + max_rel

    def layer(x, p):
        h = layer_norm(x, p["norm1"]["scale"], p["norm1"]["bias"], eps)
        q = mm("bsd,dhk->bshk", h, p["wq"])
        k = mm("bsd,dhk->bshk", h, p["wk"])
        v = mm("bsd,dhk->bshk", h, p["wv"])
        kr = mm("rd,dhk->rhk", rel, p["wk_r"])
        qr = mm("rd,dhk->rhk", rel, p["wq_r"])
        c2c = mm("bihk,bjhk->bhij", q, k)
        # content of i against the relative position (i - j), and the
        # content of j against the same relative position
        c2p = jnp.take_along_axis(mm("bihk,rhk->bhir", q, kr), delta[None, None], axis=-1)
        kqr = mm("bjhk,rhk->bhjr", k, qr)
        p2c = jnp.swapaxes(jnp.take_along_axis(kqr, delta.T[None, None], axis=-1), -1, -2)
        probs = jax.nn.softmax((c2c + c2p + p2c) / np.sqrt(3.0 * q.shape[-1]), axis=-1)
        x = x + mm("bshk,hkd->bsd", mm("bhij,bjhk->bihk", probs, v), p["wo"])
        h2 = layer_norm(x, p["norm2"]["scale"], p["norm2"]["bias"], eps)
        return x + gated_mlp(mm, p["mlp"], h2), None

    x, _ = jax.lax.scan(layer, x, params["blocks"])
    x = layer_norm(x, params["final_norm"]["scale"], params["final_norm"]["bias"], eps)
    hd = params["head"]
    z = gelu(x[:, 0])
    z = mm("bd,de->be", z, hd["lin1"]) + hd["b1"]
    z = (mm("bd,de->be", z, hd["glu_w"]) + hd["glu_b"]) * jax.nn.sigmoid(
        mm("bd,de->be", z, hd["glu_v"]) + hd["glu_c"])
    return mm("bd,dn->bn", z, hd["out"]) + hd["out_b"]


# ---------------------------------------------------------------------------
# GEN-FUSER
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("mm", "eps", "theta"))
def fuser_logits(params, enc_tokens, dec_tokens, *, mm: Matmul, eps: float, theta: float):
    """Teacher-forced logits [B, T, V]: the encoder reads ``enc_tokens``
    [B, Se] with full bidirectional attention (padding included, as
    served), the decoder reads ``dec_tokens`` [B, T] (BOS, then the served
    tokens) causally, with rotary positions, and cross-attends to every
    encoder position; the output head is ``lm_head`` where the weights
    have one, else the tied embedding."""
    embed = params["embed"]
    se, t = enc_tokens.shape[1], dec_tokens.shape[1]
    x = embed[enc_tokens] + params["enc_pos"][:se][None]

    def enc_layer(x, p):
        h = rms_norm(x, p["norm1"]["scale"], eps)
        a = p["attn"]
        q = mm("bsd,dhk->bshk", h, a["wq"])
        k = mm("bsd,dhk->bshk", h, a["wk"])
        v = mm("bsd,dhk->bshk", h, a["wv"])
        o = attention(mm, q, k, v, 1.0 / np.sqrt(q.shape[-1]))
        x = x + mm("bshk,hkd->bsd", o, a["wo"])
        return x + gated_mlp(mm, p["mlp"], rms_norm(x, p["norm2"]["scale"], eps)), None

    x, _ = jax.lax.scan(enc_layer, x, params["enc_segs"])
    enc = rms_norm(x, params["enc_norm"]["scale"], eps)

    pos = jnp.arange(t)
    causal = (pos[None, :] <= pos[:, None])[None, None]
    y = embed[dec_tokens]

    def dec_layer(y, p):
        h = rms_norm(y, p["norm1"]["scale"], eps)
        a = p["self_attn"]
        q = rope(mm("bsd,dhk->bshk", h, a["wq"]), pos, theta)
        k = rope(mm("bsd,dhk->bshk", h, a["wk"]), pos, theta)
        v = mm("bsd,dhk->bshk", h, a["wv"])
        o = attention(mm, q, k, v, 1.0 / np.sqrt(q.shape[-1]), causal)
        y = y + mm("bshk,hkd->bsd", o, a["wo"])
        hx = rms_norm(y, p["norm_x"]["scale"], eps)
        c = p["cross"]
        q = mm("bsd,dhk->bshk", hx, c["wq"])
        k = mm("bsd,dhk->bshk", enc, c["wk"])
        v = mm("bsd,dhk->bshk", enc, c["wv"])
        o = attention(mm, q, k, v, 1.0 / np.sqrt(q.shape[-1]))
        y = y + mm("bshk,hkd->bsd", o, c["wo"])
        return y + gated_mlp(mm, p["mlp"], rms_norm(y, p["norm2"]["scale"], eps)), None

    y, _ = jax.lax.scan(dec_layer, y, params["dec_segs"])
    y = rms_norm(y, params["final_norm"]["scale"], eps)
    if "lm_head" in params:
        return mm("btd,dv->btv", y, params["lm_head"])
    return mm("btd,vd->btv", y, embed)


def served_token_gaps(logits: np.ndarray, tokens: np.ndarray, lengths: np.ndarray,
                      control_logits: Optional[np.ndarray] = None) -> np.ndarray:
    """Per served position, how far below the reference's best logit the
    served token's logit lies (0 where it is the reference's greedy pick).
    With ``control_logits``, the token read is the one the control puts
    first at that position instead of the served one."""
    if control_logits is not None:
        tokens = np.argmax(control_logits, axis=-1)
    best = logits.max(axis=-1)
    picked = np.take_along_axis(logits, tokens[..., None], axis=-1)[..., 0]
    gaps = best - picked
    valid = np.arange(tokens.shape[1])[None, :] < lengths[:, None]
    return gaps[valid]


# ---------------------------------------------------------------------------
# ε-constrained knapsack (paper Algorithm 1)
# ---------------------------------------------------------------------------


def knapsack_masks(quality: np.ndarray, costs32: np.ndarray, eps: float,
                   buckets: int) -> np.ndarray:
    """[Q, N] selections for one dispatched batch.

    Scores are shifted by α = 1.01·max|score| + 1e-6 over the batch
    (Eq. 4-5), costs are ceil-bucketed against ε of each row's full cost
    in float64, the DP runs in float32 with ties keeping "not taken", and
    an empty selection falls back to the cheapest member."""
    s = np.asarray(quality, np.float32)
    alpha = float(np.max(np.abs(s))) * 1.01 + 1e-6
    profits = (s + np.float32(alpha)).astype(np.float32)
    c64 = np.asarray(costs32, np.float32).astype(np.float64)
    scale = eps * c64.sum(axis=1, keepdims=True) / buckets
    scale = np.where(scale > 0, scale, 1.0)
    weights = np.minimum(np.maximum(np.ceil(c64 / scale), 1), buckets + 1).astype(np.int64)
    q, n = s.shape
    out = np.zeros((q, n), bool)
    for r in range(q):
        dp = np.zeros((n + 1, buckets + 1), np.float32)
        for i in range(1, n + 1):
            w, p = weights[r, i - 1], profits[r, i - 1]
            dp[i] = dp[i - 1]
            if w <= buckets:
                cand = dp[i - 1, :buckets + 1 - w] + p
                dp[i, w:] = np.maximum(dp[i - 1, w:], cand)
        j = buckets
        for i in range(n, 0, -1):
            if dp[i, j] != dp[i - 1, j]:
                out[r, i - 1] = True
                j -= weights[r, i - 1]
        if not out[r].any():
            out[r, int(np.argmin(np.asarray(costs32[r], np.float32)))] = True
    return out
