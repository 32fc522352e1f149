"""MODI quality predictor (paper §2.3, Appendix A.2).

DeBERTa-style encoder (He et al. 2021): disentangled attention with
content-to-content, content-to-position and position-to-content terms over
relative-position embeddings.  Regression head per Figure 1: the CLS hidden
state -> Dropout(0.2) -> GELU -> Linear -> GLU -> Linear(N) giving one
predicted quality score per pool member from the query alone.

Trained with Huber loss (delta = 0.3) and Adam(3e-4, betas=(0.9, 0.98),
weight decay 0.01) per Table 2.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.layers import (
    apply_norm,
    declared_precision,
    dense_init,
    embed_init,
    huber_loss,
    init_embedding,
    init_mlp,
    apply_mlp,
    init_norm,
)

MAX_REL = 64  # relative-position bucket radius (2*MAX_REL embeddings)


@dataclasses.dataclass(frozen=True)
class PredictorConfig:
    encoder: ModelConfig
    num_models: int
    dropout: float = 0.2
    huber_delta: float = 0.3


class QualityPredictor:
    def __init__(self, cfg: PredictorConfig):
        self.cfg = cfg
        self.dtype = jnp.dtype(cfg.encoder.dtype)

    # ------------------------------------------------------------------
    def init(self, key: jax.Array) -> dict:
        ecfg, dtype = self.cfg.encoder, self.dtype
        d, h, hd = ecfg.d_model, ecfg.num_heads, ecfg.resolved_head_dim
        ks = jax.random.split(key, 10)

        def enc_block(k):
            k1, k2, k3, k4, k5 = jax.random.split(k, 5)
            return {
                "norm1": init_norm(d, dtype, ecfg.norm),
                "wq": dense_init(k1, d, (d, h, hd), dtype),
                "wk": dense_init(k2, d, (d, h, hd), dtype),
                "wv": dense_init(k3, d, (d, h, hd), dtype),
                "wo": dense_init(k4, h * hd, (h, hd, d), dtype),
                # disentangled position projections (shared rel-pos table below)
                "wq_r": dense_init(k1, d, (d, h, hd), dtype),
                "wk_r": dense_init(k2, d, (d, h, hd), dtype),
                "norm2": init_norm(d, dtype, ecfg.norm),
                "mlp": init_mlp(k5, d, ecfg.d_ff, dtype),
            }

        n = self.cfg.num_models
        return {
            "embed": init_embedding(ks[0], ecfg.vocab_size, d, dtype),
            "rel_embed": embed_init(ks[1], (2 * MAX_REL, d), dtype),
            "blocks": jax.vmap(enc_block)(jax.random.split(ks[2], ecfg.num_layers)),
            "final_norm": init_norm(d, dtype, ecfg.norm),
            "head": {
                "lin1": dense_init(ks[3], d, (d, d), dtype),
                "b1": jnp.zeros((d,), dtype),
                "glu_w": dense_init(ks[4], d, (d, d), dtype),
                "glu_b": jnp.zeros((d,), dtype),
                "glu_v": dense_init(ks[5], d, (d, d), dtype),
                "glu_c": jnp.zeros((d,), dtype),
                "out": dense_init(ks[6], d, (d, n), dtype),
                "out_b": jnp.zeros((n,), dtype),
            },
        }

    # ------------------------------------------------------------------
    def _disentangled_attention(self, p_l, rel_embed, x):
        """DeBERTa attention: c2c + c2p + p2c with relative positions."""
        ecfg = self.cfg.encoder
        b, s, d = x.shape
        q = jnp.einsum("bsd,dhk->bshk", x, p_l["wq"])
        k = jnp.einsum("bsd,dhk->bshk", x, p_l["wk"])
        v = jnp.einsum("bsd,dhk->bshk", x, p_l["wv"])
        # relative position deltas bucketized to [0, 2*MAX_REL)
        pos = jnp.arange(s)
        delta = jnp.clip(pos[:, None] - pos[None, :], -MAX_REL, MAX_REL - 1) + MAX_REL  # [S,S]
        kr = jnp.einsum("rd,dhk->rhk", rel_embed, p_l["wk_r"])  # [R,H,hd]
        qr = jnp.einsum("rd,dhk->rhk", rel_embed, p_l["wq_r"])
        f32 = jnp.float32
        c2c = jnp.einsum("bihk,bjhk->bhij", q.astype(f32), k.astype(f32))
        # c2p: q_c[i] . k_r[delta(i,j)]
        qkr = jnp.einsum("bihk,rhk->bhir", q.astype(f32), kr.astype(f32))  # [B,H,S,R]
        c2p = jnp.take_along_axis(qkr, delta[None, None, :, :], axis=-1)  # [B,H,S,S]
        # p2c: k_c[j] . q_r[delta(j,i)]
        kqr = jnp.einsum("bjhk,rhk->bhjr", k.astype(f32), qr.astype(f32))
        p2c = jnp.take_along_axis(kqr, delta.T[None, None, :, :], axis=-1)  # [B,H,S(j),S(i)]
        p2c = jnp.swapaxes(p2c, -1, -2)
        scale = 1.0 / jnp.sqrt(jnp.asarray(3 * q.shape[-1], f32))
        probs = jax.nn.softmax((c2c + c2p + p2c) * scale, axis=-1)
        out = jnp.einsum("bhij,bjhk->bihk", probs, v.astype(f32)).astype(x.dtype)
        return jnp.einsum("bshk,hkd->bsd", out, p_l["wo"])

    @declared_precision
    def encode(self, params: dict, tokens: jax.Array) -> jax.Array:
        """tokens: [B, S] -> hidden [B, S, D] (token 0 is CLS)."""
        ecfg = self.cfg.encoder
        x = jnp.take(params["embed"], tokens, axis=0).astype(self.dtype)
        rel = params["rel_embed"]

        def body(xc, p_l):
            h = apply_norm(p_l["norm1"], xc, ecfg.norm_eps)
            xc = xc + self._disentangled_attention(p_l, rel, h)
            h2 = apply_norm(p_l["norm2"], xc, ecfg.norm_eps)
            return xc + apply_mlp(p_l["mlp"], h2, ecfg.act), None

        x, _ = jax.lax.scan(body, x, params["blocks"])
        return apply_norm(params["final_norm"], x, ecfg.norm_eps)

    # ------------------------------------------------------------------
    @declared_precision
    def apply(
        self,
        params: dict,
        tokens: jax.Array,
        train: bool = False,
        rng: Optional[jax.Array] = None,
    ) -> jax.Array:
        """Predict r_hat(m_i, q) for every pool member: [B, num_models]."""
        h = self.encode(params, tokens)
        cls = h[:, 0, :]  # CLS pooling (A.2: best of the aggregations tried)
        hd = params["head"]
        x = cls
        if train:
            keep = 1.0 - self.cfg.dropout
            mask = jax.random.bernoulli(rng, keep, x.shape)
            x = jnp.where(mask, x / keep, 0.0)
        x = jax.nn.gelu(x)  # GELU (Eq. 6)
        x = x @ hd["lin1"] + hd["b1"]
        x = (x @ hd["glu_w"] + hd["glu_b"]) * jax.nn.sigmoid(x @ hd["glu_v"] + hd["glu_c"])  # Eq. 7
        return x @ hd["out"] + hd["out_b"]

    def loss(self, params, batch, rng=None) -> Tuple[jax.Array, dict]:
        """batch: {tokens [B,S], scores [B,N]} -> Huber(delta=0.3) (Eq. 8)."""
        train = rng is not None
        pred = self.apply(params, batch["tokens"], train=train, rng=rng)
        l = huber_loss(pred, batch["scores"], self.cfg.huber_delta)
        mae = jnp.mean(jnp.abs(pred - batch["scores"]))
        return l, {"loss": l, "mae": mae}


def build_predictor(num_models: int, encoder: Optional[ModelConfig] = None) -> QualityPredictor:
    if encoder is None:
        from repro import configs

        encoder = configs.get("modi-predictor")
    return QualityPredictor(PredictorConfig(encoder=encoder, num_models=num_models))
