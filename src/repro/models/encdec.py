"""Encoder-decoder transformer (Whisper-style audio backbone, GEN-FUSER).

The encoder consumes either precomputed frontend frame/patch embeddings
(audio — the conv/mel frontend is a stub per spec) or text tokens
(GEN-FUSER).  The decoder is a causal GQA stack with per-layer
cross-attention; cross K/V are computed once from the encoder output and
cached for decoding.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn_mod
from repro.models.config import ModelConfig
from repro.models.layers import (
    apply_mlp,
    apply_norm,
    chunked_ce_from_hidden,
    cross_entropy,
    declared_precision,
    dense_init,
    embed_init,
    embed_tokens,
    init_embedding,
    init_mlp,
    init_norm,
    lm_logits,
)
from repro.sharding import logical_constraint


class EncDecLM:
    def __init__(self, cfg: ModelConfig):
        assert cfg.is_encoder_decoder
        self.cfg = cfg
        self.dtype = jnp.dtype(cfg.dtype)

    # ------------------------------------------------------------------
    def init(self, key: jax.Array) -> dict:
        cfg, dtype = self.cfg, self.dtype
        ks = jax.random.split(key, 8)
        fdim = cfg.frontend_dim or cfg.d_model

        def enc_block(k):
            k1, k2 = jax.random.split(k)
            return {
                "norm1": init_norm(cfg.d_model, dtype, cfg.norm),
                "attn": attn_mod.init_cross_attention(k1, cfg, dtype),
                "norm2": init_norm(cfg.d_model, dtype, cfg.norm),
                "mlp": init_mlp(k2, cfg.d_model, cfg.d_ff, dtype),
            }

        def dec_block(k):
            k1, k2, k3 = jax.random.split(k, 3)
            return {
                "norm1": init_norm(cfg.d_model, dtype, cfg.norm),
                "self_attn": attn_mod.init_attention(k1, cfg, dtype),
                "norm_x": init_norm(cfg.d_model, dtype, cfg.norm),
                "cross": attn_mod.init_cross_attention(k2, cfg, dtype),
                "norm2": init_norm(cfg.d_model, dtype, cfg.norm),
                "mlp": init_mlp(k3, cfg.d_model, cfg.d_ff, dtype),
            }

        params = {
            "embed": init_embedding(ks[0], cfg.vocab_size, cfg.d_model, dtype),
            "enc_pos": embed_init(ks[1], (max(cfg.enc_seq, 1), cfg.d_model), dtype),
            "frontend_proj": dense_init(ks[2], fdim, (fdim, cfg.d_model), dtype),
            "enc_segs": jax.vmap(enc_block)(jax.random.split(ks[3], cfg.enc_layers)),
            "enc_norm": init_norm(cfg.d_model, dtype, cfg.norm),
            "dec_segs": jax.vmap(dec_block)(jax.random.split(ks[4], cfg.num_layers)),
            "final_norm": init_norm(cfg.d_model, dtype, cfg.norm),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = dense_init(ks[5], cfg.d_model, (cfg.d_model, cfg.vocab_size), dtype)
        return params

    # ------------------------------------------------------------------
    @declared_precision
    def encode(
        self,
        params: dict,
        enc_frontend: Optional[jax.Array] = None,
        enc_tokens: Optional[jax.Array] = None,
    ) -> jax.Array:
        cfg = self.cfg
        if enc_frontend is not None:
            x = enc_frontend.astype(self.dtype) @ params["frontend_proj"]
        else:
            x = embed_tokens(params["embed"], enc_tokens).astype(self.dtype)
        s = x.shape[1]
        x = x + params["enc_pos"][:s][None]
        x = logical_constraint(x, "batch", "seq", "embed")

        def body(xc, p_l):
            h = apply_norm(p_l["norm1"], xc, cfg.norm_eps)
            k, v = attn_mod.cross_kv(p_l["attn"], h)
            xc = xc + attn_mod.cross_attend(p_l["attn"], h, k, v)  # bidirectional self-attn
            h2 = apply_norm(p_l["norm2"], xc, cfg.norm_eps)
            return xc + apply_mlp(p_l["mlp"], h2, cfg.act), None

        x, _ = jax.lax.scan(body, x, params["enc_segs"])
        return apply_norm(params["enc_norm"], x, cfg.norm_eps)

    # ------------------------------------------------------------------
    def _dec_stack(self, params, x, positions, enc_out=None, cache=None, pos=None):
        """Shared decoder stack. Full-seq when positions given; decode when
        ``pos`` given (x is [B,1,D]). cache: {"self": stacked, "ck","cv"}."""
        cfg = self.cfg
        decode = pos is not None
        if cache is not None:
            ck, cv = cache["ck"], cache["cv"]
        else:
            ck = cv = None
        new_self = None
        if decode:
            def body(xc, inp):
                p_l, c_l, k_l, v_l = inp
                h = apply_norm(p_l["norm1"], xc, cfg.norm_eps)
                a, nc = attn_mod.attention_decode(p_l["self_attn"], h, pos, cfg, c_l)
                xc = xc + a
                hx = apply_norm(p_l["norm_x"], xc, cfg.norm_eps)
                xc = xc + attn_mod.cross_attend(p_l["cross"], hx, k_l, v_l)
                h2 = apply_norm(p_l["norm2"], xc, cfg.norm_eps)
                return xc + apply_mlp(p_l["mlp"], h2, cfg.act), nc
            x, new_self = jax.lax.scan(body, x, (params["dec_segs"], cache["self"], ck, cv))
        elif cache is not None:
            def body(xc, inp):
                p_l, c_l = inp
                h = apply_norm(p_l["norm1"], xc, cfg.norm_eps)
                a, nc = attn_mod.attention_forward(p_l["self_attn"], h, positions, cfg, c_l)
                xc = xc + a
                hx = apply_norm(p_l["norm_x"], xc, cfg.norm_eps)
                k_l, v_l = attn_mod.cross_kv(p_l["cross"], enc_out)
                xc = xc + attn_mod.cross_attend(p_l["cross"], hx, k_l, v_l)
                h2 = apply_norm(p_l["norm2"], xc, cfg.norm_eps)
                return xc + apply_mlp(p_l["mlp"], h2, cfg.act), (nc, k_l, v_l)
            x, (new_self, cks, cvs) = jax.lax.scan(body, x, (params["dec_segs"], cache["self"]))
            return x, {"self": new_self, "ck": cks, "cv": cvs}
        else:
            @jax.checkpoint
            def body(xc, p_l):
                h = apply_norm(p_l["norm1"], xc, cfg.norm_eps)
                a, _ = attn_mod.attention_forward(p_l["self_attn"], h, positions, cfg, None)
                xc = xc + a
                hx = apply_norm(p_l["norm_x"], xc, cfg.norm_eps)
                k_l, v_l = attn_mod.cross_kv(p_l["cross"], enc_out)
                xc = xc + attn_mod.cross_attend(p_l["cross"], hx, k_l, v_l)
                h2 = apply_norm(p_l["norm2"], xc, cfg.norm_eps)
                return xc + apply_mlp(p_l["mlp"], h2, cfg.act), None
            x, _ = jax.lax.scan(body, x, params["dec_segs"])
            return x, None
        return x, {"self": new_self, "ck": ck, "cv": cv}

    def _head(self, params, x):
        cfg = self.cfg
        x = apply_norm(params["final_norm"], x, cfg.norm_eps)
        if cfg.tie_embeddings:
            return lm_logits(params["embed"], x, transpose=True)
        return lm_logits(params["lm_head"], x, transpose=False)

    # ------------------------------------------------------------------
    @declared_precision
    def forward(self, params, dec_tokens, enc_frontend=None, enc_tokens=None):
        enc_out = self.encode(params, enc_frontend, enc_tokens)
        b, s = dec_tokens.shape
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
        x = embed_tokens(params["embed"], dec_tokens).astype(self.dtype)
        x, _ = self._dec_stack(params, x, positions, enc_out=enc_out)
        return self._head(params, x)

    @declared_precision
    def loss(self, params, batch, remat: bool = False):
        """Fused chunked head+CE — full [B, S, V] logits never materialize."""
        cfg = self.cfg
        dec_tokens = batch["dec_tokens"]
        enc_out = self.encode(
            params, batch.get("enc_frontend"), batch.get("enc_tokens")
        )
        b, s = dec_tokens.shape
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
        x = embed_tokens(params["embed"], dec_tokens).astype(self.dtype)
        x, _ = self._dec_stack(params, x, positions, enc_out=enc_out)
        h = apply_norm(params["final_norm"], x, cfg.norm_eps)
        head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
        mask = batch.get("loss_mask")
        mask = mask[:, 1:] if mask is not None else None
        loss = chunked_ce_from_hidden(
            head, h[:, :-1], dec_tokens[:, 1:], mask, cfg.tie_embeddings
        )
        return loss, {"ce": loss, "loss": loss}

    # ------------------------------------------------------------------
    # Serving
    # ------------------------------------------------------------------
    def init_cache(self, batch: int, max_seq: int, enc_seq: Optional[int] = None) -> dict:
        """``enc_seq`` overrides the config's encoder length so callers that
        serve a fixed (bucketed) encoder shape get cross-K/V buffers whose
        shape round-trips through ``prefill`` — a prerequisite for buffer
        donation in the static-shape fast path (serve.dispatch)."""
        cfg, dtype = self.cfg, self.dtype
        one = attn_mod.init_cache(cfg, batch, max_seq, dtype)
        l, h, hd = cfg.num_layers, cfg.num_heads, cfg.resolved_head_dim
        se = cfg.enc_seq if enc_seq is None else enc_seq
        return {
            "self": jax.tree.map(lambda t: jnp.broadcast_to(t[None], (l,) + t.shape), one),
            "ck": jnp.zeros((l, batch, se, h, hd), dtype),
            "cv": jnp.zeros((l, batch, se, h, hd), dtype),
        }

    @declared_precision
    def prefill(self, params, dec_tokens, cache, enc_frontend=None, enc_tokens=None):
        enc_out = self.encode(params, enc_frontend, enc_tokens)
        b, s = dec_tokens.shape
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
        x = embed_tokens(params["embed"], dec_tokens).astype(self.dtype)
        x, new_cache = self._dec_stack(params, x, positions, enc_out=enc_out, cache=cache)
        return self._head(params, x)[:, -1:], new_cache

    @declared_precision
    def decode_step(self, params, token, pos, cache):
        x = embed_tokens(params["embed"], token).astype(self.dtype)
        x, new_cache = self._dec_stack(params, x, None, cache=cache, pos=pos)
        return self._head(params, x), new_cache
