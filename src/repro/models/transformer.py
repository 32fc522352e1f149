"""Decoder-only language model: composable segment stack over all families.

A model is a sequence of *segments*, each a run of identical blocks executed
with ``jax.lax.scan`` over stacked parameters (small HLO at any depth).
Heterogeneous stacks (DeepSeek dense prefix + MoE body, Zamba2 mamba runs
with a weight-tied shared attention block) are expressed as multiple
segments.  The Zamba2 shared block's parameters live once at the top level
and are re-applied at every marker — caches are per-invocation.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn_mod
from repro.models import blocks, ssm as ssm_mod
from repro.models.config import ModelConfig, validate_config
from repro.models.layers import (
    apply_norm,
    chunked_ce_from_hidden,
    cross_entropy,
    declared_precision,
    dense_init,
    embed_tokens,
    init_embedding,
    init_norm,
    lm_logits,
)
from repro.sharding import logical_constraint


def model_segments(cfg: ModelConfig) -> List[Tuple[str, int]]:
    """(kind, count) plan for the decoder stack."""
    if cfg.family == "ssm":
        return [("mamba", cfg.num_layers)]
    if cfg.family == "hybrid":
        segs: List[Tuple[str, int]] = []
        remaining = cfg.num_layers
        period = cfg.attn_every or cfg.num_layers
        while remaining > 0:
            run = min(period, remaining)
            segs.append(("mamba", run))
            remaining -= run
            if remaining >= 0 and run == period:
                segs.append(("shared_attn", 1))
        return segs
    if cfg.num_experts:
        segs = []
        if cfg.first_dense_layers:
            segs.append(("dense", cfg.first_dense_layers))
        segs.append(("moe", cfg.num_layers - cfg.first_dense_layers))
        return segs
    return [("dense", cfg.num_layers)]


class DecoderLM:
    """Stateless functional model bound to a config."""

    def __init__(self, cfg: ModelConfig):
        validate_config(cfg)
        self.cfg = cfg
        self.segments = model_segments(cfg)
        self.dtype = jnp.dtype(cfg.dtype)

    # ------------------------------------------------------------------
    # Params
    # ------------------------------------------------------------------
    def init(self, key: jax.Array) -> dict:
        cfg, dtype = self.cfg, self.dtype
        keys = jax.random.split(key, len(self.segments) + 5)
        params: dict = {"embed": init_embedding(keys[0], cfg.vocab_size, cfg.d_model, dtype)}
        segs = {}
        for si, (kind, count) in enumerate(self.segments):
            if kind == "shared_attn":
                if "shared_attn" not in params:
                    params["shared_attn"] = blocks.init_shared_attn(keys[1], cfg, dtype)
                continue
            layer_keys = jax.random.split(keys[si + 2], count)
            segs[str(si)] = jax.vmap(lambda k: blocks.init_block(k, kind, cfg, dtype))(layer_keys)
        params["segs"] = segs
        params["final_norm"] = init_norm(cfg.d_model, dtype, cfg.norm)
        if not cfg.tie_embeddings:
            params["lm_head"] = dense_init(keys[-1], cfg.d_model, (cfg.d_model, cfg.vocab_size), dtype)
        if cfg.frontend_tokens:
            fdim = cfg.frontend_dim or cfg.d_model
            params["frontend_proj"] = dense_init(keys[-2], fdim, (fdim, cfg.d_model), dtype)
        if cfg.mtp:
            params["mtp"] = {
                "norm_h": init_norm(cfg.d_model, dtype, cfg.norm),
                "norm_e": init_norm(cfg.d_model, dtype, cfg.norm),
                "proj": dense_init(keys[-3], 2 * cfg.d_model, (2 * cfg.d_model, cfg.d_model), dtype),
                "block": blocks.init_block(keys[-4], "dense", cfg, dtype),
            }
        return params

    # ------------------------------------------------------------------
    # Caches
    # ------------------------------------------------------------------
    def cache_slots(self, max_seq: int) -> int:
        if self.cfg.sliding_window:
            return min(self.cfg.sliding_window, max_seq)
        return max_seq

    def init_cache(self, batch: int, max_seq: int) -> dict:
        cfg, dtype = self.cfg, self.dtype
        slots = self.cache_slots(max_seq)
        caches = {}
        for si, (kind, count) in enumerate(self.segments):
            if kind == "mamba":
                one = ssm_mod.init_ssm_cache(cfg, batch, dtype)
                caches[str(si)] = jax.tree.map(
                    lambda t: jnp.broadcast_to(t[None], (count,) + t.shape), one
                )
            elif kind == "shared_attn":
                caches[str(si)] = attn_mod.init_cache(cfg, batch, slots, dtype)
            else:
                one = attn_mod.init_cache(cfg, batch, slots, dtype)
                caches[str(si)] = jax.tree.map(
                    lambda t: jnp.broadcast_to(t[None], (count,) + t.shape), one
                )
        return caches

    # ------------------------------------------------------------------
    # Embedding / head
    # ------------------------------------------------------------------
    def _embed_inputs(self, params, tokens, frontend):
        cfg = self.cfg
        x = embed_tokens(params["embed"], tokens)
        if cfg.frontend_tokens:
            if frontend is None:
                raise ValueError(f"{cfg.name} requires frontend embeddings")
            fe = frontend.astype(x.dtype) @ params["frontend_proj"]
            x = jnp.concatenate([fe, x], axis=1)
        return x.astype(self.dtype)

    def _head(self, params, x):
        cfg = self.cfg
        x = apply_norm(params["final_norm"], x, cfg.norm_eps)
        if cfg.tie_embeddings:
            return lm_logits(params["embed"], x, transpose=True)
        return lm_logits(params["lm_head"], x, transpose=False)

    # ------------------------------------------------------------------
    # Full-sequence forward (training / prefill)
    # ------------------------------------------------------------------
    @declared_precision
    def forward(
        self,
        params: dict,
        tokens: jax.Array,
        frontend: Optional[jax.Array] = None,
        cache: Optional[dict] = None,
        remat: bool = False,
        positions: Optional[jax.Array] = None,
        skip_head: bool = False,
    ):
        """Returns (logits, new_cache, aux_loss, hidden).

        ``positions``: optional [B, S] absolute positions; -1 marks padding
        (masked out of attention and dropped from the KV cache)."""
        cfg = self.cfg
        x = self._embed_inputs(params, tokens, frontend)
        b, s, _ = x.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
        x = logical_constraint(x, "batch", "seq", "embed")
        aux_total = jnp.zeros((), jnp.float32)
        new_caches: dict = {}
        for si, (kind, count) in enumerate(self.segments):
            if kind == "shared_attn":
                c = cache[str(si)] if cache is not None else None
                x, nc = blocks.shared_attn_forward(params["shared_attn"], x, positions, cfg, c)
                if cache is not None:
                    new_caches[str(si)] = nc
                continue
            seg_p = params["segs"][str(si)]

            if cache is not None:
                def body(xc, inp, _kind=kind):
                    p_l, c_l = inp
                    y, nc, aux = blocks.block_forward(p_l, _kind, xc, positions, cfg, c_l)
                    return y, (nc, aux)
                fn = jax.checkpoint(body) if remat else body
                x, (ncs, auxs) = jax.lax.scan(fn, x, (seg_p, cache[str(si)]))
                new_caches[str(si)] = ncs
            else:
                def body(xc, p_l, _kind=kind):
                    y, _, aux = blocks.block_forward(p_l, _kind, xc, positions, cfg, None)
                    # sequence-parallel residual (no-op unless act_seq rule
                    # is mapped): the scan carry — which remat saves per
                    # layer — rests seq-sharded over the model axis.
                    y = logical_constraint(y, "batch", "act_seq", "embed")
                    return y, aux
                fn = jax.checkpoint(body) if remat else body
                x, auxs = jax.lax.scan(fn, x, seg_p)
            aux_total = aux_total + jnp.sum(auxs)
        logits = None if skip_head else self._head(params, x)
        return logits, (new_caches if cache is not None else None), aux_total, x

    # ------------------------------------------------------------------
    # Serving steps
    # ------------------------------------------------------------------
    @declared_precision
    def prefill(self, params, tokens, cache, frontend=None, positions=None):
        logits, new_cache, _, _ = self.forward(params, tokens, frontend, cache, positions=positions)
        return logits[:, -1:], new_cache

    @declared_precision
    def decode_step(self, params, token, pos, cache):
        """token: [B, 1] int32; pos: [B] absolute positions."""
        cfg = self.cfg
        x = embed_tokens(params["embed"], token).astype(self.dtype)
        x = logical_constraint(x, "batch", None, "embed")
        new_caches: dict = {}
        positions = pos[:, None]
        for si, (kind, count) in enumerate(self.segments):
            if kind == "shared_attn":
                x, nc = blocks.shared_attn_decode(params["shared_attn"], x, pos, cfg, cache[str(si)])
                new_caches[str(si)] = nc
                continue
            seg_p = params["segs"][str(si)]

            def body(xc, inp, _kind=kind):
                p_l, c_l = inp
                y, nc, _ = blocks.block_decode(p_l, _kind, xc, pos, cfg, c_l)
                return y, nc
            x, ncs = jax.lax.scan(body, x, (seg_p, cache[str(si)]))
            new_caches[str(si)] = ncs
        logits = self._head(params, x)
        return logits, new_caches

    # ------------------------------------------------------------------
    # Losses
    # ------------------------------------------------------------------
    def _head_hidden(self, params, x):
        """(normed hidden, head weight, transpose?) for fused chunked CE."""
        cfg = self.cfg
        h = apply_norm(params["final_norm"], x, cfg.norm_eps)
        if cfg.tie_embeddings:
            return h, params["embed"], True
        return h, params["lm_head"], False

    @declared_precision
    def loss(self, params, batch, remat: bool = False):
        """batch: {tokens [B,S], loss_mask [B,S] opt, frontend opt}.

        Uses the fused chunked head+CE (layers.chunked_ce_from_hidden) — the
        full [B, S, V] logits are never materialized."""
        cfg = self.cfg
        tokens = batch["tokens"]
        frontend = batch.get("frontend")
        _, _, aux, hidden = self.forward(
            params, tokens, frontend, remat=remat, skip_head=True
        )
        n_front = cfg.frontend_tokens
        h, head, transpose = self._head_hidden(params, hidden[:, n_front:-1])
        mask = batch.get("loss_mask")
        mask = mask[:, 1:] if mask is not None else None
        loss = chunked_ce_from_hidden(head, h, tokens[:, 1:], mask, transpose)
        metrics = {"ce": loss, "aux": aux}
        if cfg.num_experts:
            loss = loss + cfg.router_aux_weight * aux
        if cfg.mtp and "mtp" in params:
            mtp_loss = self._mtp_loss(params, hidden[:, n_front:], tokens)
            metrics["mtp"] = mtp_loss
            loss = loss + 0.3 * mtp_loss
        metrics["loss"] = loss
        return loss, metrics

    def _mtp_loss(self, params, hidden, tokens):
        """DeepSeek-V3 depth-1 multi-token prediction: predict t+2 from
        (h_t, emb(tok_{t+1})) through one extra block."""
        cfg = self.cfg
        p = params["mtp"]
        h = apply_norm(p["norm_h"], hidden[:, :-2], cfg.norm_eps)
        e = apply_norm(
            p["norm_e"], embed_tokens(params["embed"], tokens[:, 1:-1]).astype(h.dtype), cfg.norm_eps
        )
        merged = jnp.concatenate([h, e], axis=-1) @ p["proj"]
        b, s, _ = merged.shape
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
        mtp_block = jax.checkpoint(
            lambda x: blocks.block_forward(p["block"], "dense", x, positions, cfg, None)[0]
        )
        out = mtp_block(merged)
        h, head, transpose = self._head_hidden(params, out)
        return chunked_ce_from_hidden(head, h, tokens[:, 2:], None, transpose)
