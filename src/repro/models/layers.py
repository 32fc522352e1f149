"""Common neural-net building blocks (pure-functional JAX)."""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.sharding import logical_constraint

# ---------------------------------------------------------------------------
# Matmul precision
# ---------------------------------------------------------------------------


def declared_precision(method):
    """Run a model method's matmuls at the precision its dtype declares.

    A TPU's default precision rounds every float32 matmul operand to
    bfloat16 for a single MXU pass, so a float32 model would compute at
    bf16 precision, and its greedy text would depend on the batch and cache
    shapes a program was compiled for (the rounding follows the tiling).
    Methods of a float32 model therefore trace under "highest" precision
    unless the caller set one with ``jax.default_matmul_precision``; other
    dtypes are left alone.  The CPU computes float32 matmuls in float32
    either way."""

    @functools.wraps(method)
    def wrapped(self, *args, **kwargs):
        if (self.dtype != jnp.float32
                or jax.config.jax_default_matmul_precision is not None):
            return method(self, *args, **kwargs)
        with jax.default_matmul_precision("highest"):
            return method(self, *args, **kwargs)

    return wrapped


# ---------------------------------------------------------------------------
# Initialization
# ---------------------------------------------------------------------------


def dense_init(key: jax.Array, fan_in: int, shape, dtype) -> jax.Array:
    scale = 1.0 / np.sqrt(max(fan_in, 1))
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def embed_init(key: jax.Array, shape, dtype) -> jax.Array:
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def init_norm(d: int, dtype, kind: str = "rmsnorm") -> dict:
    p = {"scale": jnp.ones((d,), dtype)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((d,), dtype)
    return p


def apply_norm(p: dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    if "bias" in p:  # layernorm
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        y = (x - mean) * jax.lax.rsqrt(var + eps)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:  # rmsnorm
        ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        y = x * jax.lax.rsqrt(ms + eps) * p["scale"].astype(jnp.float32)
    return y.astype(dtype)


# ---------------------------------------------------------------------------
# Activations
# ---------------------------------------------------------------------------


def activation(name: str):
    return {
        "silu": jax.nn.silu,
        "gelu": jax.nn.gelu,
        "relu": jax.nn.relu,
    }[name]


def glu(x: jax.Array, w: jax.Array, b: jax.Array, v: jax.Array, c: jax.Array) -> jax.Array:
    """Gated Linear Unit (Dauphin et al. 2017): (xW+b) * sigmoid(xV+c)."""
    return (x @ w + b) * jax.nn.sigmoid(x @ v + c)


# ---------------------------------------------------------------------------
# Gated MLP (SwiGLU-style; used by every dense block and expert)
# ---------------------------------------------------------------------------


def init_mlp(key: jax.Array, d_model: int, d_ff: int, dtype) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "wi": dense_init(k1, d_model, (d_model, d_ff), dtype),
        "wg": dense_init(k2, d_model, (d_model, d_ff), dtype),
        "wo": dense_init(k3, d_ff, (d_ff, d_model), dtype),
    }


def apply_mlp(p: dict, x: jax.Array, act: str = "silu") -> jax.Array:
    h = activation(act)(x @ p["wg"]) * (x @ p["wi"])
    h = logical_constraint(h, "batch", "seq", "mlp")
    return h @ p["wo"]


# ---------------------------------------------------------------------------
# Embeddings / LM head
# ---------------------------------------------------------------------------


def init_embedding(key: jax.Array, vocab: int, d_model: int, dtype) -> jax.Array:
    return embed_init(key, (vocab, d_model), dtype)


def embed_tokens(table: jax.Array, tokens: jax.Array) -> jax.Array:
    out = jnp.take(table, tokens, axis=0)
    return logical_constraint(out, "batch", "seq", "embed")


def lm_logits(table_or_head: jax.Array, x: jax.Array, transpose: bool) -> jax.Array:
    w = table_or_head.T if transpose else table_or_head
    logits = x @ w.astype(x.dtype)
    return logical_constraint(logits, "batch", "seq", "vocab")


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------


def rope_frequencies(dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [..., seq, dim]; positions: broadcastable to [..., seq]."""
    dim = x.shape[-1]
    freqs = rope_frequencies(dim, theta)  # [dim/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., seq, dim/2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Losses
# ---------------------------------------------------------------------------


def cross_entropy(logits: jax.Array, labels: jax.Array, mask: Optional[jax.Array] = None):
    """Mean token-level cross entropy. logits [..., V], labels [...] int."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def chunked_ce_from_hidden(
    head: jax.Array,
    x: jax.Array,
    labels: jax.Array,
    mask: Optional[jax.Array],
    transpose: bool,
    chunk: int = 512,
) -> jax.Array:
    """Fused LM-head + cross entropy, chunked over the sequence.

    Never materializes the full [B, S, V] logits: each scan step computes
    one [B, chunk, V] slice (rematerialized in the backward), which keeps
    the CE working set at chunk/S of the naive cost — the standard fused
    linear+CE production trick (e.g. Liger), expressed in pure JAX.

    x: [B, S, D] hidden (post-final-norm); labels: [B, S] targets aligned
    with x (caller shifts); mask: [B, S] or None.
    """
    b, s, d = x.shape
    chunk = min(chunk, s)
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad))) if mask is not None else None
    if mask is None:
        mask = jnp.pad(jnp.ones((b, s), jnp.float32), ((0, 0), (0, pad)))
    nc = x.shape[1] // chunk
    xs = (
        x.reshape(b, nc, chunk, d).swapaxes(0, 1),
        labels.reshape(b, nc, chunk).swapaxes(0, 1),
        mask.reshape(b, nc, chunk).swapaxes(0, 1),
    )

    @jax.checkpoint
    def body(carry, inp):
        xc, lc, mc = inp
        nll_sum, m_sum = carry
        logits = lm_logits(head, xc, transpose).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        m = mc.astype(jnp.float32)
        return (nll_sum + jnp.sum((logz - gold) * m), m_sum + jnp.sum(m)), None

    (nll, msum), _ = jax.lax.scan(body, (jnp.zeros(()), jnp.zeros(())), xs)
    return nll / jnp.maximum(msum, 1.0)


def huber_loss(pred: jax.Array, target: jax.Array, delta: float = 0.3) -> jax.Array:
    """Huber loss (paper Eq. 8, delta=0.3 per Table 2)."""
    err = jnp.abs(pred.astype(jnp.float32) - target.astype(jnp.float32))
    quad = 0.5 * jnp.square(err)
    lin = delta * (err - 0.5 * delta)
    return jnp.mean(jnp.where(err <= delta, quad, lin))
