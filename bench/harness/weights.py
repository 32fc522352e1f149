"""Random weights made by the benchmark from the seed, on the device.

The trees have the layout the program's models take (checked against the
program's own abstract ``init`` shapes before use), but every value comes
from here: normal weights scaled by 1/sqrt(fan-in), embeddings by 0.02,
norm scales one and biases zero.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np


class _Keys:
    def __init__(self, key):
        self.key = key

    def __call__(self):
        self.key, k = jax.random.split(self.key)
        return k


def _dense(keys, fan_in, shape, dtype):
    return (jax.random.normal(keys(), shape, jnp.float32) / np.sqrt(fan_in)).astype(dtype)


def _embed(keys, shape, dtype):
    return (jax.random.normal(keys(), shape, jnp.float32) * 0.02).astype(dtype)


def _norm(layers, d, dtype, bias=False):
    p = {"scale": jnp.ones((layers, d) if layers else (d,), dtype)}
    if bias:
        p["bias"] = jnp.zeros(p["scale"].shape, dtype)
    return p


def _mlp(keys, layers, d, ff, dtype):
    return {"wi": _dense(keys, d, (layers, d, ff), dtype),
            "wg": _dense(keys, d, (layers, d, ff), dtype),
            "wo": _dense(keys, ff, (layers, ff, d), dtype)}


def _attn(keys, layers, d, h, kv, hd, dtype):
    return {"wq": _dense(keys, d, (layers, d, h, hd), dtype),
            "wk": _dense(keys, d, (layers, d, kv, hd), dtype),
            "wv": _dense(keys, d, (layers, d, kv, hd), dtype),
            "wo": _dense(keys, h * hd, (layers, h, hd, d), dtype)}


def fuser_params(keys, f: dict, dtype, silent_token: int):
    """Encoder-decoder, with an output head of its own unless ``tied_head``.
    The head's row of ``silent_token`` (end of sequence) is zero, so its
    logit is 0 while the greedy pick over the other random rows lies well
    above 0: no answer ends early."""
    d, h, hd, ff = f["d_model"], f["num_heads"], f["head_dim"], f["d_ff"]
    le, ld = f["enc_layers"], f["dec_layers"]
    embed = _embed(keys, (f["vocab_size"], d), dtype)
    params = {
        "embed": embed,
        "enc_pos": _embed(keys, (f["enc_positions"], d), dtype),
        "frontend_proj": _dense(keys, d, (d, d), dtype),
        "enc_segs": {"norm1": _norm(le, d, dtype),
                     "attn": _attn(keys, le, d, h, h, hd, dtype),
                     "norm2": _norm(le, d, dtype),
                     "mlp": _mlp(keys, le, d, ff, dtype)},
        "enc_norm": _norm(0, d, dtype),
        "dec_segs": {"norm1": _norm(ld, d, dtype),
                     "self_attn": _attn(keys, ld, d, h, f["num_kv_heads"], hd, dtype),
                     "norm_x": _norm(ld, d, dtype),
                     "cross": _attn(keys, ld, d, h, h, hd, dtype),
                     "norm2": _norm(ld, d, dtype),
                     "mlp": _mlp(keys, ld, d, ff, dtype)},
        "final_norm": _norm(0, d, dtype),
    }
    if f["tied_head"]:
        params["embed"] = embed.at[silent_token].set(0)
    else:
        head = _dense(keys, d, (d, f["vocab_size"]), dtype)
        params["lm_head"] = head.at[:, silent_token].set(0)
    return params


def predictor_params(keys, p: dict, n_members: int, dtype, max_rel: int):
    d, h, hd, ff, layers = p["d_model"], p["num_heads"], p["head_dim"], p["d_ff"], p["layers"]
    blocks = {"norm1": _norm(layers, d, dtype, bias=True),
              **{k: v for k, v in _attn(keys, layers, d, h, h, hd, dtype).items()},
              "wq_r": _dense(keys, d, (layers, d, h, hd), dtype),
              "wk_r": _dense(keys, d, (layers, d, h, hd), dtype),
              "norm2": _norm(layers, d, dtype, bias=True),
              "mlp": _mlp(keys, layers, d, ff, dtype)}
    return {
        "embed": _embed(keys, (p["vocab_size"], d), dtype),
        "rel_embed": _embed(keys, (2 * max_rel, d), dtype),
        "blocks": blocks,
        "final_norm": _norm(0, d, dtype, bias=True),
        "head": {"lin1": _dense(keys, d, (d, d), dtype), "b1": jnp.zeros((d,), dtype),
                 "glu_w": _dense(keys, d, (d, d), dtype), "glu_b": jnp.zeros((d,), dtype),
                 "glu_v": _dense(keys, d, (d, d), dtype), "glu_c": jnp.zeros((d,), dtype),
                 "out": _dense(keys, d, (d, n_members), dtype),
                 "out_b": jnp.zeros((n_members,), dtype)},
    }


def seed_key(seed: int):
    """A PRNG key for any whole number the driver may pass."""
    key = jax.random.key(seed % 2**32)
    return jax.random.fold_in(key, (seed >> 32) % 2**32)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _make(key, build, spec_json):
    return build(_Keys(key), json.loads(spec_json))


def make(seed: int, build, spec: dict):
    """Run ``build(keys, spec)`` as one jitted call on the default device;
    ``build`` is a module-level function and ``spec`` plain JSON data."""
    return _make(seed_key(seed), build, json.dumps(spec, sort_keys=True))


def check_layout(ours, theirs, what: str) -> None:
    """Fail unless ``ours`` has exactly the tree, shapes and dtypes of the
    program's abstract ``theirs``."""
    a, b = jax.tree.structure(ours), jax.tree.structure(theirs)
    if a != b:
        raise ValueError(f"{what}: weight tree differs from the program's:\n{a}\n{b}")
    for x, y in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        if x.shape != y.shape or x.dtype != y.dtype:
            raise ValueError(f"{what}: leaf {x.shape}/{x.dtype} != {y.shape}/{y.dtype}")
