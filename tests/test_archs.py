"""Per-architecture smoke tests: reduced variant of each assigned arch runs
one forward + one train step on CPU, asserting shapes and no NaNs."""

import contextlib
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro import configs
from repro.models import build_model

B, S = 2, 16


def _inputs(cfg, key):
    toks = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    batch = {"tokens": toks}
    if cfg.frontend_tokens and not cfg.is_encoder_decoder:
        batch["frontend"] = jax.random.normal(
            jax.random.key(7), (B, cfg.frontend_tokens, cfg.frontend_dim or cfg.d_model)
        )
    if cfg.is_encoder_decoder:
        batch = {"dec_tokens": toks}
        if cfg.frontend_tokens:
            batch["enc_frontend"] = jax.random.normal(
                jax.random.key(7), (B, cfg.enc_seq, cfg.frontend_dim or cfg.d_model)
            )
        else:
            batch["enc_tokens"] = jax.random.randint(jax.random.key(8), (B, 20), 0, cfg.vocab_size)
    return batch


@pytest.mark.parametrize("arch", configs.ASSIGNED_ARCHS)
def test_smoke_forward_and_train_step(arch):
    cfg = configs.get(arch).reduced(dtype="float32")
    assert cfg.num_layers == 2 and cfg.d_model <= 512
    if cfg.num_experts:
        assert cfg.num_experts <= 4
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    batch = _inputs(cfg, jax.random.key(1))

    if cfg.is_encoder_decoder:
        logits = model.forward(
            params, batch["dec_tokens"],
            enc_frontend=batch.get("enc_frontend"), enc_tokens=batch.get("enc_tokens"),
        )
        exp_s = S
    else:
        logits, _, aux, _ = model.forward(params, batch["tokens"], frontend=batch.get("frontend"))
        exp_s = S + cfg.frontend_tokens
        assert jnp.isfinite(aux)
    assert logits.shape == (B, exp_s, cfg.vocab_size)
    assert not bool(jnp.isnan(logits).any())

    # one SGD step on the model loss — grads finite, loss finite
    def loss_fn(p):
        return model.loss(p, batch)[0]

    loss, grads = jax.value_and_grad(loss_fn)(params)
    assert jnp.isfinite(loss)
    gnorm = jax.tree.reduce(
        lambda acc, g: acc + jnp.sum(jnp.square(g.astype(jnp.float32))), grads, 0.0
    )
    assert jnp.isfinite(gnorm)
    new_params = jax.tree.map(lambda p, g: p - 1e-3 * g.astype(p.dtype), params, grads)
    loss2 = loss_fn(new_params)
    assert jnp.isfinite(loss2)


@pytest.mark.parametrize("arch", [a for a in configs.ASSIGNED_ARCHS])
def test_smoke_decode_matches_forward(arch):
    """prefill + single decode step reproduces the full-forward last logits."""
    cfg = configs.get(arch).reduced(dtype="float32")
    if cfg.num_experts:
        # disable capacity dropping so prefill/decode routing agrees exactly
        cfg = dataclasses.replace(cfg, capacity_factor=8.0)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    batch = _inputs(cfg, jax.random.key(1))
    pos = jnp.full((B,), S - 1, jnp.int32)

    if cfg.is_encoder_decoder:
        toks = batch["dec_tokens"]
        full = model.forward(params, toks, enc_frontend=batch.get("enc_frontend"),
                             enc_tokens=batch.get("enc_tokens"))
        cache = model.init_cache(B, 2 * S)
        _, cache = model.prefill(params, toks[:, : S - 1], cache,
                                 enc_frontend=batch.get("enc_frontend"),
                                 enc_tokens=batch.get("enc_tokens"))
        dec, _ = model.decode_step(params, toks[:, S - 1 :], pos, cache)
        last = full[:, -1:]
    else:
        toks = batch["tokens"]
        fe = batch.get("frontend")
        full, _, _, _ = model.forward(params, toks, frontend=fe)
        cache = model.init_cache(B, 2 * S + cfg.frontend_tokens)
        _, cache = model.prefill(params, toks[:, : S - 1], cache, frontend=fe)
        if cfg.frontend_tokens:
            pos = pos + cfg.frontend_tokens
        dec, _ = model.decode_step(params, toks[:, S - 1 :], pos, cache)
        last = full[:, -1:]
    assert jnp.max(jnp.abs(dec - last)) < 5e-4


def test_param_accounting_matches_actual():
    """config.total_params() agrees with the real initialized tree (dense)."""
    for arch in ["smollm-360m", "mamba2-370m"]:
        cfg = configs.get(arch).reduced(dtype="float32")
        model = build_model(cfg)
        params = model.init(jax.random.key(0))
        actual = sum(x.size for x in jax.tree.leaves(params))
        predicted = cfg.total_params()
        # norms/dt biases are excluded from the analytic count; tolerance 2%
        assert abs(actual - predicted) / actual < 0.02, (arch, actual, predicted)


def test_long_context_support_flags():
    assert configs.get("mamba2-370m").supports_long_context
    assert configs.get("zamba2-2.7b").supports_long_context
    assert not configs.get("whisper-base").supports_long_context
    dense = configs.get("smollm-360m")
    assert not dense.supports_long_context
    assert dataclasses.replace(dense, sliding_window=8192).supports_long_context


@pytest.mark.parametrize("dtype,caller,want_highest", [
    ("float32", None, True),
    ("float32", "bfloat16", False),
    ("bfloat16", None, False),
])
def test_float32_models_trace_highest_precision_matmuls(dtype, caller, want_highest):
    """A float32 model's serving steps trace their matmuls at "highest"
    precision (a TPU would otherwise take one bf16 pass); a precision the
    caller set wins, and other dtypes keep the backend default."""
    from repro.core import build_predictor

    cfg = configs.get("gen-fuser").reduced(dtype=dtype)
    fuser = build_model(cfg)
    fp = jax.eval_shape(fuser.init, jax.random.key(0))
    predictor = build_predictor(4, encoder=configs.get("modi-predictor").reduced(dtype=dtype))
    pp = jax.eval_shape(predictor.init, jax.random.key(0))
    cache = jax.eval_shape(lambda: fuser.init_cache(B, 8, enc_seq=S))
    tok = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    pos = jax.ShapeDtypeStruct((B,), jnp.int32)
    enc = jax.ShapeDtypeStruct((B, S), jnp.int32)
    ctx = jax.default_matmul_precision(caller) if caller else contextlib.nullcontext()
    with ctx:
        texts = [
            jax.jit(fuser.prefill).lower(fp, tok, cache, enc_tokens=enc).as_text(),
            jax.jit(fuser.decode_step).lower(fp, tok, pos, cache).as_text(),
            jax.jit(predictor.apply).lower(pp, enc).as_text(),
        ]
    for text in texts:
        assert "dot_general" in text
        assert ("HIGHEST" in text) == want_highest
