"""Per-architecture smoke tests (reduced configs): one forward + train
step on CPU, asserting output shapes and finiteness; plus the decode-path
equivalence check (paged/recurrent decode == full forward logits)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, get_smoke_config, shape_cells
from repro.models import (decode_step, forward_encdec, forward_lm,
                          init_decode_state, init_params, lm_loss,
                          param_count, prefill)
from repro.models.transformer import prefill_encdec
from repro.optim import adamw_init, adamw_update

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_forward_and_train_step(arch):
    cfg = get_smoke_config(arch)
    params = init_params(cfg, KEY)
    B, S = 2, 64
    tokens = jax.random.randint(KEY, (B, S + 1), 0, cfg.vocab_size)
    if cfg.family == "encdec":
        batch = {"enc_feats": jax.random.normal(KEY, (B, S, cfg.d_model)),
                 "tokens": tokens[:, :min(S, cfg.max_decoder_len)]}
    else:
        batch = {"tokens": tokens}

    if cfg.family == "encdec":
        logits, _ = forward_encdec(cfg, params, batch["enc_feats"],
                                   batch["tokens"][:, :-1], remat=False)
        assert logits.shape == (B, batch["tokens"].shape[1] - 1,
                                cfg.vocab_size)
    else:
        logits, _ = forward_lm(cfg, params, tokens[:, :-1], remat=False)
        assert logits.shape == (B, S, cfg.vocab_size)
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())

    # one optimizer step moves the loss
    opt = adamw_init(params)
    (loss0, _), grads = jax.value_and_grad(
        lambda p: lm_loss(cfg, p, batch, remat=False), has_aux=True)(params)
    params2, opt, gnorm = adamw_update(params, grads, opt)
    loss1, _ = lm_loss(cfg, params2, batch, remat=False)
    assert bool(jnp.isfinite(loss0)) and bool(jnp.isfinite(loss1))
    assert float(gnorm) > 0


@pytest.mark.parametrize("arch", [a for a in ARCH_IDS if a != "whisper_base"])
def test_decode_matches_forward(arch):
    cfg = get_smoke_config(arch)
    params = init_params(cfg, KEY)
    B, S = 2, 48
    bt = cfg.kv_block_tokens
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                cfg.vocab_size)
    logits_full, _ = forward_lm(cfg, params, tokens, remat=False)
    want = logits_full[:, -1].astype(jnp.float32)
    MB = (S + bt - 1) // bt + 1
    state = init_decode_state(cfg, B, B * MB, MB)
    phys = jnp.asarray(np.arange(B * MB, dtype=np.int32).reshape(B, MB))
    _, state = prefill(cfg, params, tokens[:, :S - 1], state, phys)
    got, _ = decode_step(cfg, params, state, tokens[:, S - 1], phys)
    rel = float(jnp.max(jnp.abs(want - got.astype(jnp.float32)))) / \
        float(jnp.max(jnp.abs(want)))
    assert rel < 0.03, rel


def test_whisper_decode_matches_forward():
    cfg = get_smoke_config("whisper_base")
    params = init_params(cfg, jax.random.PRNGKey(2))
    B, Se, Sd = 2, 32, 20
    feats = jax.random.normal(jax.random.PRNGKey(2), (B, Se, cfg.d_model))
    dec = jax.random.randint(jax.random.PRNGKey(3), (B, Sd), 0,
                             cfg.vocab_size)
    logits_full, _ = forward_encdec(cfg, params, feats, dec, remat=False)
    want = logits_full[:, -1].astype(jnp.float32)
    bt = cfg.kv_block_tokens
    MB = (Sd + bt - 1) // bt + 1
    state = init_decode_state(cfg, B, B * MB, MB, enc_len=Se)
    phys = jnp.asarray(np.arange(B * MB, dtype=np.int32).reshape(B, MB))
    _, state = prefill_encdec(cfg, params, feats, dec[:, :Sd - 1], state,
                              phys)
    got, _ = decode_step(cfg, params, state, dec[:, Sd - 1], phys)
    rel = float(jnp.max(jnp.abs(want - got.astype(jnp.float32)))) / \
        float(jnp.max(jnp.abs(want)))
    assert rel < 0.03, rel


def test_full_config_param_counts():
    """Full configs match published parameter counts (±10%)."""
    targets = {"chameleon_34b": 34e9, "qwen3_14b": 14.8e9, "yi_6b": 6.1e9,
               "mamba2_370m": 0.37e9, "qwen3_moe_235b_a22b": 235e9,
               "kimi_k2_1t_a32b": 1.0e12, "whisper_base": 72e6}
    for arch, want in targets.items():
        got = param_count(get_config(arch))
        assert abs(got - want) / want < 0.11, (arch, got)


def test_shape_cells_cover_assignment():
    cells = [(a, s) for a in ARCH_IDS for s in shape_cells(a)]
    # every arch runs train/prefill/decode; long_500k only sub-quadratic
    assert len(cells) == 33
    assert ("mamba2_370m", "long_500k") in cells
    assert ("qwen3_14b", "long_500k") not in cells


def test_one_chip_config_cuts_only_depth():
    """The one-chip qwen3_14b keeps every published width and differs from
    the full config only in depth (and bf16 weights, as published)."""
    import dataclasses

    from repro.configs import get_one_chip_config

    full, chip = get_config("qwen3_14b"), get_one_chip_config("qwen3_14b")
    assert chip.n_layers == 8 and chip.param_dtype == jnp.bfloat16
    assert dataclasses.replace(chip, name=full.name, n_layers=full.n_layers,
                               param_dtype=full.param_dtype) == full
    with pytest.raises(ValueError):
        get_one_chip_config("yi_6b")
