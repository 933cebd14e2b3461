"""The served expert layer at a chip's share (``models.moe.moe_serve``)
and its grouped-FFN kernel (``kernels.expert_ffn``), on the CPU with the
kernel interpreted.

- The kernel equals ``jax.lax.ragged_dot`` on the rows it computes, with
  empty groups, every row in one group, no row at all, and groups that
  share row tiles; given every layer's stack and a layer index, it equals
  the kernel given that layer alone.
- The shares add up: over the 4 shares of 2 experts each of the 8-expert
  smoke preset, the layer's outputs sum to the uncut layer's.
- Nothing is dropped: with a router that sends every choice of every
  token to the 2 experts held here, far past any capacity, the layer
  equals a per-token loop over the tokens' choices.
- The parameters count the published model whatever share is held.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_one_chip_config, get_smoke_config
from repro.kernels.expert_ffn.ops import expert_ffn
from repro.models import active_param_count, param_count
from repro.models.moe import init_moe, moe_serve
from repro.models.common import KeyGen


@pytest.mark.parametrize("m, sizes", [
    (40, [3, 0, 10, 7]),                   # empty group between others
    (40, [0, 0, 40, 0]),                   # every row in one group
    (40, [40, 0, 0, 0]),
    (40, [0, 0, 0, 0]),                    # no row held here
    (200, [20, 0, 30, 50, 0, 10, 40, 1]),  # groups straddle row tiles
])
def test_kernel_matches_ragged_dot(m, sizes):
    d, f, G = 128, 256, len(sizes)
    rng = np.random.default_rng(m + len(sizes))

    def normal(*shape, fan_in=1):
        return jnp.asarray(rng.normal(size=shape) / np.sqrt(fan_in),
                           jnp.bfloat16)
    x = normal(m, d)
    w_in, w_gate = normal(G, d, f, fan_in=d), normal(G, d, f, fan_in=d)
    w_out = normal(G, f, d, fan_in=f)
    gs = jnp.asarray(sizes, jnp.int32)
    got = expert_ffn(x, gs, w_in, w_gate, w_out)
    want = expert_ffn(x, gs, w_in, w_gate, w_out, backend="ref")
    n = sum(sizes)
    assert got.shape == (m, d) and got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got[:n], np.float32),
                               np.asarray(want[:n], np.float32),
                               rtol=2e-2, atol=2e-2)


def test_kernel_reads_one_layer_of_a_stack():
    """With a layer index the kernel takes every layer's experts and uses
    that layer's, as from the layer alone."""
    L, G, m, d, f = 3, 4, 40, 128, 256
    rng = np.random.default_rng(9)

    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape) / np.sqrt(shape[-2]),
                           jnp.bfloat16)
    x = jnp.asarray(rng.normal(size=(m, d)), jnp.bfloat16)
    w = (normal(L, G, d, f), normal(L, G, d, f), normal(L, G, f, d))
    gs = jnp.asarray([9, 0, 20, 6], jnp.int32)
    for layer in range(L):
        got = expert_ffn(x, gs, *w, jnp.int32(layer))
        alone = expert_ffn(x, gs, *(a[layer] for a in w))
        np.testing.assert_array_equal(np.asarray(got[:35]),
                                      np.asarray(alone[:35]))


def _f32_moe():
    """The smoke preset (8 experts, 2 per token) in float32 throughout, so
    sums of shares compare to float32 rounding."""
    return dataclasses.replace(get_smoke_config("qwen3_moe_235b_a22b"),
                               dtype=jnp.float32, param_dtype=jnp.float32)


def _share(cfg, p, first, held):
    sub = {k: (v[first:first + held] if k.startswith("we_") else v)
           for k, v in p.items()}
    return dataclasses.replace(cfg, experts_held=held,
                               first_held_expert=first), sub


def test_shares_sum_to_the_uncut_layer():
    cfg = _f32_moe()
    p = init_moe(cfg, KeyGen(jax.random.PRNGKey(3)))
    x = jax.random.normal(jax.random.PRNGKey(4), (3, 7, cfg.d_model))
    whole = moe_serve(cfg, p, x)
    parts = [moe_serve(*_share(cfg, p, first, 2), x)
             for first in range(0, cfg.n_experts, 2)]
    assert all(float(jnp.abs(q).max()) > 0 for q in parts)
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)


def test_a_chip_holding_every_choice_drops_nothing():
    cfg = _f32_moe()
    p = init_moe(cfg, KeyGen(jax.random.PRNGKey(5)))
    # every token has a large first coordinate and the router reads it
    # into experts 6 and 7 only: all 2 x 64 choices land on this chip's
    # two experts, 64 rows each (a 1.25 capacity would keep 20)
    x = jax.random.normal(jax.random.PRNGKey(6), (4, 16, cfg.d_model))
    x = x.at[..., 0].set(8.0)
    router = p["router"].at[0].set(0.0).at[0, 6].set(4.0).at[0, 7].set(3.95)
    cfg6, sub = _share(cfg, dict(p, router=router), 6, 2)
    got = moe_serve(cfg6, sub, x)

    xf = np.asarray(x, np.float64).reshape(-1, cfg.d_model)
    w = {k: np.asarray(v, np.float64) for k, v in sub.items()}
    logits = xf @ w["router"]
    want = np.zeros_like(xf)
    for t, h in enumerate(xf):
        top = np.argsort(-logits[t])[:cfg.experts_per_token]
        assert set(top) == {6, 7}
        g = np.exp(logits[t, top] - logits[t].max())
        for e, ge in zip(top, g / g.sum()):
            up, gate = h @ w["we_in"][e - 6], h @ w["we_gate"][e - 6]
            want[t] += ge * ((gate / (1 + np.exp(-gate)) * up)
                             @ w["we_out"][e - 6])
    # float32 against float64, outputs up to about 50
    np.testing.assert_allclose(np.asarray(got).reshape(want.shape), want,
                               rtol=1e-4, atol=1e-3)


def test_prefill_chunks_route_like_one_pass(monkeypatch):
    """A prefill's tokens go through the layer in chunks; a chunk size
    that does not divide them pads the last chunk without effect."""
    import repro.models.moe as moe
    cfg = _f32_moe()
    p = init_moe(cfg, KeyGen(jax.random.PRNGKey(7)))
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 13, cfg.d_model))
    whole = moe_serve(cfg, p, x)
    monkeypatch.setattr(moe, "SERVE_CHUNK_TOKENS", 8)
    np.testing.assert_allclose(np.asarray(moe_serve(cfg, p, x)),
                               np.asarray(whole), rtol=1e-5, atol=1e-5)


def test_param_counts_are_the_published_models():
    full = get_config("qwen3_moe_235b_a22b")
    chip = get_one_chip_config("qwen3_moe_235b_a22b")
    assert (chip.n_experts, chip.n_held_experts) == (128, 8)
    cut = dataclasses.replace(full, experts_held=8)
    assert param_count(cut) == param_count(full)
    assert active_param_count(cut) == active_param_count(full)
    assert abs(active_param_count(full) - 22e9) / 22e9 < 0.05
    # and the one-chip cut differs from the published config only in
    # depth, the experts held and bf16 weights
    assert dataclasses.replace(
        chip, name=full.name, n_layers=full.n_layers, experts_held=None,
        param_dtype=full.param_dtype) == full
