"""Per-kernel shape/dtype sweeps: Pallas (interpret) vs pure-jnp oracles."""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp
import pytest

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.paged_attention.ops import paged_attention
from repro.kernels.paged_attention.ref import paged_attention_ref
from repro.kernels.pte_gather.ops import pte_gather
from repro.kernels.pte_gather.ref import pte_gather_ref

RNG = np.random.default_rng(0)


def _tables(B, MB, bt, N):
    tables = np.full((B, MB), -1, np.int32)
    lens = RNG.integers(1, MB * bt, B).astype(np.int32)
    perm = RNG.permutation(N)
    f = 0
    for b in range(B):
        nb = int(np.ceil(lens[b] / bt))
        tables[b, :nb] = perm[f:f + nb]
        f += nb
    return jnp.asarray(tables), jnp.asarray(lens)


@pytest.mark.parametrize("B,H,K,hd,bt,MB,N,window", [
    (2, 8, 2, 64, 16, 8, 32, None),
    (3, 4, 4, 128, 16, 4, 16, None),       # MHA
    (2, 16, 2, 64, 8, 16, 48, 24),         # sliding window
    (1, 4, 1, 32, 4, 4, 8, None),          # MQA, tiny blocks
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_matches_ref(B, H, K, hd, bt, MB, N, window, dtype):
    q = jnp.asarray(RNG.standard_normal((B, H, hd)), dtype)
    ks = jnp.asarray(RNG.standard_normal((K, N, bt, hd)), dtype)
    vs = jnp.asarray(RNG.standard_normal((K, N, bt, hd)), dtype)
    tables, lens = _tables(B, MB, bt, N)
    out = paged_attention(q, ks, vs, tables, lens, window=window)
    ref = paged_attention_ref(q, ks, vs, tables, lens, window=window)
    tol = 5e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=tol)


@pytest.mark.parametrize("B,H,K,S,hd,causal,window", [
    (2, 4, 2, 128, 64, True, None),
    (1, 8, 8, 256, 32, True, None),
    (2, 4, 1, 128, 128, True, 64),
    (1, 4, 2, 256, 64, False, None),       # encoder (bidirectional)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(B, H, K, S, hd, causal, window, dtype):
    q = jnp.asarray(RNG.standard_normal((B, H, S, hd)), dtype)
    k = jnp.asarray(RNG.standard_normal((B, K, S, hd)), dtype)
    v = jnp.asarray(RNG.standard_normal((B, K, S, hd)), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window)
    ref = flash_attention_ref(q, k, v, causal=causal, window=window)
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=tol)


@pytest.mark.parametrize("T,epb,M,degree", [
    (8, 64, 16, 2), (4, 512, 32, 9), (16, 128, 7, 0), (2, 64, 5, 3),
])
def test_pte_gather_matches_ref(T, epb, M, degree):
    entries = np.full((T, epb), -1, np.int32)
    mask = RNG.random((T, epb)) > 0.4
    entries[mask] = (RNG.integers(0, 1 << 20, mask.sum())
                     | (3 << 28)).astype(np.int32)
    logical = RNG.integers(-2, T * epb, M).astype(np.int32)
    e, l = jnp.asarray(entries), jnp.asarray(logical)
    f1, p1, w1 = pte_gather(e, l, degree)
    f2, p2, w2 = pte_gather_ref(e, l, degree)
    np.testing.assert_array_equal(np.asarray(f1), np.asarray(f2))
    np.testing.assert_array_equal(np.asarray(p1), np.asarray(p2))
    np.testing.assert_array_equal(np.asarray(w1), np.asarray(w2))
