"""``kvcache.gather.commit_token_writes``: one decode step's new K and V
for every layer, written into the stacked cache after the layer scan.

- Every live row's token lands in every layer at its frame and slot, and
  nothing else in the cache changes (a numpy reference), in the plain
  and the pooled layout.
- The write loop runs once per row, not once per row and layer: the
  device operations a step runs, and so the events a traced window
  holds, do not grow with depth times batch.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kvcache.gather import commit_token_writes

L, F, BT, K, HD, B = 3, 10, 4, 2, 8, 5


def _case(pools):
    rng = np.random.default_rng(pools)
    shape = (L,) + ((pools, F // pools) if pools > 1 else (F,)) + (BT, K, HD)
    ks = rng.normal(size=shape).astype(np.float32)
    vs = rng.normal(size=shape).astype(np.float32)
    kn = rng.normal(size=(L, B, K, HD)).astype(np.float32)
    vn = rng.normal(size=(L, B, K, HD)).astype(np.float32)
    # rows 0-3 live (row 3's next block unmapped, so it writes nothing),
    # row 4 padding
    per_pool = F // pools
    phys = np.array([[1, 0], [0, 2], [3, 4], [4, -1], [-1, -1]], np.int32)
    phys = np.where(phys >= 0, phys % per_pool, -1)
    pos = np.array([2, 5, 0, 7, 0], np.int32)
    return ks, vs, kn, vn, phys, pos


@pytest.mark.parametrize("pools", [1, 5])
def test_commit_writes_each_live_row_into_every_layer(pools):
    ks, vs, kn, vn, phys, pos = _case(pools)
    got_k, got_v = commit_token_writes(*map(jnp.asarray, (ks, vs, kn, vn,
                                                          phys, pos)), BT)
    want_k, want_v = ks.copy(), vs.copy()
    flat = (L, F) + ks.shape[-3:]
    wk, wv = want_k.reshape(flat), want_v.reshape(flat)   # views
    pool_of = np.arange(B) // max(B // pools, 1)
    for b in range(B):
        frame = phys[b, min(pos[b] // BT, phys.shape[1] - 1)]
        if frame < 0:
            continue
        g = frame + (pool_of[b] * (F // pools) if pools > 1 else 0)
        wk[:, g, pos[b] % BT] = kn[:, b]
        wv[:, g, pos[b] % BT] = vn[:, b]
    np.testing.assert_array_equal(np.asarray(got_k), want_k)
    np.testing.assert_array_equal(np.asarray(got_v), want_v)


def test_commit_loop_runs_once_per_row():
    ks, vs, kn, vn, phys, pos = _case(1)
    jaxpr = jax.make_jaxpr(commit_token_writes, static_argnums=6)(
        ks, vs, kn, vn, phys, pos, BT)
    lengths = [e.params["length"] for e in jaxpr.jaxpr.eqns
               if e.primitive.name == "scan"]
    assert lengths == [B]
