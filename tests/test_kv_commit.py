"""``kvcache.gather.commit_token_writes`` and ``scatter_prefill``: a decode
step's new K and V, or a prompt's, for every layer, written into the
stacked cache [L, (P,) K, F, bt, hd] after the layer scan.

- Every live token lands in every layer and head at its frame and slot,
  and nothing else in the cache changes, bit for bit against a numpy
  reference, in the plain and the pooled layout, in float32 and bfloat16.
  Tokens whose block is unmapped write nothing, frame 0 included.  A
  prompt is written in whole blocks: the tail of its last block past the
  prompt holds zeros.
- The write is one scatter per stack whose updates are single ``hd``
  rows: no loop, and the device operations a step runs, and so the events
  a traced window holds, do not grow with depth times batch.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kvcache.gather import commit_token_writes, scatter_prefill

L, F, BT, K, HD, B = 3, 10, 4, 2, 8, 5


def _stack_shape(pools, layers=L):
    return (layers,) + ((pools, K, F // pools) if pools > 1
                        else (K, F)) + (BT, HD)


def _tables(pools):
    # rows 0-3 live (row 3's second block unmapped), row 4 padding
    phys = np.array([[1, 0], [0, 2], [3, 4], [4, -1], [-1, -1]], np.int32)
    return np.where(phys >= 0, phys % (F // pools), -1)


def _reference(ks, new, phys, pos, pools):
    """``ks`` with token t's head k of layer l at (l, pool, k, frame, slot):
    new [L, B, *T, K, hd], pos [B, *T]; a prompt ([B, S] positions) fills
    the rest of its last block with zeros."""
    if pos.ndim == 2:
        S = pos.shape[1]
        pad = -S % BT
        new = np.concatenate([new, np.zeros_like(new[:, :, :pad])], axis=2)
        pos = np.tile(np.arange(S + pad, dtype=np.int32), (B, 1))
    want = ks.copy()
    view = want.reshape(_stack_shape(1))     # pools laid end to end
    if pools > 1:
        view = np.moveaxis(want, 1, 2).reshape(L, K, F, BT, HD)
    pool_of = np.arange(B) // max(B // pools, 1)
    for b, t in np.ndindex(pos.shape if pos.ndim == 2 else (B, 1)):
        p = pos[b, t] if pos.ndim == 2 else pos[b]
        frame = phys[b, min(p // BT, phys.shape[1] - 1)]
        if frame < 0:
            continue
        g = frame + (pool_of[b] * (F // pools) if pools > 1 else 0)
        view[:, :, g, p % BT] = new[:, b, t] if pos.ndim == 2 else new[:, b]
    if pools > 1:
        want = np.moveaxis(view.reshape(L, K, pools, F // pools, BT, HD),
                           2, 1)
    return want


def _check_write(step, pools, dtype):
    rng = np.random.default_rng(pools)
    shape = _stack_shape(pools)
    ks = np.asarray(jnp.asarray(rng.normal(size=shape), dtype))
    vs = np.asarray(jnp.asarray(rng.normal(size=shape), dtype))
    phys = _tables(pools)
    if step == "decode":
        pos = np.array([2, 5, 0, 7, 0], np.int32)
        write = commit_token_writes
    else:
        pos = np.tile(np.arange(6, dtype=np.int32), (B, 1))
        write = scatter_prefill
    tok = pos.shape
    kn = np.asarray(jnp.asarray(rng.normal(size=(L,) + tok + (K, HD)), dtype))
    vn = np.asarray(jnp.asarray(rng.normal(size=(L,) + tok + (K, HD)), dtype))
    args = [jnp.asarray(a) for a in (ks, vs, kn, vn, phys)]
    if step == "decode":
        got_k, got_v = jax.jit(write, static_argnums=6)(*args,
                                                        jnp.asarray(pos), BT)
    else:
        got_k, got_v = jax.jit(write)(*args)
    want_k = _reference(ks, kn, phys, pos, pools)
    want_v = _reference(vs, vn, phys, pos, pools)
    assert got_k.dtype == dtype
    assert np.asarray(got_k).tobytes() == want_k.tobytes()
    assert np.asarray(got_v).tobytes() == want_v.tobytes()


@pytest.mark.parametrize("pools", [1, 5])
def test_commit_writes_each_live_row_into_every_layer(pools):
    for dtype in (jnp.float32, jnp.bfloat16):
        _check_write("decode", pools, dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("pools", [1, 5], ids=["plain", "pooled"])
def test_prefill_scatter_writes_each_live_token(pools, dtype):
    _check_write("prefill", pools, dtype)


def _commit_eqns(layers, batch):
    shape = _stack_shape(1, layers)
    jaxpr = jax.make_jaxpr(commit_token_writes, static_argnums=6)(
        jnp.zeros(shape), jnp.zeros(shape),
        jnp.zeros((layers, batch, K, HD)), jnp.zeros((layers, batch, K, HD)),
        jnp.zeros((batch, 2), jnp.int32), jnp.zeros((batch,), jnp.int32),
        BT)
    eqns = [e for e in jaxpr.jaxpr.eqns]
    while len(eqns) == 1 and "jaxpr" in eqns[0].params:   # a call's body
        eqns = eqns[0].params["jaxpr"].jaxpr.eqns
    return eqns


def test_commit_loop_runs_once_per_row():
    """No loop at all: one scatter per stack of single-``hd``-row updates,
    and the same operations at twice the depth and twice the batch."""
    eqns = _commit_eqns(L, B)
    names = [e.primitive.name for e in eqns]
    assert not {"scan", "while"} & set(names), names
    scatters = [e for e in eqns if e.primitive.name == "scatter"]
    assert len(scatters) == 2, names                 # one for K, one for V
    for e in scatters:
        dims = e.params["dimension_numbers"]
        assert len(dims.update_window_dims) == 1      # one hd row
        assert e.params["mode"] == jax.lax.GatherScatterMode.FILL_OR_DROP
    assert names == [e.primitive.name for e in _commit_eqns(2 * L, 2 * B)]
