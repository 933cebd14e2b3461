"""``gather_readonly`` reads a layer's live blocks straight from the stacked
KV cache, laid out heads before frames ([L, K, F, bt, hd], pooled [L, P,
K, F_local, bt, hd]).  Its values must be bit for bit those of slicing the
layer's pool out of the stack and gathering every head's frames, [B, K,
nb, bt, hd], where an unmapped (-1) block i of row b reads frame (b * nb
+ i) % F, so that no frame is read once for every unmapped block.  The
layer index is traced, as in the decode step's scan over layers."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kvcache.gather import gather_readonly

L, F, BT, K, HD = 3, 10, 4, 2, 8
B, MB = 4, 5


def _tables(rng, n_frames):
    """[B, MB] tables of distinct live frames with -1 entries mixed in:
    a tail of unmapped blocks per row, a whole unmapped row, and holes."""
    tbl = rng.integers(0, n_frames, (B, MB)).astype(np.int32)
    tbl[0, 3:] = -1
    tbl[1, 1] = -1
    tbl[2] = -1
    return tbl


def _scan_gather(k_stack, v_stack, tables):
    def body(_, li):
        return None, gather_readonly(k_stack, v_stack, li, tables)
    _, (ka, va) = jax.jit(lambda: jax.lax.scan(
        body, None, jnp.arange(k_stack.shape[0])))()
    return np.asarray(ka), np.asarray(va)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("pools", [0, 2], ids=["plain", "pooled"])
def test_gather_readonly_matches_slice_then_gather(pools, dtype):
    rng = np.random.default_rng(pools)
    shape = (L, K, F, BT, HD) if pools == 0 else (L, pools, K, F, BT, HD)
    k_np = rng.normal(size=shape).astype(np.float32)
    v_np = rng.normal(size=shape).astype(np.float32)
    k_stack = jnp.asarray(k_np, dtype)
    v_stack = jnp.asarray(v_np, dtype)
    tables = _tables(rng, F)
    ka, va = _scan_gather(k_stack, v_stack, jnp.asarray(tables))

    # reference: the layer's pool sliced out, each row reading its own
    # pool (row b lives in pool b // (B / pools)), every head's frames
    filler = np.arange(B * MB).reshape(B, MB) % F
    frames = np.where(tables >= 0, tables, filler)
    k_ref, v_ref = np.asarray(k_stack), np.asarray(v_stack)
    if pools == 0:
        k_ref, v_ref = k_ref[:, None], v_ref[:, None]
    pool = np.arange(B) // (B // max(pools, 1))
    for li in range(L):
        # [B, MB, K, BT, HD] -> [B, K, MB, BT, HD]
        want_k = k_ref[li][pool[:, None], :, frames].transpose(0, 2, 1, 3, 4)
        want_v = v_ref[li][pool[:, None], :, frames].transpose(0, 2, 1, 3, 4)
        assert ka[li].shape == (B, K, MB, BT, HD)
        assert ka[li].tobytes() == np.ascontiguousarray(want_k).tobytes(), li
        assert va[li].tobytes() == np.ascontiguousarray(want_v).tobytes(), li
