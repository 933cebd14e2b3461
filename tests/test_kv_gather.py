"""``gather_readonly`` reads a layer's live blocks straight from the stacked
KV cache.  Its values must be bit for bit those of slicing the layer's pool
out of the stack, clamping -1 table entries to frame 0 and gathering the
frames.  The layer index is traced, as in the decode step's scan over
layers."""
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
    shape = (L, F, BT, K, HD) if pools == 0 else (L, pools, F, BT, K, HD)
    k_np = rng.normal(size=shape).astype(np.float32)
    v_np = rng.normal(size=shape).astype(np.float32)
    k_stack = jnp.asarray(k_np, dtype)
    v_stack = jnp.asarray(v_np, dtype)
    tables = _tables(rng, F)
    ka, va = _scan_gather(k_stack, v_stack, jnp.asarray(tables))

    # reference: the layer's pool sliced out, pools laid end to end, each
    # row reading its own pool (row b lives in pool b // (B / pools))
    offset = (np.zeros(B, np.int32) if pools == 0
              else np.arange(B) // (B // pools) * F)
    frames = np.where(tables >= 0, tables + offset[:, None], 0)
    k_ref = np.asarray(k_stack).reshape((L, -1, BT, K, HD))
    v_ref = np.asarray(v_stack).reshape((L, -1, BT, K, HD))
    for li in range(L):
        want_k, want_v = k_ref[li][frames], v_ref[li][frames]
        assert ka[li].shape == (B, MB, BT, K, HD)
        assert ka[li].tobytes() == want_k.tobytes(), li
        assert va[li].tobytes() == want_v.tobytes(), li
