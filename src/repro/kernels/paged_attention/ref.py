"""Pure-jnp oracle for paged decode attention.

Semantics: one query token per sequence attends over its paged KV cache.
`block_tables` holds PHYSICAL frame ids (outputs of the numaPTE block-table
translation, repro.pagedpt.lookup_blocks); -1 marks absent blocks.  Token t
of sequence b lives, for each KV head k, in slab [k, block_tables[b, t //
bt], t % bt].
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -2.0 ** 30


def paged_attention_ref(q: jax.Array, k_slabs: jax.Array, v_slabs: jax.Array,
                        block_tables: jax.Array, seq_lens: jax.Array,
                        *, window: Optional[int] = None,
                        scale: Optional[float] = None) -> jax.Array:
    """q: [B,H,hd]; k/v_slabs: [K,N,bt,hd]; block_tables: [B,MB];
    seq_lens: [B] (valid tokens per sequence).  Returns [B,H,hd] f32."""
    B, H, hd = q.shape
    K, N, bt, _ = k_slabs.shape
    MB = block_tables.shape[1]
    G = H // K
    scale = scale if scale is not None else hd ** -0.5

    frames = jnp.where(block_tables >= 0, block_tables, 0)
    k = k_slabs[:, frames].reshape(K, B, MB * bt, hd)    # [K,B,T,hd]
    v = v_slabs[:, frames].reshape(K, B, MB * bt, hd)
    qg = q.reshape(B, K, G, hd).astype(jnp.float32)
    scores = jnp.einsum("bkgd,kbtd->bkgt", qg, k.astype(jnp.float32)) * scale
    t = jnp.arange(MB * bt)
    valid = t[None, :] < seq_lens[:, None]
    valid &= jnp.repeat(block_tables >= 0, bt, axis=1)
    if window is not None:
        valid &= t[None, :] >= (seq_lens[:, None] - window)
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgt,kbtd->bkgd", probs, v.astype(jnp.float32))
    return out.reshape(B, H, hd)
