"""Pure-jnp oracle for the grouped expert FFN: ``jax.lax.ragged_dot``.

x [M, d] sorted by group, group_sizes [G]: each group's rows go through
its expert's SiLU-gated FFN, h = silu(x @ w_gate[g]) * (x @ w_in[g]),
y = h @ w_out[g], with float32 accumulation and h rounded to x's dtype
as the kernel rounds it.  Rows past ``sum(group_sizes)`` come out zero.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def expert_ffn_ref(x: jax.Array, group_sizes: jax.Array, w_in: jax.Array,
                   w_gate: jax.Array, w_out: jax.Array) -> jax.Array:
    def dot(a, w):
        return jax.lax.ragged_dot(a, w, group_sizes,
                                  preferred_element_type=jnp.float32)
    h = (jax.nn.silu(dot(x, w_gate)) * dot(x, w_in)).astype(x.dtype)
    return dot(h, w_out).astype(x.dtype)
