"""jit'd public wrapper for paged decode attention.

The Pallas kernel is compiled for the TPU, and interpreted only where JAX's
default backend is the CPU.  ``backend='ref'`` selects the jnp oracle — used by the
dry-run lowering so XLA sees a pure-HLO path with identical semantics.
"""
from __future__ import annotations

from typing import Optional

import jax

from .. import interpret_on_cpu
from .kernel import paged_attention_kernel
from .ref import paged_attention_ref


def paged_attention(q: jax.Array, k_slabs: jax.Array, v_slabs: jax.Array,
                    block_tables: jax.Array, seq_lens: jax.Array, *,
                    window: Optional[int] = None,
                    backend: str = "pallas") -> jax.Array:
    if backend == "ref":
        return paged_attention_ref(q, k_slabs, v_slabs, block_tables,
                                   seq_lens, window=window)
    return paged_attention_kernel(q, k_slabs, v_slabs, block_tables,
                                  seq_lens, window=window,
                                  interpret=interpret_on_cpu())
