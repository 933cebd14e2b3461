"""jit'd public wrapper for flash (prefill) attention."""
from __future__ import annotations

from typing import Optional

import jax

from .. import interpret_on_cpu
from .kernel import flash_attention_kernel
from .ref import flash_attention_ref


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    backend: str = "pallas") -> jax.Array:
    if backend == "ref":
        return flash_attention_ref(q, k, v, causal=causal, window=window)
    return flash_attention_kernel(q, k, v, causal=causal, window=window,
                                  interpret=interpret_on_cpu())
