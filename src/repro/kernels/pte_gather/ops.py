"""jit'd public wrapper for the fused walk+prefetch kernel."""
from __future__ import annotations

from typing import Tuple

import jax

from .. import interpret_on_cpu
from .kernel import pte_gather_kernel
from .ref import pte_gather_ref


def pte_gather(entries: jax.Array, logical: jax.Array,
               prefetch_degree: int, *, backend: str = "pallas"
               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    if backend == "ref":
        return pte_gather_ref(entries, logical, prefetch_degree)
    return pte_gather_kernel(entries, logical, prefetch_degree,
                             interpret=interpret_on_cpu())
