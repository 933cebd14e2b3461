"""Pallas kernels of the serving path (``flash_attention``,
``paged_attention``, ``pte_gather``, ``expert_ffn``) plus the jitted
``fifo_miss`` loop.

Each ``ops`` wrapper compiles its kernel for the TPU, and interprets it
only where JAX's default backend is the CPU (``interpret_on_cpu``)."""
from __future__ import annotations

import jax


def interpret_on_cpu() -> bool:
    """Whether a Pallas kernel runs in interpret mode: only where JAX's
    default backend is the CPU, which is where the ops wrappers, traced
    under ``jit``, place their operands and compile their programs."""
    return jax.default_backend() == "cpu"
