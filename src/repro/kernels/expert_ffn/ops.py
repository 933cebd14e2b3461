"""Public wrapper of the grouped expert FFN: pads the rows to the
kernel's row tile and runs the Pallas kernel (interpreted where JAX's
default backend is the CPU), or the ``ragged_dot`` oracle."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import interpret_on_cpu
from .kernel import expert_ffn_kernel, row_tile
from .ref import expert_ffn_ref


def expert_ffn(x: jax.Array, group_sizes: jax.Array, w_in: jax.Array,
               w_gate: jax.Array, w_out: jax.Array, layer=None, *,
               backend: str = "pallas") -> jax.Array:
    """x [M, d] sorted by group, group_sizes [G] int32 -> [M, d]; the rows
    past ``sum(group_sizes)`` are undefined (zero with ``backend="ref"``).
    The weights are one layer's, [G, ...], or with ``layer`` (an int32
    scalar) every layer's stack, [L, G, ...], of which the kernel reads
    that layer's without slicing it out."""
    if layer is None:
        w_in, w_gate, w_out = w_in[None], w_gate[None], w_out[None]
        layer = 0
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))
    if backend == "ref":
        return expert_ffn_ref(x, group_sizes, w_in[layer[0]],
                              w_gate[layer[0]], w_out[layer[0]])
    m = x.shape[0]
    pad = -m % row_tile(m)
    xp = jnp.pad(x, ((0, pad), (0, 0))) if pad else x
    y = expert_ffn_kernel(xp, group_sizes.astype(jnp.int32), w_in, w_gate,
                          w_out, layer, interpret=interpret_on_cpu())
    return y[:m] if pad else y
