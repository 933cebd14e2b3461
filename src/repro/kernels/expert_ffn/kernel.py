"""The SiLU-gated FFN of the experts a chip holds, over rows sorted by
expert (a grouped matmul), as three calls of megablox's Pallas ``gmm``.

``x`` [M, d] holds the routed rows, those of held expert 0 first, then
expert 1's, and so on; ``group_sizes`` [G] counts each expert's rows.
The weights are every layer's stack, [L, G, ...], and ``layer`` picks
one.  ``gmm`` is given the stack whole, as L * G groups (a reshape, no
copy), with this layer's row counts in its G places and zero in every
other: it visits only groups that hold rows, so it reads this layer's
experts from the stack itself, where a layer sliced out of the stack
before the call would be copied first (in a layer scan XLA materialises
such a slice, in HBM or VMEM, every step).  Each visited expert's
matrices are read once per row tile it has rows in; at decode, where
all routed rows fit one row tile, every held expert's three matrices
cross HBM once a layer.

h = silu(x @ w_gate[g]) * (x @ w_in[g]) from float32 products, rounded to
x's dtype; y = h @ w_out[g].  Rows past ``sum(group_sizes)`` are not
computed, and their output rows are left undefined: the caller drops
them.  In a device trace the three calls are the ops named ``gmm``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import gmm


def row_tile(m: int) -> int:
    """Rows per tile: 64 when all routed rows are few (decode: 64 rows x 8
    choices, about 32 of them held here, so one tile holds them all), else
    128."""
    return 64 if m <= 1024 else 128


def _tile(n: int, pref: int) -> int:
    """The largest of ``pref``, ``pref / 2``, ... (down to 128) that
    divides ``n``; ``n`` itself when none does."""
    t = pref
    while t >= 128:
        if n % t == 0:
            return t
        t //= 2
    return n


@functools.partial(jax.jit, static_argnames=("interpret",))
def expert_ffn_kernel(x: jax.Array, group_sizes: jax.Array,
                      w_in: jax.Array, w_gate: jax.Array, w_out: jax.Array,
                      layer: jax.Array, *, interpret: bool = False
                      ) -> jax.Array:
    """x [M, d] sorted by group; group_sizes [G] int32; w_in, w_gate
    [L, G, d, f]; w_out [L, G, f, d]; layer int32 [1].  Returns [M, d] in
    x's dtype, rows past ``sum(group_sizes)`` undefined.  M must be a
    multiple of ``row_tile(M)``."""
    m, d = x.shape
    L, G, _, f = w_in.shape
    tm = row_tile(m)
    sizes = jax.lax.dynamic_update_slice(
        jnp.zeros((L * G,), jnp.int32), group_sizes, (layer[0] * G,))

    def grouped(lhs, w, out_dtype, tk, tn):
        return gmm(lhs, w.reshape((L * G,) + w.shape[2:]), sizes,
                   preferred_element_type=out_dtype, tiling=(tm, tk, tn),
                   interpret=interpret)

    gate = grouped(x, w_gate, jnp.float32, _tile(d, 512), f)
    up = grouped(x, w_in, jnp.float32, _tile(d, 512), f)
    h = (jax.nn.silu(gate) * up).astype(x.dtype)
    return grouped(h, w_out, x.dtype, f, _tile(d, 1024))
