"""Pallas TPU paged decode attention.

TPU adaptation of the paper's translation consumer: the grid walks each
sequence's block list; the *block table is a scalar-prefetch operand*, so
the physical frame id (the PTE) is known to the DMA engine before the KV
slab block is fetched from HBM into VMEM — the page walk rides the scalar
pipeline, hiding translation latency behind the KV stream, which is the
kernel-level analogue of numaPTE keeping walks local.

Grid: (B, num_blocks).  The inner dimension is sequential on TPU, so the
online-softmax accumulators live in VMEM scratch across iterations.

Block shapes: KV slab block [1, bt, K, hd] with bt*K*hd*2B per operand
(e.g. 16*8*128*2 = 32KB) — two operands in VMEM double-buffered = 128KB,
comfortably inside the ~16MB VMEM budget; q/out blocks are [1, H, hd].
MXU alignment: hd is 64/112/128/256 across the pool; contractions are over
hd (lane-aligned at 128 for the common configs).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0 ** 30


def _kernel(blocks_ref, lens_ref,            # scalar prefetch
            q_ref, k_ref, v_ref,             # VMEM blocks
            o_ref,                           # output
            m_ref, l_ref, acc_ref,           # scratch
            *, bt: int, n_kv: int, scale: float, window: Optional[int]):
    b = pl.program_id(0)
    i = pl.program_id(1)
    nb = pl.num_programs(1)

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    seq_len = lens_ref[b]
    frame = blocks_ref[b, i]
    block_live = (frame >= 0) & (i * bt < seq_len)

    @pl.when(block_live)
    def _attend():
        q = q_ref[0].astype(jnp.float32)             # [H, hd]
        k = k_ref[0].astype(jnp.float32)             # [bt, K, hd]
        v = v_ref[0].astype(jnp.float32)
        H, hd = q.shape
        G = H // n_kv
        qg = q.reshape(n_kv, G, hd)
        s = jax.lax.dot_general(qg, k,
                                (((2,), (2,)), ((0,), (1,))),
                                preferred_element_type=jnp.float32)
        # dims: [K, G, bt]
        s = s * scale
        pos = i * bt + jax.lax.broadcasted_iota(jnp.int32, (1, 1, bt), 2)
        ok = pos < seq_len
        if window is not None:
            ok &= pos >= seq_len - window
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_ref[...]                          # [K, G]
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[..., None])            # [K, G, bt]
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
        pv = jax.lax.dot_general(p, v,
                                 (((2,), (0,)), ((0,), (1,))),
                                 preferred_element_type=jnp.float32)
        # dims: [K, G, hd]
        acc_ref[...] = acc_ref[...] * alpha[..., None] + pv
        m_ref[...] = m_new

    @pl.when(i == nb - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)[..., None]
        H = q_ref.shape[1]
        hd = q_ref.shape[2]
        o_ref[0] = (acc_ref[...] / l).reshape(H, hd).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_attention_kernel(q: jax.Array, k_slabs: jax.Array,
                           v_slabs: jax.Array, block_tables: jax.Array,
                           seq_lens: jax.Array, *,
                           window: Optional[int] = None,
                           interpret: bool = False) -> jax.Array:
    """q: [B,H,hd]; k/v_slabs: [N,bt,K,hd]; block_tables: [B,MB] physical
    frames; seq_lens: [B].  Returns [B,H,hd] float32."""
    B, H, hd = q.shape
    N, bt, K, _ = k_slabs.shape
    MB = block_tables.shape[1]
    G = H // K
    scale = hd ** -0.5

    grid = (B, MB)
    kernel = functools.partial(_kernel, bt=bt, n_kv=K, scale=scale,
                               window=window)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, H, hd), lambda b, i, bl, ln: (b, 0, 0)),
                pl.BlockSpec((1, bt, K, hd),
                             lambda b, i, bl, ln: (jnp.maximum(bl[b, i], 0), 0, 0, 0)),
                pl.BlockSpec((1, bt, K, hd),
                             lambda b, i, bl, ln: (jnp.maximum(bl[b, i], 0), 0, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, H, hd), lambda b, i, bl, ln: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((K, G), jnp.float32),        # m
                pltpu.VMEM((K, G), jnp.float32),        # l
                pltpu.VMEM((K, G, hd), jnp.float32),    # acc
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, hd), jnp.float32),
        interpret=interpret,
    )(block_tables, seq_lens, q, k_slabs, v_slabs)
    return out
