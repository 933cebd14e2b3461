"""Pallas TPU flash attention (prefill), GQA-aware, causal + windowed.

Grid: (B, H, Sq/bq, Sk/bk) — the k dimension is innermost/sequential, with
online-softmax state in VMEM scratch.  Causal + sliding-window structure is
exploited at *grid* granularity: fully-masked k blocks are skipped before
any DMA math (pl.when), so a local-attention layer's compute scales with
window*S rather than S^2 — the structural speedup gemma3/recurrentgemma
rely on at 32k-500k context.

Block shapes: q/o [1,1,bq,hd], k/v [1,1,bk,hd]; bq=bk=128 keeps each
operand 128*128*4B = 64KB and the MXU fully fed at hd>=128.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0 ** 30
DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            bq: int, bk: int, scale: float, causal: bool,
            window: Optional[int]):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * bq
    k_start = ki * bk
    live = True
    if causal:
        live = k_start <= q_start + bq - 1            # block reachable
    if window is not None:
        live = live & (k_start + bk - 1 >= q_start - window + 1)

    @pl.when(live)
    def _attend():
        q = q_ref[0, 0].astype(jnp.float32)           # [bq, hd]
        k = k_ref[0, 0].astype(jnp.float32)           # [bk, hd]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        ok = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            ok &= qpos >= kpos
        if window is not None:
            ok &= (qpos - kpos) < window
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention_kernel(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True,
                           window: Optional[int] = None,
                           block_q: int = DEFAULT_BLOCK_Q,
                           block_k: int = DEFAULT_BLOCK_K,
                           interpret: bool = False) -> jax.Array:
    """q: [B,H,S,hd]; k,v: [B,K,S,hd].  Returns [B,H,S,hd] f32."""
    B, H, S, hd = q.shape
    K = k.shape[1]
    G = H // K
    bq = min(block_q, S)
    bk = min(block_k, S)
    assert S % bq == 0 and S % bk == 0, (S, bq, bk)
    scale = hd ** -0.5
    grid = (B, H, S // bq, S // bk)
    kernel = functools.partial(_kernel, bq=bq, bk=bk, scale=scale,
                               causal=causal, window=window)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        out_shape=jax.ShapeDtypeStruct((B, H, S, hd), jnp.float32),
        interpret=interpret,
    )(q, k, v)
