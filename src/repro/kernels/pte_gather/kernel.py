"""Pallas TPU kernel: fused block-table walk + degree-d PTE prefetch.

The paper's page-fault fast path as one TPU kernel: translate a batch of
logical block ids against the local table replica and, for each, stream the
2^d-entry neighbourhood out of the covering table page (Fig 5 semantics —
never crossing the page boundary).  The table page index is a
scalar-prefetch operand so the right 2KB table row is DMA'd to VMEM before
the vector work, exactly one row per miss — the TPU shape of "the walk is
always local, the prefetch is free because the PT page is already open".

Grid: (M,), one miss per step.  Every block's last two dims equal the
array's (the TPU tiling rule for blocks that are not multiples of
(8, 128)): the table is viewed as [T, 1, epb] and fetched one [1, 1, epb]
page at a time, and the outputs are [M, 1, 1] / [M, 1, 2^d] with one row
per step.  The entry is picked by a lane mask and the window by a dynamic
lane rotation, so no vector is indexed at a dynamic lane offset.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

PERM_SHIFT = 28
FRAME_MASK = (1 << PERM_SHIFT) - 1
_INT32_MIN = -(1 << 31)


def _kernel(tids_ref, logical_ref,          # scalar prefetch
            row_ref,                        # [1, 1, epb] the covering page
            frames_ref, present_ref, window_ref,
            *, epb: int, width: int, n_tables: int):
    m = pl.program_id(0)
    logical = logical_ref[m]
    idx = logical % epb
    row = row_ref[0]                                        # [1, epb]
    lane = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
    raw = jnp.max(jnp.where(lane == idx, row, _INT32_MIN), axis=1,
                  keepdims=True)                            # [1, 1]
    ok = (logical >= 0) & (logical < n_tables * epb) & (raw >= 0)
    frames_ref[0] = jnp.where(ok, raw & FRAME_MASK, -1)
    present_ref[0] = ok.astype(jnp.int32)
    start = jnp.clip(idx - width // 2, 0, epb - width)
    # rotate entry `start` to lane 0; the window is the first `width` lanes
    rolled = pltpu.roll(row, (epb - start) % epb, 1)
    window_ref[0] = jnp.where(logical >= 0, rolled[:, :width], -1)


@functools.partial(jax.jit, static_argnames=("prefetch_degree", "interpret"))
def pte_gather_kernel(entries: jax.Array, logical: jax.Array,
                      prefetch_degree: int, *, interpret: bool = False
                      ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """entries: [T, epb] packed PTEs; logical: [M].  Returns
    (frames [M] i32, present [M] bool, window [M, 2^d] i32)."""
    T, epb = entries.shape
    M = logical.shape[0]
    W = 1 << prefetch_degree
    assert W <= epb, (W, epb)
    tids = jnp.clip(jnp.where(logical >= 0, logical // epb, 0), 0, T - 1)
    kernel = functools.partial(_kernel, epb=epb, width=W, n_tables=T)
    frames, present, window = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(M,),
            in_specs=[
                pl.BlockSpec((1, 1, epb),
                             lambda m, tids, logical: (tids[m], 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, 1), lambda m, tids, logical: (m, 0, 0)),
                pl.BlockSpec((1, 1, 1), lambda m, tids, logical: (m, 0, 0)),
                pl.BlockSpec((1, 1, W), lambda m, tids, logical: (m, 0, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((M, 1, 1), jnp.int32),
            jax.ShapeDtypeStruct((M, 1, 1), jnp.int32),
            jax.ShapeDtypeStruct((M, 1, W), jnp.int32),
        ],
        interpret=interpret,
    )(tids, logical, entries.reshape(T, 1, epb))
    return (frames.reshape(M), present.reshape(M).astype(jnp.bool_),
            window.reshape(M, W))
