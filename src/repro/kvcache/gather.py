"""Pool-partitioned KV slab update + gather (the numaPTE sharding).

The KV pool is laid out heads before frames: one layer's slabs are
``[K, F, bt, hd]`` and the decode state stacks them as ``[L, K, F, bt,
hd]``.  Pool-partitioned, each data shard has its own pool of ``F_local``
frames — ``[L, n_pools, K, F_local, bt, hd]``, the pool axis mapped to
'data' — and every sequence's frames live in its own shard's pool.  This
is the device-level mirror of the paper's partitioned page tables
(Section 3.3: each node owns the tables of its own data, no cross-node
traffic in the common case): the decode-step gather is provably
pool-local, so SPMD emits *zero* collectives for KV reads, instead of the
all-gather a flat sharded pool would force.

Heads-major is what the attention dot reads for any head count: the
gathered blocks come out ``[B, K, nb, bt, hd]`` and every minor tile is a
``[bt, hd]`` block, so the TPU compiler keeps the pool in the layout it is
stored in.  A decode step writes one ``hd`` row at (layer, head, frame,
slot) per token (``_write_rows``), a prefill whole ``[bt, hd]`` blocks at
(layer, head, frame) (``_write_blocks``): a write that spans the layer or
the head axis makes the compiler relay out the whole stack to put that
axis beside ``hd``.

``update_gather_pooled`` runs under shard_map over ('data',) nested in the
jitted step; head_dim stays sharded over 'model' outside the map.

The device trace names this module's work by scope: ``kv_gather`` (the
read of a step's live blocks), ``kv_commit`` (the decode step's token
writes) and ``kv_scatter`` (the prefill's writes).
"""
from __future__ import annotations

import contextlib
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..distributed.sharding import current_rules, get_active_mesh as _mesh


def _on_mesh(mesh, axis) -> bool:
    """Whether ``mesh`` has every mesh axis that the pool axis ``axis``
    (one name or a tuple, e.g. ('pod', 'data')) names."""
    names = axis if isinstance(axis, tuple) else (axis,)
    return mesh is not None and all(n in mesh.axis_names for n in names)


def _pool_of_rows(n_rows: int, n_pools: int) -> jax.Array:
    """The pool of each batch row: the rows split evenly over the pools,
    in order, as the batch axis is sharded in lockstep with the pools."""
    return jnp.arange(n_rows) // max(n_rows // n_pools, 1)


def _token_frames(phys_blocks: jax.Array, positions: jax.Array,
                  block_tokens: int, n_frames: int
                  ) -> Tuple[jax.Array, jax.Array]:
    """(frame, slot) of each row's token at ``positions`` [B] under the
    tables ``phys_blocks`` [B, MB].  A token whose block is unmapped (-1:
    inactive or padding rows) gets frame ``n_frames``, outside the pool,
    so ``_write_rows`` drops it: it never touches frame 0."""
    blk = jnp.clip(positions // block_tokens, 0, phys_blocks.shape[1] - 1)
    frame = jnp.take_along_axis(phys_blocks, blk[:, None], axis=1)[:, 0]
    return (jnp.where(frame >= 0, frame, n_frames),
            positions % block_tokens)


def _write_rows(slabs: jax.Array, rows: jax.Array, frame: jax.Array,
                slot: jax.Array, pool: Optional[jax.Array] = None
                ) -> jax.Array:
    """Write ``rows`` [(L,) B, K, hd] into ``slabs`` [(L,) (P,) K, F, bt,
    hd] as one scatter: head k of row b's token in layer l lands at (l,
    pool[b], k, frame[b], slot[b]).  Each update is a single ``hd`` row,
    so no write spans the layer or the head axis, and the number of
    operations does not grow with depth times batch.  ``frame`` == F
    drops the token."""
    idx = (jnp.arange(rows.shape[-2]), frame[:, None], slot[:, None])
    if pool is not None:
        idx = (pool[:, None],) + idx
    if rows.ndim == 4:                           # a leading layer axis
        idx = (jnp.arange(rows.shape[0])[:, None, None],) + idx
    return slabs.at[idx].set(rows.astype(slabs.dtype), mode="drop")


def _write_blocks(stack: jax.Array, kv: jax.Array, frame: jax.Array,
                  pool: Optional[jax.Array] = None) -> jax.Array:
    """Write a prompt's K or V, kv [L, B, S, K, hd] at positions 0..S-1,
    into ``stack`` [L, (P,) K, F, bt, hd] as one scatter of whole [bt, hd]
    blocks: block i of row b's head k in layer l lands at (l, pool[b], k,
    frame[b, i]).  Each update is one tile and none spans the layer or the
    head axis; single-row updates, 16 times as many, took the TPU
    compiler eight times as long.  A prompt that ends inside a block
    fills the block's tail with zeros, which no read attends before a
    decode step writes there.  ``frame`` == F drops the block."""
    L, B, S, K, hd = kv.shape
    nb = frame.shape[1]
    bt = stack.shape[-2]
    kv = jnp.pad(kv, ((0, 0), (0, 0), (0, nb * bt - S), (0, 0), (0, 0)))
    kv = kv.reshape(L, B, nb, bt, K, hd).transpose(0, 1, 2, 4, 3, 5)
    idx = (jnp.arange(K), frame[:, :, None])
    if pool is not None:
        idx = (pool[:, None, None],) + idx
    idx = (jnp.arange(L)[:, None, None, None],) + idx
    return stack.at[idx].set(kv.astype(stack.dtype), mode="drop")


def _gather_layer(stack: jax.Array, layer_idx, frames: jax.Array,
                  pool: Optional[jax.Array] = None) -> jax.Array:
    """Row b's blocks ``frames[b]`` [B, nb] of layer ``layer_idx``'s pool
    (pool ``pool[b, i]``'s, where pooled), every head: [B, K, nb, bt, hd].

    ``stack`` is [L, K, F, bt, hd] or [L, P, K, F, bt, hd], read as one
    gather on its flat [L * (P *) K * F, bt, hd] view at rows
    ``((layer * P + pool) * K + head) * F + frame``.  On a TPU v5e this 1-D
    index compiles to a faster gather than a multi-dimensional one, which
    the gather unpacks; slicing the layer's pool out first would make XLA
    copy the whole pool before the gather reads it.

    An unmapped block (-1) reads frame ``(b * nb + i) % F``, which the
    attention masks out.  Sent all to frame 0, they read the same 4 KB
    [bt, hd] block of each head again and again: a qwen3_14b decode step
    with two thirds of its blocks unmapped took 14% longer on a TPU v5e."""
    K, F = stack.shape[-4:-2]
    filler = jnp.arange(frames.size).reshape(frames.shape) % F
    frames = jnp.where(frames >= 0, frames, filler)
    base = layer_idx
    if pool is not None:
        base = (layer_idx * stack.shape[1] + pool)[:, None, :]
    heads = jnp.arange(K)[None, :, None]
    flat = stack.reshape((-1,) + stack.shape[-2:])
    return flat[(base * K + heads) * F + frames[:, None, :]]


@jax.named_scope("kv_gather")
def update_gather_plain(k_slabs: jax.Array, v_slabs: jax.Array,
                        k_new: jax.Array, v_new: jax.Array,
                        phys_blocks: jax.Array, positions: jax.Array,
                        block_tokens: int, fused_scope: bool = False
                        ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Single-pool path.  k_slabs [K, F, bt, hd]; k_new [B, K, hd].
    Returns the written slabs and the gathered blocks [B, K, nb, bt, hd].
    fused_scope=True declares the update+gather VMEM-resident (it ships as
    the Pallas paged-attention kernel, which streams slabs per block)."""
    ctx = (jax.named_scope("vmem_paged_gather") if fused_scope
           else contextlib.nullcontext())
    with ctx:
        frame, slot = _token_frames(phys_blocks, positions, block_tokens,
                                    k_slabs.shape[1])
        k_slabs = _write_rows(k_slabs, k_new, frame, slot)
        v_slabs = _write_rows(v_slabs, v_new, frame, slot)
        return (k_slabs, v_slabs,
                _gather_layer(k_slabs[None], 0, phys_blocks),
                _gather_layer(v_slabs[None], 0, phys_blocks))


@jax.named_scope("kv_gather")
def gather_readonly(k_stack: jax.Array, v_stack: jax.Array,
                    layer_idx: jax.Array, phys_blocks: jax.Array,
                    fused_scope: bool = False
                    ) -> Tuple[jax.Array, jax.Array]:
    """Read-only gather of one layer's live blocks from the stacked cache:
    [B, K, nb, bt, hd] each.

    k_stack: [L, K, F, bt, hd] (or [L, P, K, F_local, bt, hd] pooled).
    Keeping the cache read-only inside the layer scan is what lets XLA
    alias the buffer through the loop — scan-carried *updated* slabs force
    a whole-layer copy per iteration (and a full-cache double buffer on
    some backends).  The new token's KV is appended to the attention
    outside (see attn_decode_paged_ro) and committed post-scan by
    commit_token_writes.

    Each branch is one gather on the stack itself (``_gather_layer``).
    """
    ctx = (jax.named_scope("vmem_paged_gather") if fused_scope
           else contextlib.nullcontext())
    pooled = k_stack.ndim == 6
    mesh = _mesh()
    rules = current_rules()
    data_ax = rules.lookup("blocks")
    with ctx:
        if not pooled:
            return (_gather_layer(k_stack, layer_idx, phys_blocks),
                    _gather_layer(v_stack, layer_idx, phys_blocks))
        if not _on_mesh(mesh, data_ax):
            pool = jnp.broadcast_to(
                _pool_of_rows(phys_blocks.shape[0], k_stack.shape[1])[:, None],
                phys_blocks.shape)
            return (_gather_layer(k_stack, layer_idx, phys_blocks, pool),
                    _gather_layer(v_stack, layer_idx, phys_blocks, pool))

        hd_ax = rules.lookup("head_dim")
        kv_ax = rules.lookup("kv_heads")
        stack_spec = P(None, data_ax, kv_ax, None, None, hd_ax)
        out_spec = P(data_ax, kv_ax, None, None, hd_ax)

        def local(ks, vs, g, li):
            return (_gather_layer(ks[:, 0], li, g),
                    _gather_layer(vs[:, 0], li, g))

        f = jax.shard_map(local, mesh=mesh,
                          in_specs=(stack_spec, stack_spec, P(data_ax, None),
                                    P()),
                          out_specs=(out_spec, out_spec), check_vma=False)
        return f(k_stack, v_stack, phys_blocks, layer_idx)


def _write_pools(write, k_stack: jax.Array, v_stack: jax.Array,
                 k: jax.Array, v: jax.Array, *per_row: jax.Array
                 ) -> Tuple[jax.Array, jax.Array]:
    """``write(stack, new, *per_row, pool)`` on the K and the V stack, each
    [L, (P,) K, F, bt, hd]; ``new`` is [L, B, ..., K, hd] and each of
    ``per_row`` has the batch axis first.  ``pool`` is None for a plain
    stack, each row's pool for a pooled one, and on a mesh each shard
    writes its own pool in shard_map."""
    if k_stack.ndim == 5:
        return (write(k_stack, k, *per_row, None),
                write(v_stack, v, *per_row, None))

    mesh = _mesh()
    rules = current_rules()
    data_ax = rules.lookup("blocks")
    if not _on_mesh(mesh, data_ax):
        pool = _pool_of_rows(k.shape[1], k_stack.shape[1])
        return (write(k_stack, k, *per_row, pool),
                write(v_stack, v, *per_row, pool))

    hd_ax = rules.lookup("head_dim")
    kv_ax = rules.lookup("kv_heads")
    stack_spec = P(None, data_ax, kv_ax, None, None, hd_ax)
    new_spec = P(None, data_ax, *(None,) * (k.ndim - 4), kv_ax, hd_ax)
    row_specs = tuple(P(data_ax, *(None,) * (a.ndim - 1)) for a in per_row)

    def local(ks, vs, kn, vn, *rows):
        pool = jnp.zeros(kn.shape[1], jnp.int32)   # this shard's one pool
        return (write(ks, kn, *rows, pool), write(vs, vn, *rows, pool))

    f = jax.shard_map(local, mesh=mesh,
                      in_specs=(stack_spec, stack_spec, new_spec, new_spec)
                      + row_specs,
                      out_specs=(stack_spec, stack_spec), check_vma=False)
    return f(k_stack, v_stack, k, v, *per_row)


@jax.named_scope("kv_commit")
def commit_token_writes(k_stack: jax.Array, v_stack: jax.Array,
                        k_new: jax.Array, v_new: jax.Array,
                        phys_blocks: jax.Array, positions: jax.Array,
                        block_tokens: int) -> Tuple[jax.Array, jax.Array]:
    """Write every layer's new-token KV into the stacked cache after the
    layer scan: one scatter per stack, of L * B * K single ``hd`` rows
    (``_write_rows``); rows whose block is unmapped are dropped.

    k_new/v_new: [L, B, K, hd] (collected scan outputs); positions [B];
    traffic is L*B*K*hd — the cache itself is aliased in place."""
    frame, slot = _token_frames(phys_blocks, positions, block_tokens,
                                k_stack.shape[-3])
    return _write_pools(_write_rows, k_stack, v_stack, k_new, v_new, frame,
                        slot)


@jax.named_scope("kv_scatter")
def scatter_prefill(k_stack: jax.Array, v_stack: jax.Array, k: jax.Array,
                    v: jax.Array, phys_blocks: jax.Array
                    ) -> Tuple[jax.Array, jax.Array]:
    """Write a prompt's KV, positions 0..S-1 of every row, for every layer
    into the stacked cache after the layer scan: one scatter per stack of
    whole [bt, hd] blocks (``_write_blocks``).  k/v [L, B, S, K, hd].
    Blocks that are unmapped (-1: inactive/padding rows) are dropped —
    never written to frame 0."""
    nb = -(-k.shape[2] // k_stack.shape[-2])
    frame = phys_blocks[:, :nb]
    frame = jnp.where(frame >= 0, frame, k_stack.shape[-3])
    return _write_pools(_write_blocks, k_stack, v_stack, k, v, frame)


@jax.named_scope("kv_gather")
def update_gather_pooled(k_slabs: jax.Array, v_slabs: jax.Array,
                         k_new: jax.Array, v_new: jax.Array,
                         phys_blocks: jax.Array, positions: jax.Array,
                         block_tokens: int, fused_scope: bool = False
                         ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Pool-partitioned path.  k_slabs [Pools, K, F_local, bt, hd];
    phys_blocks frame ids are LOCAL to each sequence's pool; the batch axis
    is sharded over 'data' in lockstep with the pool axis."""
    mesh = _mesh()
    rules = current_rules()
    data_ax = rules.lookup("blocks")  # pool axes
    if not _on_mesh(mesh, data_ax):
        # no mesh (smoke tests): each row reads and writes its own pool
        ctx = (jax.named_scope("vmem_paged_gather") if fused_scope
               else contextlib.nullcontext())
        with ctx:
            frame, slot = _token_frames(phys_blocks, positions, block_tokens,
                                        k_slabs.shape[2])
            pool = _pool_of_rows(frame.shape[0], k_slabs.shape[0])
            k_slabs = _write_rows(k_slabs, k_new, frame, slot, pool)
            v_slabs = _write_rows(v_slabs, v_new, frame, slot, pool)
            pools = jnp.broadcast_to(pool[:, None], phys_blocks.shape)
            return (k_slabs, v_slabs,
                    _gather_layer(k_slabs[None], 0, phys_blocks, pools),
                    _gather_layer(v_slabs[None], 0, phys_blocks, pools))

    hd_ax = rules.lookup("head_dim")
    kv_ax = rules.lookup("kv_heads")
    slab_spec = P(data_ax, kv_ax, None, None, hd_ax)
    new_spec = P(data_ax, kv_ax, hd_ax)
    tbl_spec = P(data_ax, None)

    def local(ks, vs, kn, vn, pb, pos):
        ks, vs = ks[0], vs[0]            # this shard's pool
        ks, vs, ka, va = update_gather_plain(ks, vs, kn, vn, pb, pos,
                                             block_tokens, fused_scope)
        return ks[None], vs[None], ka, va

    f = jax.shard_map(
        local, mesh=mesh,
        in_specs=(slab_spec, slab_spec, new_spec, new_spec, tbl_spec,
                  P(data_ax)),
        out_specs=(slab_spec, slab_spec,
                   P(data_ax, kv_ax, None, None, hd_ax),
                   P(data_ax, kv_ax, None, None, hd_ax)),
        check_vma=False)
    return f(k_slabs, v_slabs, k_new, v_new, phys_blocks, positions)


def decode_attention_sp(q: jax.Array, k_slabs: jax.Array, v_slabs: jax.Array,
                        k_new: jax.Array, v_new: jax.Array,
                        phys_blocks: jax.Array, positions: jax.Array,
                        seq_lens: jax.Array, *, block_tokens: int,
                        n_kv: int, window=None
                        ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Sequence-parallel paged decode attention (flash-decoding).

    For long-context decode where batch < data-axis size (long_500k): the
    block-table COLUMNS are sharded over 'data' — one sequence's KV blocks
    spread across shards, each shard owning the frames its columns point to
    (pool-local by construction).  Every shard computes a partial online
    softmax over its slice; partials combine with three scalar-sized
    collectives (max, sum, weighted-acc) instead of moving any KV.

    q: [B,H,hd]; k/v_slabs: [P, K, F_local, bt, hd]; phys_blocks: [B, MB]
    (frames local to the owning shard's pool); positions/seq_lens: [B].
    Returns (out [B,H,hd] f32, k_slabs, v_slabs).
    """
    mesh = _mesh()
    rules = current_rules()
    data_ax = rules.lookup("blocks")
    B, H, hd = q.shape
    G = H // n_kv
    scale = hd ** -0.5
    NEG = -2.0 ** 30
    bt = block_tokens
    qg = q.reshape(B, n_kv, G, hd)

    def scores_of(k_all, t, tables, pos, lens):
        """Scores [B,K,G,T] over gathered blocks [B,K,nb,bt,hd] at token
        indices ``t`` [T], and which of them a row may attend."""
        k_all = k_all.reshape(B, n_kv, -1, hd)
        s = jnp.einsum("bkgd,bktd->bkgt", qg, k_all,
                       preferred_element_type=jnp.float32) * scale
        ok = (t[None, :] < lens[:, None]) & jnp.repeat(tables >= 0, bt,
                                                        axis=1)
        if window is not None:
            ok &= (pos[:, None] - t[None, :]) < window
        return jnp.where(ok[:, None, None, :], s, NEG), ok

    def local(q, ks, vs, pb, pos, lens, shard_idx):
        # ks/vs: [K, F_local, bt, hd]; pb: [B, MB_local] columns of my slice
        MBl = pb.shape[1]
        col0 = shard_idx * MBl                    # my first global column
        # write the new token's KV if its block lives in my slice
        blk = pos // bt
        mine = (blk >= col0) & (blk < col0 + MBl)
        frame, slot = _token_frames(pb, pos - col0 * bt, bt, ks.shape[1])
        frame = jnp.where(mine, frame, ks.shape[1])
        ks = _write_rows(ks, k_new, frame, slot)
        vs = _write_rows(vs, v_new, frame, slot)
        # gather my slice and compute the partial softmax
        k_all = _gather_layer(ks[None], 0, pb)
        v_all = _gather_layer(vs[None], 0, pb).reshape(B, n_kv, -1, hd)
        with jax.named_scope("vmem_paged_attn_sp"):
            s, ok = scores_of(k_all, col0 * bt + jnp.arange(MBl * bt), pb,
                              pos, lens)
            m = jnp.max(s, axis=-1)                      # [B,K,G]
            p = jnp.exp(s - m[..., None])
            p = jnp.where(ok[:, None, None, :], p, 0.0)
            l = jnp.sum(p, axis=-1)
            acc = jnp.einsum("bkgt,bktd->bkgd", p,
                             v_all.astype(jnp.float32))
        # combine partials across shards
        from jax import lax
        gm = lax.pmax(m, data_ax)
        w = jnp.exp(m - gm)
        gl = lax.psum(l * w, data_ax)
        gacc = lax.psum(acc * w[..., None], data_ax)
        out = (gacc / jnp.maximum(gl, 1e-30)[..., None]).reshape(B, H, hd)
        return out, ks[None], vs[None]

    if not _on_mesh(mesh, data_ax):
        # single device: column c's frames live in pool c // (MB / P)
        n_pools, F = k_slabs.shape[0], k_slabs.shape[2]
        MB = phys_blocks.shape[1]
        col_pool = jnp.arange(MB) // (MB // n_pools)
        frame, slot = _token_frames(phys_blocks, positions, bt, F)
        pool = col_pool[jnp.clip(positions // bt, 0, MB - 1)]
        k_slabs = _write_rows(k_slabs, k_new, frame, slot, pool)
        v_slabs = _write_rows(v_slabs, v_new, frame, slot, pool)
        pools = jnp.broadcast_to(col_pool[None, :], phys_blocks.shape)
        k_all = _gather_layer(k_slabs[None], 0, phys_blocks, pools)
        v_all = _gather_layer(v_slabs[None], 0, phys_blocks, pools)
        s, _ = scores_of(k_all, jnp.arange(MB * bt), phys_blocks, positions,
                         seq_lens)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bkgt,bktd->bkgd", p.astype(v_all.dtype),
                         v_all.reshape(B, n_kv, -1, hd),
                         preferred_element_type=jnp.float32)
        return out.reshape(B, H, hd), k_slabs, v_slabs

    # SP layout: slabs replicated over 'model' (the per-device share comes
    # from the 'data' split of the sequence), q replicated — the partial
    # softmax combine is the only cross-shard traffic.
    slab_spec = P(data_ax, None, None, None, None)

    def wrapper(q, ks, vs, pb, pos, lens):
        from jax import lax
        shard_idx = lax.axis_index(data_ax)
        return local(q, ks[0], vs[0], pb, pos, lens, shard_idx)

    f = jax.shard_map(
        wrapper, mesh=mesh,
        in_specs=(P(), slab_spec, slab_spec, P(None, data_ax), P(), P()),
        out_specs=(P(), slab_spec, slab_spec),
        check_vma=False)
    return f(q, k_slabs, v_slabs, phys_blocks, positions, seq_lens)
