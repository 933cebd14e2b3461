"""Pool-partitioned KV slab update + gather (the numaPTE sharding).

The KV pool is partitioned per data shard — ``slabs [n_pools, F_local, bt,
K, hd]`` with the pool axis mapped to 'data' — and every sequence's frames
live in its own shard's pool.  This is the device-level mirror of the
paper's partitioned page tables (Section 3.3: each node owns the tables of
its own data, no cross-node traffic in the common case): the decode-step
gather is provably pool-local, so SPMD emits *zero* collectives for KV
reads, instead of the all-gather a flat sharded pool would force.

``update_gather_pooled`` runs under shard_map over ('data',) nested in the
jitted step; head_dim stays sharded over 'model' outside the map.

The device trace names this module's work by scope: ``kv_gather`` (the
read of a step's live blocks), ``kv_commit`` (the decode step's token
writes) and ``kv_scatter`` (the prefill's writes).
"""
from __future__ import annotations

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


@jax.named_scope("kv_gather")
def update_gather_plain(k_slabs: jax.Array, v_slabs: jax.Array,
                        k_new: jax.Array, v_new: jax.Array,
                        phys_blocks: jax.Array, positions: jax.Array,
                        block_tokens: int, fused_scope: bool = False
                        ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Single-pool path.  k_slabs [F, bt, K, hd]; k_new [B, K, hd].
    fused_scope=True declares the update+gather VMEM-resident (it ships as
    the Pallas paged-attention kernel, which streams slabs per block)."""
    import contextlib
    ctx = (jax.named_scope("vmem_paged_gather") if fused_scope
           else contextlib.nullcontext())
    with ctx:
        bt = block_tokens
        slot = positions % bt
        blk = jnp.clip(positions // bt, 0, phys_blocks.shape[1] - 1)
        frame = jnp.take_along_axis(phys_blocks, blk[:, None], axis=1)[:, 0]
        valid = frame >= 0
        frame = jnp.where(valid, frame, 0)
        # per-row dynamic_update_slice instead of a batched scatter: XLA
        # expands small scatters into whole-buffer gather+select rewrites,
        # which would bill (and on CPU, actually move) the entire cache
        # for a one-token write.  Rows whose current block is unmapped
        # (-1 tables: inactive/padding rows) write the slab's own bytes
        # back, so they can never corrupt frame 0.
        def write(slabs, args):
            f, s, val, ok = args
            val = jnp.where(ok, val.astype(slabs.dtype), slabs[f, s])
            return jax.lax.dynamic_update_slice(
                slabs, val[None, None],
                (f, s, jnp.zeros((), f.dtype), jnp.zeros((), f.dtype))), None

        k_slabs, _ = jax.lax.scan(write, k_slabs, (frame, slot, k_new, valid))
        v_slabs, _ = jax.lax.scan(write, v_slabs, (frame, slot, v_new, valid))
        gather = jnp.where(phys_blocks >= 0, phys_blocks, 0)
        return k_slabs, v_slabs, k_slabs[gather], v_slabs[gather]


def _gather_layer(stack: jax.Array, layer_idx: jax.Array,
                  frames: jax.Array) -> jax.Array:
    """``stack[layer_idx][frames]`` as one gather on the [L * F, ...] view
    of ``stack`` [L, F, ...], at flat rows ``layer_idx * F + frames``.  On
    a TPU v5e this 1-D index compiles to a faster gather than
    ``stack[layer_idx, frames]``, whose 2-D index the gather unpacks."""
    L, F = stack.shape[:2]
    return stack.reshape((L * F,) + stack.shape[2:])[layer_idx * F + frames]


@jax.named_scope("kv_gather")
def gather_readonly(k_stack: jax.Array, v_stack: jax.Array,
                    layer_idx: jax.Array, phys_blocks: jax.Array,
                    fused_scope: bool = False
                    ) -> Tuple[jax.Array, jax.Array]:
    """Read-only gather of one layer's live blocks from the stacked cache.

    k_stack: [L, F, bt, K, hd] (or [L, P, F_local, ...] pooled).  Keeping
    the cache read-only inside the layer scan is what lets XLA alias the
    buffer through the loop — scan-carried *updated* slabs force a
    whole-layer copy per iteration (and a full-cache double buffer on some
    backends).  The new token's KV is appended to the attention outside
    (see attn_decode_paged) and committed post-scan by commit_token_writes.

    Each branch is one gather on the stack itself (``_gather_layer``):
    slicing the layer's pool out first makes XLA copy the whole pool (in
    HBM at long contexts) before the gather reads it.
    """
    import contextlib
    ctx = (jax.named_scope("vmem_paged_gather") if fused_scope
           else contextlib.nullcontext())
    pooled = k_stack.ndim == 6
    mesh = _mesh()
    rules = current_rules()
    data_ax = rules.lookup("blocks")
    with ctx:
        if not pooled:
            gather = jnp.where(phys_blocks >= 0, phys_blocks, 0)
            return (_gather_layer(k_stack, layer_idx, gather),
                    _gather_layer(v_stack, layer_idx, gather))
        if not _on_mesh(mesh, data_ax):
            L, P_, F = k_stack.shape[:3]
            pool_of = jnp.arange(phys_blocks.shape[0]) // max(
                phys_blocks.shape[0] // P_, 1)
            glob = jnp.where(phys_blocks >= 0,
                             phys_blocks + pool_of[:, None] * F, 0)
            ks = k_stack.reshape((L, P_ * F) + k_stack.shape[3:])
            vs = v_stack.reshape((L, P_ * F) + v_stack.shape[3:])
            return (_gather_layer(ks, layer_idx, glob),
                    _gather_layer(vs, layer_idx, glob))

        hd_ax = rules.lookup("head_dim")
        kv_ax = rules.lookup("kv_heads")
        stack_spec = P(None, data_ax, None, None, kv_ax, hd_ax)
        out_spec = P(data_ax, None, None, kv_ax, hd_ax)

        def local(ks, vs, pb, li):
            g = jnp.where(pb >= 0, pb, 0)
            return (_gather_layer(ks[:, 0], li, g),
                    _gather_layer(vs[:, 0], li, g))

        f = jax.shard_map(local, mesh=mesh,
                          in_specs=(stack_spec, stack_spec, P(data_ax, None),
                                    P()),
                          out_specs=(out_spec, out_spec), check_vma=False)
        return f(k_stack, v_stack, phys_blocks, layer_idx)


def _commit_plain(k_stack, v_stack, k_new, v_new, frame, slot, valid=None):
    """k_stack [L,F,bt,K,hd]; k_new [L,B,K,hd]; one DUS per row writes its
    new token into every layer at once (the row's frame and slot are the
    same in all layers), so the loop runs B times, not L * B: each of its
    iterations is a handful of device operations.  ``valid`` [B] masks
    inactive (padding) rows into write-backs of the slab's own bytes, so
    unmapped rows never touch frame 0."""
    B = k_new.shape[1]
    if valid is None:
        valid = jnp.ones((B,), bool)
    zero = jnp.zeros((), frame.dtype)

    def write(stacks, args):
        ks, vs = stacks
        b, kv_, vv_ = args                       # kv_ [L, K, hd]
        idx = (zero, frame[b], slot[b], zero, zero)
        kv_ = jnp.where(valid[b], kv_.astype(ks.dtype),
                        ks[:, frame[b], slot[b]])
        vv_ = jnp.where(valid[b], vv_.astype(vs.dtype),
                        vs[:, frame[b], slot[b]])
        ks = jax.lax.dynamic_update_slice(ks, kv_[:, None, None], idx)
        vs = jax.lax.dynamic_update_slice(vs, vv_[:, None, None], idx)
        return (ks, vs), None

    (k_stack, v_stack), _ = jax.lax.scan(
        write, (k_stack, v_stack),
        (jnp.arange(B), jnp.swapaxes(k_new, 0, 1), jnp.swapaxes(v_new, 0, 1)))
    return k_stack, v_stack


@jax.named_scope("kv_commit")
def commit_token_writes(k_stack: jax.Array, v_stack: jax.Array,
                        k_new: jax.Array, v_new: jax.Array,
                        phys_blocks: jax.Array, positions: jax.Array,
                        block_tokens: int) -> Tuple[jax.Array, jax.Array]:
    """Write every layer's new-token KV into the stacked cache in one pass.

    k_new/v_new: [L, B, K, hd] (collected scan outputs); traffic is
    L*B*K*hd — the cache itself is aliased in place."""
    L, B = k_new.shape[:2]
    bt = block_tokens
    slot = positions % bt
    blk = jnp.clip(positions // bt, 0, phys_blocks.shape[1] - 1)
    frame = jnp.take_along_axis(phys_blocks, blk[:, None], axis=1)[:, 0]
    valid = frame >= 0
    frame = jnp.where(valid, frame, 0)
    pooled = k_stack.ndim == 6
    if not pooled:
        return _commit_plain(k_stack, v_stack, k_new, v_new, frame, slot,
                             valid)

    mesh = _mesh()
    rules = current_rules()
    data_ax = rules.lookup("blocks")
    if not _on_mesh(mesh, data_ax):
        P_, F = k_stack.shape[1:3]
        pool_of = jnp.arange(B) // max(B // P_, 1)
        gframe = frame + pool_of * F
        ks = k_stack.reshape((L, P_ * F) + k_stack.shape[3:])
        vs = v_stack.reshape((L, P_ * F) + v_stack.shape[3:])
        ks, vs = _commit_plain(ks, vs, k_new, v_new, gframe, slot, valid)
        return ks.reshape(k_stack.shape), vs.reshape(v_stack.shape)

    hd_ax = rules.lookup("head_dim")
    kv_ax = rules.lookup("kv_heads")
    stack_spec = P(None, data_ax, None, None, kv_ax, hd_ax)
    new_spec = P(None, data_ax, kv_ax, hd_ax)

    def local(ks, vs, kn, vn, fr, sl, ok):
        ks2 = ks[:, 0]
        vs2 = vs[:, 0]
        ks2, vs2 = _commit_plain(ks2, vs2, kn, vn, fr, sl, ok)
        return ks2[:, None], vs2[:, None]

    f = jax.shard_map(local, mesh=mesh,
                      in_specs=(stack_spec, stack_spec, new_spec, new_spec,
                                P(data_ax), P(data_ax), P(data_ax)),
                      out_specs=(stack_spec, stack_spec), check_vma=False)
    return f(k_stack, v_stack, k_new, v_new, frame, slot, valid)


@jax.named_scope("kv_gather")
def update_gather_pooled(k_slabs: jax.Array, v_slabs: jax.Array,
                         k_new: jax.Array, v_new: jax.Array,
                         phys_blocks: jax.Array, positions: jax.Array,
                         block_tokens: int, fused_scope: bool = False
                         ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Pool-partitioned path.  k_slabs [Pools, F_local, bt, K, hd];
    phys_blocks frame ids are LOCAL to each sequence's pool; the batch axis
    is sharded over 'data' in lockstep with the pool axis."""
    mesh = _mesh()
    rules = current_rules()
    data_ax = rules.lookup("blocks")  # pool axes
    if not _on_mesh(mesh, data_ax):
        # no mesh (smoke tests): collapse pools and run the plain path
        P_, F = k_slabs.shape[:2]
        pool_of = jnp.arange(phys_blocks.shape[0]) // max(
            phys_blocks.shape[0] // P_, 1)
        glob = jnp.where(phys_blocks >= 0,
                         phys_blocks + pool_of[:, None] * F, -1)
        kf = k_slabs.reshape((P_ * F,) + k_slabs.shape[2:])
        vf = v_slabs.reshape((P_ * F,) + v_slabs.shape[2:])
        kf, vf, ka, va = update_gather_plain(kf, vf, k_new, v_new, glob,
                                             positions, block_tokens,
                                             fused_scope)
        return (kf.reshape(k_slabs.shape), vf.reshape(v_slabs.shape), ka, va)

    hd_ax = rules.lookup("head_dim")
    kv_ax = rules.lookup("kv_heads")
    slab_spec = P(data_ax, None, None, kv_ax, hd_ax)
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
                   P(data_ax, None, None, kv_ax, hd_ax),
                   P(data_ax, None, None, kv_ax, hd_ax)),
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

    q: [B,H,hd]; k/v_slabs: [P, F_local, bt, K, hd]; phys_blocks: [B, MB]
    (frames local to the owning shard's pool); positions/seq_lens: [B].
    Returns (out [B,H,hd] f32, k_slabs, v_slabs).
    """
    mesh = _mesh()
    rules = current_rules()
    data_ax = rules.lookup("blocks")
    hd_ax = rules.lookup("head_dim")
    kv_ax = rules.lookup("kv_heads")
    B, H, hd = q.shape
    G = H // n_kv
    scale = hd ** -0.5
    NEG = -2.0 ** 30

    def local(q, ks, vs, pb, pos, lens, shard_idx):
        # ks/vs: [F_local, bt, K, hd]; pb: [B, MB_local] columns of my slice
        bt = block_tokens
        MBl = pb.shape[1]
        col0 = shard_idx * MBl                    # my first global column
        # write the new token's KV if its block lives in my slice
        blk = pos // bt
        slot = pos % bt
        mine = (blk >= col0) & (blk < col0 + MBl)
        local_col = jnp.clip(blk - col0, 0, MBl - 1)
        frame = jnp.take_along_axis(pb, local_col[:, None], axis=1)[:, 0]
        frame_w = jnp.where(mine & (frame >= 0), frame, 0)
        k_upd = jnp.where(mine[:, None, None], k_new.astype(ks.dtype),
                          ks[frame_w, slot])
        v_upd = jnp.where(mine[:, None, None], v_new.astype(vs.dtype),
                          vs[frame_w, slot])
        ks = ks.at[frame_w, slot].set(k_upd)
        vs = vs.at[frame_w, slot].set(v_upd)
        # gather my slice and compute the partial softmax
        gather = jnp.where(pb >= 0, pb, 0)
        k_all = ks[gather].reshape(B, MBl * bt, n_kv, hd)
        v_all = vs[gather].reshape(B, MBl * bt, n_kv, hd)
        qg = q.reshape(B, n_kv, G, hd)
        with jax.named_scope("vmem_paged_attn_sp"):
            s = jnp.einsum("bkgd,btkd->bkgt", qg, k_all,
                           preferred_element_type=jnp.float32) * scale
            t = col0 * bt + jnp.arange(MBl * bt)
            ok = (t[None, :] < lens[:, None]) & jnp.repeat(pb >= 0, bt, axis=1)
            if window is not None:
                ok &= (pos[:, None] - t[None, :]) < window
            s = jnp.where(ok[:, None, None, :], s, NEG)
            m = jnp.max(s, axis=-1)                      # [B,K,G]
            p = jnp.exp(s - m[..., None])
            p = jnp.where(ok[:, None, None, :], p, 0.0)
            l = jnp.sum(p, axis=-1)
            acc = jnp.einsum("bkgt,btkd->bkgd", p,
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
        # single-device fallback: flatten pools and reuse the plain path
        P_, F = k_slabs.shape[:2]
        MB = phys_blocks.shape[1]
        MBl = MB // P_
        col_shard = jnp.arange(MB) // MBl
        glob = jnp.where(phys_blocks >= 0,
                         phys_blocks + col_shard[None, :] * F, -1)
        kf = k_slabs.reshape((P_ * F,) + k_slabs.shape[2:])
        vf = v_slabs.reshape((P_ * F,) + v_slabs.shape[2:])
        kf, vf, k_all, v_all = update_gather_plain(
            kf, vf, k_new, v_new, glob, positions, block_tokens)
        bt = block_tokens
        k_all = k_all.reshape(B, MB * bt, n_kv, hd)
        v_all = v_all.reshape(B, MB * bt, n_kv, hd)
        qg = q.reshape(B, n_kv, G, hd)
        s = jnp.einsum("bkgd,btkd->bkgt", qg, k_all,
                       preferred_element_type=jnp.float32) * scale
        t = jnp.arange(MB * bt)
        ok = (t[None, :] < seq_lens[:, None]) & jnp.repeat(
            phys_blocks >= 0, bt, axis=1)
        if window is not None:
            ok &= (positions[:, None] - t[None, :]) < window
        s = jnp.where(ok[:, None, None, :], s, NEG)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bkgt,btkd->bkgd", p.astype(v_all.dtype), v_all,
                         preferred_element_type=jnp.float32).reshape(B, H, hd)
        return out, kf.reshape(k_slabs.shape), vf.reshape(v_slabs.shape)

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


@jax.named_scope("kv_scatter")
def scatter_prefill_plain(k_slabs: jax.Array, v_slabs: jax.Array,
                          k: jax.Array, v: jax.Array,
                          phys_blocks: jax.Array, positions: jax.Array,
                          block_tokens: int) -> Tuple[jax.Array, jax.Array]:
    """Scatter a full prompt's KV into slabs.  k [B,S,K,hd]; positions
    [B,S].  Tokens whose block is unmapped (-1: inactive/padding rows) are
    redirected out of bounds, which JAX scatter drops — never frame 0."""
    B, S = positions.shape
    bt = block_tokens
    blk = jnp.clip(positions // bt, 0, phys_blocks.shape[1] - 1)
    frame = jnp.take_along_axis(phys_blocks, blk, axis=1)
    frame = jnp.where(frame >= 0, frame, k_slabs.shape[0])
    slot = positions % bt
    k_slabs = k_slabs.at[frame.reshape(-1), slot.reshape(-1)].set(
        k.reshape((B * S,) + k.shape[2:]).astype(k_slabs.dtype),
        mode="drop")
    v_slabs = v_slabs.at[frame.reshape(-1), slot.reshape(-1)].set(
        v.reshape((B * S,) + v.shape[2:]).astype(v_slabs.dtype),
        mode="drop")
    return k_slabs, v_slabs


@jax.named_scope("kv_scatter")
def scatter_prefill_pooled(k_slabs: jax.Array, v_slabs: jax.Array,
                           k: jax.Array, v: jax.Array,
                           phys_blocks: jax.Array, positions: jax.Array,
                           block_tokens: int) -> Tuple[jax.Array, jax.Array]:
    """Pool-partitioned prefill scatter (frames local to each pool)."""
    mesh = _mesh()
    rules = current_rules()
    data_ax = rules.lookup("blocks")
    if not _on_mesh(mesh, data_ax):
        P_, F = k_slabs.shape[:2]
        pool_of = jnp.arange(phys_blocks.shape[0]) // max(
            phys_blocks.shape[0] // P_, 1)
        glob = jnp.where(phys_blocks >= 0,
                         phys_blocks + pool_of[:, None] * F, -1)
        kf = k_slabs.reshape((P_ * F,) + k_slabs.shape[2:])
        vf = v_slabs.reshape((P_ * F,) + v_slabs.shape[2:])
        kf, vf = scatter_prefill_plain(kf, vf, k, v, glob, positions,
                                       block_tokens)
        return kf.reshape(k_slabs.shape), vf.reshape(v_slabs.shape)

    hd_ax = rules.lookup("head_dim")
    kv_ax = rules.lookup("kv_heads")
    slab_spec = P(data_ax, None, None, kv_ax, hd_ax)
    kv_spec = P(data_ax, None, kv_ax, hd_ax)

    def local(ks, vs, kn, vn, pb, pos):
        ks, vs = scatter_prefill_plain(ks[0], vs[0], kn, vn, pb, pos,
                                       block_tokens)
        return ks[None], vs[None]

    f = jax.shard_map(local, mesh=mesh,
                      in_specs=(slab_spec, slab_spec, kv_spec, kv_spec,
                                P(data_ax, None), P(data_ax, None)),
                      out_specs=(slab_spec, slab_spec),
                      check_vma=False)
    return f(k_slabs, v_slabs, k, v, phys_blocks, positions)
