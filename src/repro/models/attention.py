"""GQA attention: training/prefill forward + paged / ring-buffer decode.

Covers every attention flavour in the assigned pool: grouped-query KV
(all), qk-norm (chameleon/gemma3/qwen3/qwen3-moe), sliding-window local
layers (gemma3/recurrentgemma), MHA (whisper), cross-attention (whisper
decoder).  Decode reads KV through the paged block-table substrate — the
physical frame ids given to ``attn_decode_paged`` come from
``repro.pagedpt.lookup_blocks``, i.e. every decode step performs the
paper's address translation.
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..distributed import constrain
from .common import KeyGen, ModelConfig, _dense, apply_rope, init_norm, rms_norm

NEG_INF = -2.0 ** 30


def init_attn(cfg: ModelConfig, keys: KeyGen, cross: bool = False
              ) -> Dict[str, jax.Array]:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    p = {
        "wq": _dense(keys(), (d, cfg.n_heads * hd), cfg.param_dtype),
        "wk": _dense(keys(), (d, cfg.n_kv_heads * hd), cfg.param_dtype),
        "wv": _dense(keys(), (d, cfg.n_kv_heads * hd), cfg.param_dtype),
        "wo": _dense(keys(), (cfg.n_heads * hd, d), cfg.param_dtype),
    }
    if cfg.qk_norm and not cross:
        p["q_norm"] = jnp.zeros((hd,), cfg.param_dtype)
        p["k_norm"] = jnp.zeros((hd,), cfg.param_dtype)
    return p


def _project_qkv(cfg: ModelConfig, p: Dict[str, jax.Array], xq: jax.Array,
                 xkv: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    B, Sq, _ = xq.shape
    Skv = xkv.shape[1]
    hd = cfg.resolved_head_dim
    q = (xq @ p["wq"].astype(cfg.dtype)).reshape(B, Sq, cfg.n_heads, hd)
    k = (xkv @ p["wk"].astype(cfg.dtype)).reshape(B, Skv, cfg.n_kv_heads, hd)
    v = (xkv @ p["wv"].astype(cfg.dtype)).reshape(B, Skv, cfg.n_kv_heads, hd)
    if cfg.qk_norm and "q_norm" in p:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    return q, k, v


@jax.named_scope("attn_qkv")
def _qkv_decode(cfg: ModelConfig, p: Dict[str, jax.Array], x: jax.Array,
                positions: jax.Array, rope_theta: float
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One decode token's q, k, v [B,1,*,hd], RoPE'd at ``positions``."""
    q, k, v = _project_qkv(cfg, p, x, x)
    if cfg.use_rope:
        q = apply_rope(q, positions[:, None], rope_theta)
        k = apply_rope(k, positions[:, None], rope_theta)
    return q, k, v


def _gqa_scores(cfg: ModelConfig, q: jax.Array, k: jax.Array) -> jax.Array:
    """q [B,Sq,H,hd], k [B,Sk,K,hd] -> scores [B,K,G,Sq,Sk]."""
    B, Sq, H, hd = q.shape
    K = cfg.n_kv_heads
    G = H // K
    qg = q.reshape(B, Sq, K, G, hd)
    # bf16 operands with f32 accumulation (MXU numerics): converting k to
    # f32 would let XLA hoist the convert over the KV gather and
    # materialize a full-precision copy of the whole cache
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k,
                        preferred_element_type=jnp.float32)
    scores *= hd ** -0.5
    if cfg.attn_logit_softcap:
        c = cfg.attn_logit_softcap
        scores = jnp.tanh(scores / c) * c
    return scores


def _gqa_out(cfg: ModelConfig, probs: jax.Array, v: jax.Array,
             p: Dict[str, jax.Array]) -> jax.Array:
    B, K, G, Sq, Sk = probs.shape
    hd = v.shape[-1]
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    out = out.reshape(B, Sq, K * G * hd).astype(cfg.dtype)
    return out @ p["wo"].astype(cfg.dtype)


def attn_forward(cfg: ModelConfig, p: Dict[str, jax.Array], x: jax.Array,
                 positions: jax.Array, *, window: Optional[int],
                 rope_theta: float, causal: bool = True,
                 kv_x: Optional[jax.Array] = None,
                 kv_positions: Optional[jax.Array] = None) -> jax.Array:
    """Training / prefill attention (full materialized scores).

    window: sliding-window size for local layers (None = full).
    kv_x: cross-attention source (whisper decoder); disables causal+rope
    on the kv side when positions are not given.
    """
    cross = kv_x is not None
    xkv = kv_x if cross else x
    with jax.named_scope("attn_qkv"):
        q, k, v = _project_qkv(cfg, p, x, xkv)
        q = constrain(q, "batch", "seq", "heads", None)
        k = constrain(k, "batch", "seq", "kv_heads", None)
        v = constrain(v, "batch", "seq", "kv_heads", None)
        if cfg.use_rope:
            q = apply_rope(q, positions, rope_theta)
            if not cross:
                k = apply_rope(k, kv_positions if kv_positions is not None
                               else positions, rope_theta)
    # The scores/softmax core ships as the Pallas flash kernel on TPU
    # (repro.kernels.flash_attention); the named scope declares its
    # intermediates VMEM-resident for the dry-run byte accounting.
    with jax.named_scope("attn"), jax.named_scope("vmem_attn"):
        scores = _gqa_scores(cfg, q, k)         # [B,K,G,Sq,Sk]
        q_pos = positions if positions.ndim == 2 else positions[None]
        k_pos = kv_positions if kv_positions is not None else positions
        k_pos = k_pos if k_pos.ndim == 2 else k_pos[None]
        if causal and not cross:
            # mask[b, q, k] = may q attend to k
            delta = q_pos[:, :, None] - k_pos[:, None, :]   # [B, Sq, Sk]
            mask = delta >= 0
            if window is not None:
                mask &= delta < window
            scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        out = _gqa_out(cfg, probs, v, p)
    return constrain(out, "batch", "act_seq", None)


def attn_decode_paged_ro(cfg: ModelConfig, p: Dict[str, jax.Array],
                         x: jax.Array, positions: jax.Array,
                         k_stack: jax.Array, v_stack: jax.Array,
                         layer_idx: jax.Array, phys_blocks: jax.Array,
                         seq_lens: jax.Array, *, rope_theta: float,
                         window: Optional[int] = None,
                         fused_scope: bool = False
                         ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Read-only paged decode: the cache is NOT mutated inside the layer
    scan (so the buffer aliases through the loop); the new token's KV is
    appended to the attention as an extra column and returned for a single
    post-scan commit (repro.kvcache.gather.commit_token_writes).

    Returns (attn_out [B,1,D], k_new [B,K,hd], v_new [B,K,hd]).
    """
    from ..kvcache.gather import gather_readonly
    B = x.shape[0]
    hd = cfg.resolved_head_dim
    K = cfg.n_kv_heads
    bt = k_stack.shape[-3]
    q, k_new, v_new = _qkv_decode(cfg, p, x, positions, rope_theta)
    k_all, v_all = gather_readonly(k_stack, v_stack, layer_idx, phys_blocks,
                                   fused_scope)
    nb = phys_blocks.shape[1]
    k_all = k_all.reshape(B, nb * bt, K, hd)
    v_all = v_all.reshape(B, nb * bt, K, hd)
    with jax.named_scope("attn"), jax.named_scope("vmem_paged_attn"):
        scores = _gqa_scores(cfg, q, k_all)           # [B,K,G,1,T]
        s_new = _gqa_scores(cfg, q, k_new)            # [B,K,G,1,1]
        t = jnp.arange(nb * bt)
        valid = t[None, :] < positions[:, None]       # strictly old tokens
        valid &= (phys_blocks >= 0).repeat(bt, axis=1)
        if window is not None:
            valid &= (positions[:, None] - t[None, :]) < window
        scores = jnp.where(valid[:, None, None, None, :], scores, NEG_INF)
        full = jnp.concatenate([scores, s_new], axis=-1)
        probs = jax.nn.softmax(full, axis=-1)
        p_old, p_new = probs[..., :-1], probs[..., -1:]
        out = jnp.einsum("bkgqs,bskd->bqkgd", p_old.astype(v_all.dtype),
                         v_all, preferred_element_type=jnp.float32)
        out = out + jnp.einsum("bkgqs,bskd->bqkgd",
                               p_new.astype(v_new.dtype), v_new,
                               preferred_element_type=jnp.float32)
        out = out.reshape(B, 1, cfg.n_heads * hd).astype(cfg.dtype)
        out = out @ p["wo"].astype(cfg.dtype)
    return (constrain(out, "batch", None, None), k_new[:, 0], v_new[:, 0])


class PagedKV(NamedTuple):
    """Paged KV slabs for one layer group (leading layer axis for scan)."""
    k: jax.Array   # [L, n_blocks, block_tokens, kv_heads, head_dim]
    v: jax.Array   # [L, n_blocks, block_tokens, kv_heads, head_dim]


def attn_decode_paged(cfg: ModelConfig, p: Dict[str, jax.Array],
                      x: jax.Array, positions: jax.Array,
                      kv: Tuple[jax.Array, jax.Array],
                      phys_blocks: jax.Array, seq_lens: jax.Array, *,
                      rope_theta: float, window: Optional[int] = None,
                      kernel: str = "ref", sp: bool = False
                      ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """One decode step (one new token per sequence) with paged KV.

    x: [B, 1, D]; positions: [B]; kv: (k_slabs, v_slabs) for THIS layer,
    each [n_blocks, bt, K, hd]; phys_blocks: [B, max_blocks] physical frame
    ids from the block-table translation (-1 = absent); seq_lens: [B]
    length INCLUDING the new token.
    Returns (attn_out [B,1,D], updated slabs).
    """
    from ..kvcache.gather import (decode_attention_sp, update_gather_plain,
                                  update_gather_pooled)
    B = x.shape[0]
    hd = cfg.resolved_head_dim
    K = cfg.n_kv_heads
    bt = kv[0].shape[-3]
    q, k_new, v_new = _qkv_decode(cfg, p, x, positions, rope_theta)
    if sp:
        # sequence-parallel long-context decode (flash-decoding combine)
        out, k_slabs, v_slabs = decode_attention_sp(
            q[:, 0], kv[0], kv[1], k_new[:, 0], v_new[:, 0], phys_blocks,
            positions, seq_lens, block_tokens=bt, n_kv=K, window=window)
        out = out.reshape(B, 1, cfg.n_heads * hd).astype(cfg.dtype)
        out = out @ p["wo"].astype(cfg.dtype)
        return constrain(out, "batch", None, None), (k_slabs, v_slabs)
    # ---- write new token's KV + gather live blocks (pool-local) --------------
    pooled = kv[0].ndim == 5
    fn = update_gather_pooled if pooled else update_gather_plain
    if kernel == "pallas" and not pooled:
        k_slabs, v_slabs, _, _ = fn(kv[0], kv[1], k_new[:, 0], v_new[:, 0],
                                    phys_blocks, positions, bt)
        from ..kernels.paged_attention import ops as pa_ops
        with jax.named_scope("attn"):
            out = pa_ops.paged_attention(q[:, 0], k_slabs, v_slabs,
                                         phys_blocks, seq_lens,
                                         window=window)
            out = out.reshape(B, 1, cfg.n_heads * hd).astype(cfg.dtype)
            out = out @ p["wo"].astype(cfg.dtype)
        return constrain(out, "batch", None, None), (k_slabs, v_slabs)

    # kernel == "fused_ref": the whole update+gather+softmax region is the
    # shipped Pallas paged-attention kernel (validated in tests/); declaring
    # it one fused VMEM region makes the dry-run byte accounting model the
    # kernel (slabs are STREAMED: per-block reads, no k_all materialization)
    import contextlib
    scope_all = jax.named_scope("vmem_paged_attn") if kernel == "fused_ref" \
        else contextlib.nullcontext()
    with scope_all:
        k_slabs, v_slabs, k_all, v_all = fn(kv[0], kv[1], k_new[:, 0],
                                            v_new[:, 0], phys_blocks,
                                            positions, bt,
                                            kernel == "fused_ref")
        nb = phys_blocks.shape[1]
        k_all = k_all.reshape(B, nb * bt, K, hd)
        v_all = v_all.reshape(B, nb * bt, K, hd)
        # scores/softmax ship as the Pallas paged-attention kernel on TPU
        with jax.named_scope("attn"), jax.named_scope("vmem_paged_attn"):
            scores = _gqa_scores(cfg, q, k_all)    # [B,K,G,1,T]
            t = jnp.arange(nb * bt)
            valid = (t[None, :] < seq_lens[:, None])
            valid &= (phys_blocks >= 0).repeat(bt, axis=1)
            if window is not None:
                valid &= (positions[:, None] - t[None, :]) < window
            scores = jnp.where(valid[:, None, None, None, :], scores, NEG_INF)
            probs = jax.nn.softmax(scores, axis=-1)
            out = _gqa_out(cfg, probs, v_all, p)   # [B,1,D]
    return constrain(out, "batch", None, None), (k_slabs, v_slabs)


def attn_decode_ring(cfg: ModelConfig, p: Dict[str, jax.Array], x: jax.Array,
                     positions: jax.Array, ring_k: jax.Array,
                     ring_v: jax.Array, *, rope_theta: float, window: int
                     ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Decode for sliding-window layers with a ring-buffer KV of size
    `window` per sequence.  ring_k/v: [B, window, K, hd]."""
    B = x.shape[0]
    hd = cfg.resolved_head_dim
    K = cfg.n_kv_heads
    q, k_new, v_new = _qkv_decode(cfg, p, x, positions, rope_theta)
    slot = positions % window
    ring_k = jax.vmap(lambda r, s, val: r.at[s].set(val))(
        ring_k, slot, k_new[:, 0].astype(ring_k.dtype))
    ring_v = jax.vmap(lambda r, s, val: r.at[s].set(val))(
        ring_v, slot, v_new[:, 0].astype(ring_v.dtype))
    scores = _gqa_scores(cfg, q, ring_k)       # [B,K,G,1,window]
    idx = jnp.arange(window)
    age = positions[:, None] - idx[None, :]    # ring slot i holds pos where pos%window==i
    # slot i currently holds position: largest pos' <= positions with pos'%window == i
    pos_in_slot = positions[:, None] - ((positions[:, None] - idx[None, :]) % window)
    valid = (pos_in_slot >= 0) & (pos_in_slot >= positions[:, None] - window + 1) \
        & (pos_in_slot <= positions[:, None])
    scores = jnp.where(valid[:, None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = _gqa_out(cfg, probs, ring_v, p)
    return constrain(out, "batch", None, None), ring_k, ring_v
