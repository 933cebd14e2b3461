"""Model assembly: decoder-only LMs (dense/MoE/SSM/hybrid/VLM backbone) and
the Whisper-style encoder-decoder, built from layer groups.

Each layer group runs as one ``lax.scan`` over stacked parameters (HLO size
stays O(kinds), compile time stays sane at 94 layers), with optional
per-layer rematerialization for training memory.

Decode state:
  * global-attention groups — paged KV slabs indexed by *physical* frame ids
    coming from the numaPTE block-table translation (repro.pagedpt);
  * local-window groups — ring buffers of size `window`;
  * SSD / RG-LRU groups — O(1) recurrent states (+ conv tails).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..distributed import constrain
from .attention import (attn_decode_paged, attn_decode_ring, attn_forward,
                        init_attn)
from .common import (KeyGen, LayerGroup, ModelConfig, _dense, apply_norm,
                     init_norm, layer_groups, stack_layer_params)
from .ffn import ffn_forward, init_ffn
from .moe import EXPERT_WEIGHTS, init_moe, moe_forward, moe_serve
from .rglru import init_rglru, rglru_decode, rglru_forward
from .ssm import init_ssd, ssd_decode, ssd_forward

PyTree = Any


# --------------------------------------------------------------------------- init
def _init_layer(cfg: ModelConfig, keys: KeyGen, group: LayerGroup) -> PyTree:
    p: Dict[str, PyTree] = {"norm1": init_norm(cfg, cfg.d_model)}
    if group.kind in ("attn", "enc_attn", "dec_attn"):
        p["attn"] = init_attn(cfg, keys)
        p["norm2"] = init_norm(cfg, cfg.d_model)
        if group.kind == "dec_attn":
            p["cross"] = init_attn(cfg, keys, cross=True)
            p["norm_cross"] = init_norm(cfg, cfg.d_model)
        p["moe" if group.moe else "ffn"] = (
            init_moe(cfg, keys) if group.moe else init_ffn(cfg, keys))
    elif group.kind == "rglru":
        p["rglru"] = init_rglru(cfg, keys)
        p["norm2"] = init_norm(cfg, cfg.d_model)
        p["ffn"] = init_ffn(cfg, keys)
    elif group.kind == "ssd":
        p["ssd"] = init_ssd(cfg, keys)
    else:
        raise ValueError(group.kind)
    return p


def init_params(cfg: ModelConfig, key: jax.Array) -> PyTree:
    keys = KeyGen(key)
    groups = layer_groups(cfg)
    params: Dict[str, PyTree] = {
        "groups": [stack_layer_params(
            [_init_layer(cfg, keys, g) for _ in range(g.n_layers)])
            for g in groups],
        "final_norm": init_norm(cfg, cfg.d_model),
    }
    if cfg.family == "encdec":
        params["dec_pos"] = _dense(keys(), (cfg.max_decoder_len, cfg.d_model),
                                   cfg.param_dtype, scale=0.02)
        params["dec_embedding"] = _dense(
            keys(), (cfg.vocab_size, cfg.d_model), cfg.param_dtype)
        params["enc_norm"] = init_norm(cfg, cfg.d_model)
    else:
        params["embedding"] = _dense(keys(), (cfg.vocab_size, cfg.d_model),
                                     cfg.param_dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = _dense(keys(), (cfg.d_model, cfg.vocab_size),
                                   cfg.param_dtype)
    return params


def param_count(cfg: ModelConfig) -> int:
    """Parameters of the published model, whatever share of its experts
    ``cfg`` holds."""
    import math
    cfg = dataclasses.replace(cfg, experts_held=None, first_held_expert=0)
    shapes = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    return sum(math.prod(l.shape) for l in jax.tree.leaves(shapes))


def active_param_count(cfg: ModelConfig) -> int:
    """Params touched per token (MoE: top-k experts only)."""
    total = param_count(cfg)
    if cfg.n_experts == 0:
        return total
    moe_layers = cfg.n_layers - cfg.first_dense_layers
    per_expert = cfg.d_model * cfg.moe_d_ff * (3 if cfg.ffn_act in ("silu", "geglu") else 2)
    inactive = moe_layers * (cfg.n_experts - cfg.experts_per_token) * per_expert
    return total - inactive


# --------------------------------------------------------------------------- fwd
def _attn_block(cfg: ModelConfig, group: LayerGroup, lp: PyTree, x: jax.Array,
                positions: jax.Array, causal: bool,
                kv_x: Optional[jax.Array] = None) -> Tuple[jax.Array, jax.Array]:
    h = apply_norm(cfg, x, lp["norm1"])
    a = attn_forward(cfg, lp["attn"], h, positions, window=group.window,
                     rope_theta=group.rope_theta, causal=causal)
    x = x + a
    if "cross" in lp and kv_x is not None:
        h = apply_norm(cfg, x, lp["norm_cross"])
        a = attn_forward(cfg, lp["cross"], h, positions, window=None,
                         rope_theta=group.rope_theta, causal=False, kv_x=kv_x)
        x = x + a
    h = apply_norm(cfg, x, lp["norm2"])
    aux = jnp.zeros((), jnp.float32)
    if group.moe:
        f, aux = moe_forward(cfg, lp["moe"], h)
    else:
        f = ffn_forward(cfg, lp["ffn"], h)
    return x + f, aux


def _layer_fwd(cfg: ModelConfig, group: LayerGroup, lp: PyTree, x: jax.Array,
               positions: jax.Array, kv_x: Optional[jax.Array] = None
               ) -> Tuple[jax.Array, jax.Array]:
    if group.kind in ("attn", "dec_attn"):
        return _attn_block(cfg, group, lp, x, positions, causal=True, kv_x=kv_x)
    if group.kind == "enc_attn":
        return _attn_block(cfg, group, lp, x, positions, causal=False)
    if group.kind == "rglru":
        h = apply_norm(cfg, x, lp["norm1"])
        x = x + rglru_forward(cfg, lp["rglru"], h)
        h = apply_norm(cfg, x, lp["norm2"])
        return x + ffn_forward(cfg, lp["ffn"], h), jnp.zeros((), jnp.float32)
    if group.kind == "ssd":
        h = apply_norm(cfg, x, lp["norm1"])
        return x + ssd_forward(cfg, lp["ssd"], h), jnp.zeros((), jnp.float32)
    raise ValueError(group.kind)


def _remat_policy(name: str):
    return {"full": jax.checkpoint_policies.nothing_saveable,
            "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            }[name]


def _run_groups(cfg: ModelConfig, params: PyTree, x: jax.Array,
                positions: jax.Array, groups: List[LayerGroup],
                group_params: List[PyTree], remat,
                kv_x: Optional[jax.Array] = None) -> Tuple[jax.Array, jax.Array]:
    aux_total = jnp.zeros((), jnp.float32)
    for g, gp in zip(groups, group_params):
        fwd = functools.partial(_layer_fwd, cfg, g, kv_x=kv_x)
        if remat:
            policy = _remat_policy(remat if isinstance(remat, str) else "full")
            fwd = jax.checkpoint(fwd, policy=policy)

        def body(carry, lp, fwd=fwd):
            x, aux = carry
            x, a = fwd(lp, x, positions)
            return (x, aux + a), None

        (x, aux_total), _ = jax.lax.scan(body, (x, aux_total), gp)
    return x, aux_total


def forward_lm(cfg: ModelConfig, params: PyTree, tokens: jax.Array,
               *, remat: bool = True) -> Tuple[jax.Array, jax.Array]:
    """Decoder-only LM forward.  tokens: [B,S] int32 -> logits [B,S,V]."""
    B, S = tokens.shape
    x = params["embedding"].astype(cfg.dtype)[tokens]
    x = x * jnp.asarray(cfg.d_model ** 0.5, cfg.dtype)   # gemma-style scale
    x = constrain(x, "batch", "seq", None)
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    x, aux = _run_groups(cfg, params, x, positions, layer_groups(cfg),
                         params["groups"], remat)
    x = apply_norm(cfg, x, params["final_norm"])
    head = params.get("lm_head", params["embedding"].T)
    logits = x @ head.astype(cfg.dtype)
    return constrain(logits, "batch", "seq", "vocab"), aux


def forward_encdec(cfg: ModelConfig, params: PyTree, enc_feats: jax.Array,
                   dec_tokens: jax.Array, *, remat: bool = True
                   ) -> Tuple[jax.Array, jax.Array]:
    """Whisper-style: enc_feats [B,Se,D] (frontend stub), dec_tokens [B,Sd]."""
    B, Se, _ = enc_feats.shape
    Sd = dec_tokens.shape[1]
    enc_g, dec_g = layer_groups(cfg)
    enc_pos = jnp.broadcast_to(jnp.arange(Se)[None, :], (B, Se))
    x = enc_feats.astype(cfg.dtype) + _sinusoids(Se, cfg.d_model)[None]
    x, _ = _run_groups(cfg, params, x, enc_pos, [enc_g],
                       [params["groups"][0]], remat)
    enc_out = apply_norm(cfg, x, params["enc_norm"])

    y = params["dec_embedding"].astype(cfg.dtype)[dec_tokens]
    y = y + params["dec_pos"].astype(cfg.dtype)[:Sd][None]
    dec_pos = jnp.broadcast_to(jnp.arange(Sd)[None, :], (B, Sd))
    y, aux = _run_groups(cfg, params, y, dec_pos, [dec_g],
                         [params["groups"][1]], remat, kv_x=enc_out)
    y = apply_norm(cfg, y, params["final_norm"])
    head = params.get("lm_head", params["dec_embedding"].T)
    logits = y @ head.astype(cfg.dtype)
    return constrain(logits, "batch", "seq", "vocab"), aux


def _sinusoids(length: int, channels: int) -> jax.Array:
    log_timescale = jnp.log(10_000.0) / (channels // 2 - 1)
    inv = jnp.exp(-log_timescale * jnp.arange(channels // 2))
    scaled = jnp.arange(length)[:, None].astype(jnp.float32) * inv[None]
    return jnp.concatenate([jnp.sin(scaled), jnp.cos(scaled)], axis=1)


def lm_loss(cfg: ModelConfig, params: PyTree, batch: Dict[str, jax.Array],
            *, remat: bool = True) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Next-token cross-entropy (+ MoE aux).  batch: tokens [B,S+1] or
    {'enc_feats','tokens'} for encdec."""
    if cfg.family == "encdec":
        logits, aux = forward_encdec(cfg, params, batch["enc_feats"],
                                     batch["tokens"][:, :-1], remat=remat)
    else:
        logits, aux = forward_lm(cfg, params, batch["tokens"][:, :-1],
                                 remat=remat)
    targets = batch["tokens"][:, 1:]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    mask = batch.get("mask")
    if mask is not None:
        mask = mask[:, 1:]
        loss = -jnp.sum(ll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    else:
        loss = -jnp.mean(ll)
    total = loss + 0.01 * aux
    return total, {"loss": loss, "aux": aux,
                   "tokens": jnp.asarray(targets.size, jnp.float32)}


# --------------------------------------------------------------------------- decode
class DecodeState(NamedTuple):
    """Per-group caches (tuple indexed like layer_groups(cfg))."""
    caches: Tuple[Dict[str, jax.Array], ...]
    seq_lens: jax.Array           # [B] tokens generated so far (incl. prompt)


def init_decode_state(cfg: ModelConfig, batch: int, n_blocks: int,
                      max_blocks: int, *, enc_len: int = 0, n_pools: int = 1,
                      dtype=None) -> DecodeState:
    """n_blocks: physical KV frames in the pool; max_blocks: per-seq table.
    n_pools > 1 partitions the pool per data shard (numaPTE sharding)."""
    dtype = dtype or cfg.dtype
    hd, K = cfg.resolved_head_dim, cfg.n_kv_heads
    bt = cfg.kv_block_tokens
    slab_dims = ((n_pools, n_blocks // n_pools) if n_pools > 1
                 else (n_blocks,))
    caches: List[Dict[str, jax.Array]] = []
    for g in layer_groups(cfg):
        L = g.n_layers
        if g.kind in ("attn", "dec_attn") and g.window is None:
            c = {"k_slabs": jnp.zeros((L,) + slab_dims + (bt, K, hd), dtype),
                 "v_slabs": jnp.zeros((L,) + slab_dims + (bt, K, hd), dtype)}
            if g.kind == "dec_attn":
                c["cross_k"] = jnp.zeros((L, batch, enc_len, K, hd), dtype)
                c["cross_v"] = jnp.zeros((L, batch, enc_len, K, hd), dtype)
            caches.append(c)
        elif g.kind == "attn":   # local window ring
            caches.append(
                {"ring_k": jnp.zeros((L, batch, g.window, K, hd), dtype),
                 "ring_v": jnp.zeros((L, batch, g.window, K, hd), dtype)})
        elif g.kind == "rglru":
            w = cfg.lru_width or cfg.d_model
            caches.append(
                {"h": jnp.zeros((L, batch, w), jnp.float32),
                 "conv": jnp.zeros((L, batch, cfg.conv_width - 1, w), dtype)})
        elif g.kind == "ssd":
            conv_ch = cfg.d_inner + 2 * cfg.ssm_state
            caches.append(
                {"h": jnp.zeros((L, batch, cfg.ssm_n_heads, cfg.ssm_state,
                                 cfg.ssm_head_dim), jnp.float32),
                 "conv": jnp.zeros((L, batch, cfg.conv_width - 1, conv_ch),
                                   dtype)})
        elif g.kind == "enc_attn":
            caches.append({})      # encoder has no decode state
        else:
            raise ValueError(g.kind)
    return DecodeState(tuple(caches),
                       jnp.zeros((batch,), jnp.int32))


def decode_step(cfg: ModelConfig, params: PyTree, state: DecodeState,
                tokens: jax.Array, phys_blocks: jax.Array, *,
                kernel: str = "ref", sp: bool = False
                ) -> Tuple[jax.Array, DecodeState]:
    """One token per sequence.  tokens: [B]; phys_blocks: [B, max_blocks]
    physical frame ids from the numaPTE block-table translation."""
    positions = state.seq_lens                       # position of new token
    with jax.named_scope("embed"):
        if cfg.family == "encdec":
            x = params["dec_embedding"].astype(cfg.dtype)[tokens][:, None]
            pos_emb = params["dec_pos"].astype(cfg.dtype)[
                jnp.clip(positions, 0, cfg.max_decoder_len - 1)]
            x = x + pos_emb[:, None]
        else:
            x = params["embedding"].astype(cfg.dtype)[tokens][:, None]
            x = x * jnp.asarray(cfg.d_model ** 0.5, cfg.dtype)
    groups = layer_groups(cfg)
    new_caches: List[Dict[str, jax.Array]] = []
    seq_lens = state.seq_lens + 1
    for g, gp, cache in zip(groups, params["groups"], state.caches):
        if g.kind == "enc_attn":
            new_caches.append(cache)
            continue
        x, cache = _decode_group(cfg, g, gp, cache, x, positions,
                                 phys_blocks, seq_lens, kernel, sp)
        new_caches.append(cache)
    with jax.named_scope("lm_head"):
        x = apply_norm(cfg, x, params["final_norm"])
        head = params.get(
            "lm_head",
            (params["dec_embedding"] if cfg.family == "encdec"
             else params["embedding"]).T)
        logits = (x @ head.astype(cfg.dtype))[:, 0]
    return logits, DecodeState(tuple(new_caches), seq_lens)


def _decode_group(cfg: ModelConfig, g: LayerGroup, gp: PyTree,
                  cache: Dict[str, jax.Array], x: jax.Array,
                  positions: jax.Array, phys_blocks: jax.Array,
                  seq_lens: jax.Array, kernel: str, sp: bool = False
                  ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    if g.kind in ("attn", "dec_attn") and g.window is None:
        if kernel in ("ref", "fused_ref") and not sp:
            # read-only cache inside the scan + one post-scan token commit:
            # the cache buffer aliases through the loop instead of paying a
            # whole-layer copy per iteration (see kvcache.gather)
            from ..kvcache.gather import commit_token_writes
            from .attention import attn_decode_paged_ro
            k_stack, v_stack = cache["k_slabs"], cache["v_slabs"]
            gp_scan, experts = _experts_out_of_scan(g, gp)

            def body(x, xs):
                lp, li, *cross = xs
                with jax.named_scope("attn_qkv"):
                    h = apply_norm(cfg, x, lp["norm1"])
                a, kn, vn = attn_decode_paged_ro(
                    cfg, lp["attn"], h, positions, k_stack, v_stack, li,
                    phys_blocks, seq_lens, rope_theta=g.rope_theta,
                    fused_scope=(kernel == "fused_ref"))
                with jax.named_scope("attn"):
                    x = x + a
                if cross:
                    ck, cv = cross
                    h = apply_norm(cfg, x, lp["norm_cross"])
                    a = _cross_decode(cfg, lp["cross"], h, ck, cv)
                    x = x + a
                return _ffn_block(cfg, g, lp, x, experts, li), (kn, vn)

            L = jax.tree.leaves(gp)[0].shape[0]
            xs = (gp_scan, jnp.arange(L))
            if g.kind == "dec_attn":
                xs = xs + (cache["cross_k"], cache["cross_v"])
            x, (k_new, v_new) = jax.lax.scan(body, x, xs)
            ks, vs = commit_token_writes(
                k_stack, v_stack, k_new, v_new, phys_blocks, positions,
                cfg.kv_block_tokens)
            cache = dict(cache, k_slabs=ks, v_slabs=vs)
            return x, cache

        def body(x, xs):
            lp, ks, vs, *cross = xs
            with jax.named_scope("attn_qkv"):
                h = apply_norm(cfg, x, lp["norm1"])
            a, (ks, vs) = attn_decode_paged(
                cfg, lp["attn"], h, positions, (ks, vs), phys_blocks,
                seq_lens, rope_theta=g.rope_theta, kernel=kernel, sp=sp)
            with jax.named_scope("attn"):
                x = x + a
            if cross:
                ck, cv = cross
                h = apply_norm(cfg, x, lp["norm_cross"])
                a = _cross_decode(cfg, lp["cross"], h, ck, cv)
                x = x + a
            return _ffn_block(cfg, g, lp, x), (ks, vs)

        xs = (gp, cache["k_slabs"], cache["v_slabs"])
        if g.kind == "dec_attn":
            xs = xs + (cache["cross_k"], cache["cross_v"])
        x, (ks, vs) = jax.lax.scan(body, x, xs)
        cache = dict(cache, k_slabs=ks, v_slabs=vs)
        return x, cache
    if g.kind == "attn":   # ring
        def body(x, xs):
            lp, rk, rv = xs
            h = apply_norm(cfg, x, lp["norm1"])
            a, rk, rv = attn_decode_ring(cfg, lp["attn"], h, positions, rk,
                                         rv, rope_theta=g.rope_theta,
                                         window=g.window)
            x = x + a
            h = apply_norm(cfg, x, lp["norm2"])
            f = ffn_forward(cfg, lp["ffn"], h)
            return x + f, (rk, rv)

        x, (rk, rv) = jax.lax.scan(body, x, (gp, cache["ring_k"],
                                             cache["ring_v"]))
        return x, {"ring_k": rk, "ring_v": rv}
    if g.kind == "rglru":
        def body(x, xs):
            lp, h0, conv = xs
            hn = apply_norm(cfg, x, lp["norm1"])
            a, h0, conv = rglru_decode(cfg, lp["rglru"], hn, h0, conv)
            x = x + a
            hn = apply_norm(cfg, x, lp["norm2"])
            return x + ffn_forward(cfg, lp["ffn"], hn), (h0, conv)

        x, (h, conv) = jax.lax.scan(body, x, (gp, cache["h"], cache["conv"]))
        return x, {"h": h, "conv": conv}
    if g.kind == "ssd":
        def body(x, xs):
            lp, h0, conv = xs
            hn = apply_norm(cfg, x, lp["norm1"])
            a, h0, conv = ssd_decode(cfg, lp["ssd"], hn, h0, conv)
            return x + a, (h0, conv)

        x, (h, conv) = jax.lax.scan(body, x, (gp, cache["h"], cache["conv"]))
        return x, {"h": h, "conv": conv}
    raise ValueError(g.kind)


def _experts_out_of_scan(g: LayerGroup, gp: PyTree):
    """An expert group's params without its stacked expert weights, for
    the layer scan to slice, and those weights, which the grouped-FFN
    kernel reads a layer at a time from the stack by the layer's index: a
    slice taken by the scan would be copied every step before the kernel
    reads it."""
    if not g.moe:
        return gp, None
    moe = gp["moe"]
    return (dict(gp, moe={k: v for k, v in moe.items()
                          if k not in EXPERT_WEIGHTS}),
            {k: moe[k] for k in EXPERT_WEIGHTS})


@jax.named_scope("ffn")
def _ffn_block(cfg: ModelConfig, g: LayerGroup, lp: PyTree, x: jax.Array,
               experts: Optional[PyTree] = None,
               layer: Optional[jax.Array] = None) -> jax.Array:
    """Pre-norm FFN (or served expert) sublayer with its residual; an
    expert layer's scopes ``router`` and ``experts`` nest in ``ffn``.
    ``experts``: the group's stacked expert weights, of which ``layer``'s
    are used (``_experts_out_of_scan``)."""
    h = apply_norm(cfg, x, lp["norm2"])
    if g.moe:
        p = lp["moe"] if experts is None else {**lp["moe"], **experts}
        f = moe_serve(cfg, p, h, layer)
    else:
        f = ffn_forward(cfg, lp["ffn"], h)
    return x + f


def _cross_decode(cfg: ModelConfig, p: PyTree, x: jax.Array, ck: jax.Array,
                  cv: jax.Array) -> jax.Array:
    """Cross-attention decode against precomputed encoder KV [B,Se,K,hd]."""
    from .attention import _gqa_out, _gqa_scores
    B = x.shape[0]
    hd = cfg.resolved_head_dim
    q = (x @ p["wq"].astype(cfg.dtype)).reshape(B, 1, cfg.n_heads, hd)
    scores = _gqa_scores(cfg, q, ck)
    probs = jax.nn.softmax(scores, axis=-1)
    return _gqa_out(cfg, probs, cv, p)


def prefill(cfg: ModelConfig, params: PyTree, tokens: jax.Array,
            state: DecodeState, phys_blocks: jax.Array
            ) -> Tuple[jax.Array, DecodeState]:
    """Prefill a prompt batch [B,S]: full forward + scatter KV into slabs.

    SSM/recurrent caches are refreshed by replaying the recurrence; paged
    groups scatter their per-layer K/V through the block table.
    """
    B, S = tokens.shape
    with jax.named_scope("embed"):
        x = params["embedding"].astype(cfg.dtype)[tokens]
        x = x * jnp.asarray(cfg.d_model ** 0.5, cfg.dtype)
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    groups = layer_groups(cfg)
    new_caches: List[Dict[str, jax.Array]] = []
    for g, gp, cache in zip(groups, params["groups"], state.caches):
        x, cache = _prefill_group(cfg, g, gp, cache, x, positions,
                                  phys_blocks)
        new_caches.append(cache)
    with jax.named_scope("lm_head"):
        x = apply_norm(cfg, x, params["final_norm"])
        head = params.get("lm_head", params["embedding"].T)
        logits = (x[:, -1] @ head.astype(cfg.dtype))
    return logits, DecodeState(tuple(new_caches),
                               jnp.full((B,), S, jnp.int32))


def _prefill_group(cfg, g, gp, cache, x, positions, phys_blocks):
    """Forward one group over the full prompt and update its cache."""
    from .attention import _project_qkv
    from .common import apply_rope
    B, S, _ = x.shape
    bt = cfg.kv_block_tokens

    if g.kind == "attn" and g.window is None:
        from ..kvcache.gather import (scatter_prefill_plain,
                                      scatter_prefill_pooled)

        gp_scan, experts = _experts_out_of_scan(g, gp)

        def body(carry, xs):
            x = carry
            lp, li, ks, vs = xs
            with jax.named_scope("attn_qkv"):
                h = apply_norm(cfg, x, lp["norm1"])
            a = attn_forward(cfg, lp["attn"], h, positions, window=None,
                             rope_theta=g.rope_theta)
            # scatter this layer's K/V into the paged slabs (pool-local)
            with jax.named_scope("attn_qkv"):
                q, k, v = _project_qkv(cfg, lp["attn"], h, h)
                if cfg.use_rope:
                    k = apply_rope(k, positions, g.rope_theta)
            scatter = (scatter_prefill_pooled if ks.ndim == 5
                       else scatter_prefill_plain)
            ks, vs = scatter(ks, vs, k, v, phys_blocks, positions, bt)
            with jax.named_scope("attn"):
                x = x + a
            return _ffn_block(cfg, g, lp, x, experts, li), (ks, vs)

        x, (ks, vs) = jax.lax.scan(
            body, x, (gp_scan, jnp.arange(g.n_layers), cache["k_slabs"],
                      cache["v_slabs"]))
        return x, dict(cache, k_slabs=ks, v_slabs=vs)

    # other kinds: run the layer forward AND capture its decode state inside
    # the same scan (the state depends on each layer's own input).
    if g.kind == "attn":   # local-window ring buffers
        W = g.window
        n_fill = min(S, W)
        src = jnp.arange(S - n_fill, S)
        slots = src % W

        def body(carry, xs):
            x = carry
            lp, rk0, rv0 = xs
            h = apply_norm(cfg, x, lp["norm1"])
            a = attn_forward(cfg, lp["attn"], h, positions, window=W,
                             rope_theta=g.rope_theta)
            q, k, v = _project_qkv(cfg, lp["attn"], h, h)
            if cfg.use_rope:
                k = apply_rope(k, positions, g.rope_theta)
            rk = jnp.zeros_like(rk0).at[:, slots].set(
                k[:, src].astype(rk0.dtype))
            rv = jnp.zeros_like(rv0).at[:, slots].set(
                v[:, src].astype(rv0.dtype))
            x = x + a
            h = apply_norm(cfg, x, lp["norm2"])
            return x + ffn_forward(cfg, lp["ffn"], h), (rk, rv)

        x, (rks, rvs) = jax.lax.scan(body, x, (gp, cache["ring_k"],
                                               cache["ring_v"]))
        return x, {"ring_k": rks, "ring_v": rvs}

    if g.kind == "rglru":
        def body(carry, lp):
            x = carry
            h = apply_norm(cfg, x, lp["norm1"])
            out, st = rglru_forward(cfg, lp["rglru"], h, return_state=True)
            x = x + out
            h = apply_norm(cfg, x, lp["norm2"])
            return x + ffn_forward(cfg, lp["ffn"], h), st

        x, st = jax.lax.scan(body, x, gp)
        return x, {"h": st["h"], "conv": st["conv"]}

    if g.kind == "ssd":
        def body(carry, lp):
            x = carry
            h = apply_norm(cfg, x, lp["norm1"])
            out, st = ssd_forward(cfg, lp["ssd"], h, return_state=True)
            return x + out, st

        x, st = jax.lax.scan(body, x, gp)
        return x, {"h": st["h"], "conv": st["conv"]}
    return x, cache


def prefill_encdec(cfg: ModelConfig, params: PyTree, enc_feats: jax.Array,
                   dec_tokens: jax.Array, state: DecodeState,
                   phys_blocks: jax.Array) -> Tuple[jax.Array, DecodeState]:
    """Whisper-style prefill: run the encoder, fill each decoder layer's
    cross-attention KV from the encoder output, then prefill the decoder
    prompt (self-attn KV scattered into paged slabs through the numaPTE
    block tables — the cross KV is the big read-only paged region)."""
    from .attention import _project_qkv
    B, Se, _ = enc_feats.shape
    Sd = dec_tokens.shape[1]
    bt = cfg.kv_block_tokens
    enc_g, dec_g = layer_groups(cfg)
    enc_pos = jnp.broadcast_to(jnp.arange(Se)[None, :], (B, Se))
    x = enc_feats.astype(cfg.dtype) + _sinusoids(Se, cfg.d_model)[None]
    x, _ = _run_groups(cfg, params, x, enc_pos, [enc_g],
                       [params["groups"][0]], remat=False)
    enc_out = apply_norm(cfg, x, params["enc_norm"])

    dec_cache = state.caches[1]
    dp = params["groups"][1]

    # cross KV per decoder layer (scan over stacked params)
    def fill_cross(lp):
        cp = lp["cross"]
        hd = cfg.resolved_head_dim
        ck = (enc_out @ cp["wk"].astype(cfg.dtype)).reshape(
            B, Se, cfg.n_kv_heads, hd)
        cv = (enc_out @ cp["wv"].astype(cfg.dtype)).reshape(
            B, Se, cfg.n_kv_heads, hd)
        return ck.astype(dec_cache["cross_k"].dtype), \
            cv.astype(dec_cache["cross_v"].dtype)

    cks, cvs = jax.vmap(fill_cross)(dp)

    # decoder prompt prefill
    y = params["dec_embedding"].astype(cfg.dtype)[dec_tokens]
    y = y + params["dec_pos"].astype(cfg.dtype)[:Sd][None]
    dec_pos = jnp.broadcast_to(jnp.arange(Sd)[None, :], (B, Sd))
    from ..kvcache.gather import scatter_prefill_plain, scatter_prefill_pooled

    def body(carry, xs):
        yv = carry
        lp, ks, vs = xs
        h = apply_norm(cfg, yv, lp["norm1"])
        a = attn_forward(cfg, lp["attn"], h, dec_pos, window=None,
                         rope_theta=dec_g.rope_theta)
        q, k, v = _project_qkv(cfg, lp["attn"], h, h)
        scatter = (scatter_prefill_pooled if ks.ndim == 5
                   else scatter_prefill_plain)
        ks, vs = scatter(ks, vs, k, v, phys_blocks, dec_pos, bt)
        yv = yv + a
        h = apply_norm(cfg, yv, lp["norm_cross"])
        a = attn_forward(cfg, lp["cross"], h, dec_pos, window=None,
                         rope_theta=dec_g.rope_theta, causal=False,
                         kv_x=enc_out)
        yv = yv + a
        h = apply_norm(cfg, yv, lp["norm2"])
        return yv + ffn_forward(cfg, lp["ffn"], h), (ks, vs)

    y, (ks, vs) = jax.lax.scan(
        body, y, (dp, dec_cache["k_slabs"], dec_cache["v_slabs"]))
    y = apply_norm(cfg, y, params["final_norm"])
    head = params.get("lm_head", params["dec_embedding"].T)
    logits = (y[:, -1] @ head.astype(cfg.dtype))
    new_dec = dict(dec_cache, k_slabs=ks, v_slabs=vs, cross_k=cks,
                   cross_v=cvs)
    return logits, DecodeState((state.caches[0], new_dec),
                               jnp.full((B,), Sd, jnp.int32))


@jax.named_scope("lm_head")
def greedy_sample(logits: jax.Array) -> jax.Array:
    """Argmax over the vocabulary.  Named with the head that feeds it: XLA
    fuses the head's matmul into the argmax."""
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)
