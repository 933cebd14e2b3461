"""Mixture-of-Experts: the served layer at a chip's share of the experts,
and the training layer with expert parallelism (EP over the 'model' axis).

Served (``moe_serve``, the decode step and prefill): a layer holds
``cfg.n_held_experts`` experts, from ``cfg.first_held_expert`` on, of the
``cfg.n_experts`` its router scores.  Every token is routed over all of
them (softmax in float32, top ``experts_per_token``, renormalised over
those); the (token, choice) pairs whose expert is held here are sorted by
expert and run through one grouped FFN (``repro.kernels.expert_ffn``),
scaled by their gate weights and added back to their tokens.  Nothing is
dropped: the buffers hold every choice of every token.  What the experts
held elsewhere would add is left out, with nothing standing in for them or
for the exchange that would carry their tokens.  Under a mesh the layer
runs in ``shard_map``: the held experts are split over the mesh's expert
axis, each chip computes its share's part for its rows of the batch, and
the parts are summed over that axis.

Training (``moe_forward``): sort-based capacity dispatch with static
shapes: assignments are ranked within their expert by a stable sort;
tokens beyond the per-expert capacity are dropped (Switch-style).  Expert
weights are sharded on the expert axis, so the per-expert einsums run
expert-parallel under pjit and the gather/scatter at the boundaries lowers
to the EP all-to-all/reduce pattern in SPMD.  Memory: the dispatched
activations are [E, C, D] with E*C = tokens*top_k*capacity_factor —
independent of expert count.  It needs every expert held.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..distributed import constrain, current_rules
from ..distributed.sharding import get_active_mesh
from ..kernels.expert_ffn.ops import expert_ffn
from .common import KeyGen, ModelConfig, _dense, activation, ffn_has_gate
from .ffn import ffn_forward, init_ffn

CAPACITY_FACTOR = 1.25
#: the served layer routes at most this many tokens at once (a prefill's
#: tokens go in chunks), so that its buffers, sized for every choice of
#: every token held here, stay small: 2048 tokens x 8 choices of 4096
#: bf16 values is 134 MB
SERVE_CHUNK_TOKENS = 2048


def init_moe(cfg: ModelConfig, keys: KeyGen) -> Dict[str, jax.Array]:
    d, f, e = cfg.d_model, cfg.moe_d_ff, cfg.n_experts
    held = cfg.n_held_experts
    p = {
        "router": _dense(keys(), (d, e), cfg.param_dtype, scale=0.1),
        "we_in": _dense(keys(), (held, d, f), cfg.param_dtype),
        "we_out": _dense(keys(), (held, f, d), cfg.param_dtype),
    }
    if ffn_has_gate(cfg.ffn_act):
        p["we_gate"] = _dense(keys(), (held, d, f), cfg.param_dtype)
    if cfg.n_shared_experts:
        p["shared"] = init_ffn(cfg, keys, d_ff=cfg.moe_d_ff * cfg.n_shared_experts)
    return p


def expert_capacity(n_tokens: int, n_experts: int, top_k: int,
                    factor: float = CAPACITY_FACTOR) -> int:
    c = int(n_tokens * top_k * factor / n_experts)
    return max(8, -(-c // 8) * 8)   # round up to 8 for layout friendliness


def moe_forward(cfg: ModelConfig, p: Dict[str, jax.Array], x: jax.Array
                ) -> Tuple[jax.Array, jax.Array]:
    """x: [B, S, D] -> (out [B, S, D], aux load-balance loss scalar)."""
    if cfg.n_held_experts != cfg.n_experts:
        raise ValueError("training needs every expert: this layer holds "
                         f"{cfg.n_held_experts} of {cfg.n_experts}")
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.experts_per_token
    N = B * S
    xf = x.reshape(N, D)

    logits = (xf @ p["router"].astype(cfg.dtype)).astype(jnp.float32)  # [N,E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, eids = jax.lax.top_k(probs, K)                 # [N,K]
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    # ---- load-balance auxiliary loss (Switch) --------------------------------
    me = jnp.mean(probs, axis=0)                              # [E]
    ce = jnp.zeros((E,), jnp.float32).at[eids.reshape(-1)].add(1.0) / N
    aux = E * jnp.sum(me * ce)

    # ---- sort-based capacity dispatch ----------------------------------------
    C = expert_capacity(N, E, K, cfg.moe_capacity_factor)
    flat_e = eids.reshape(-1)                                 # [N*K]
    flat_tok = jnp.repeat(jnp.arange(N), K)
    flat_w = gate_vals.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    se, st, sw = flat_e[order], flat_tok[order], flat_w[order]
    # rank of each assignment within its expert
    first = jnp.searchsorted(se, jnp.arange(E), side="left")  # [E]
    rank = jnp.arange(N * K) - first[se]
    keep = rank < C
    slot = jnp.where(keep, se * C + rank, E * C)              # sentinel slot
    disp_tok = jnp.full((E * C + 1,), N, jnp.int32).at[slot].set(
        st.astype(jnp.int32))[:-1].reshape(E, C)
    disp_w = jnp.zeros((E * C + 1,), jnp.float32).at[slot].set(
        sw)[:-1].reshape(E, C)

    # ---- expert computation (expert axis sharded -> EP) -----------------------
    x_pad = jnp.concatenate([xf, jnp.zeros((1, D), xf.dtype)])
    xe = x_pad[jnp.minimum(disp_tok, N)]                      # [E, C, D]
    xe = constrain(xe, "experts", None, None)
    h = jnp.einsum("ecd,edf->ecf", xe, p["we_in"].astype(cfg.dtype))
    gate = jnp.einsum("ecd,edf->ecf", xe, p["we_gate"].astype(cfg.dtype)) \
        if "we_gate" in p else None
    h = activation(cfg.ffn_act, h, gate)
    h = constrain(h, "experts", None, None)
    ye = jnp.einsum("ecf,efd->ecd", h, p["we_out"].astype(cfg.dtype))
    ye = ye * disp_w[..., None].astype(cfg.dtype)

    # ---- combine back to tokens ----------------------------------------------
    out = jnp.zeros((N + 1, D), cfg.dtype).at[disp_tok.reshape(-1)].add(
        ye.reshape(E * C, D))[:N]
    if cfg.n_shared_experts:
        out = out + ffn_forward(cfg, p["shared"], xf[None])[0]
    return out.reshape(B, S, D), aux


#: the expert weights a layer scan leaves stacked (see ``moe_serve``)
EXPERT_WEIGHTS = ("we_in", "we_gate", "we_out")


def moe_serve(cfg: ModelConfig, p: Dict[str, jax.Array], x: jax.Array,
              layer: Optional[jax.Array] = None) -> jax.Array:
    """The served layer at this chip's share: x [B, S, D] -> [B, S, D],
    the held experts' part of every token's output (plus the shared
    expert's, which every chip computes alike).  With ``layer``, the
    ``EXPERT_WEIGHTS`` of ``p`` are every layer's stack and the kernel
    reads that layer's from it."""
    if cfg.ffn_act != "silu":
        raise ValueError(f"the served expert layer is SiLU-gated, not "
                         f"{cfg.ffn_act}")
    B, S, D = x.shape
    experts = tuple(p[k].astype(cfg.dtype) for k in EXPERT_WEIGHTS)
    mesh = get_active_mesh()
    if mesh is None:
        out = _serve_rows(cfg, p["router"], experts, x.reshape(B * S, D),
                          layer, cfg.first_held_expert)
    else:
        out = _serve_on_mesh(cfg, mesh, p["router"], experts, x, layer)
    out = out.astype(cfg.dtype).reshape(B, S, D)
    if cfg.n_shared_experts:
        out = out + ffn_forward(cfg, p["shared"], x)
    return out


def _mesh_axes(mesh, logical: str, size: int) -> Tuple[str, ...]:
    """The mesh axes that the logical axis ``logical`` maps to, or () where
    the mesh lacks one of them or they do not divide ``size`` (the
    parameters are then replicated on it, see ``launch.specs``)."""
    names = current_rules().lookup(logical)
    names = names if isinstance(names, tuple) else (names,)
    if not names or any(n is None or n not in mesh.axis_names
                        for n in names):
        return ()
    return names if size % math.prod(mesh.shape[n] for n in names) == 0 \
        else ()


def _serve_on_mesh(cfg: ModelConfig, mesh, router: jax.Array,
                   experts: Tuple[jax.Array, ...], x: jax.Array,
                   layer: Optional[jax.Array]) -> jax.Array:
    """``moe_serve`` under a mesh, in ``shard_map`` (XLA cannot partition
    the kernel): each chip routes its rows of the batch over every expert
    and computes the part of its share of the held experts, which are
    split over the mesh's expert axis as the parameters are; the parts are
    summed over that axis.  Returns float32 [B, S, D]."""
    B, S, D = x.shape
    held = cfg.n_held_experts
    e_ax = _mesh_axes(mesh, "experts", held)
    b_ax = tuple(a for a in _mesh_axes(mesh, "batch", B) if a not in e_ax)
    per_chip = held // math.prod(mesh.shape[a] for a in e_ax)
    rows = P(b_ax or None, None, None)
    w_specs = tuple(P(*(None,) * (w.ndim - 3), e_ax or None, None, None)
                    for w in experts)
    layer = jnp.zeros((), jnp.int32) if layer is None else layer
    stacked = experts[0].ndim == 4

    def local(router, x, layer, *experts):
        first = cfg.first_held_expert
        if e_ax:
            first = first + jax.lax.axis_index(e_ax) * per_chip
        out = _serve_rows(cfg, router, experts, x.reshape(-1, D),
                          layer if stacked else None, first)
        if e_ax:
            out = jax.lax.psum(out, e_ax)
        return out.reshape(x.shape)

    return jax.shard_map(local, mesh=mesh,
                         in_specs=(P(), rows, P()) + w_specs,
                         out_specs=rows, check_vma=False)(
                             router, x, layer, *experts)


def _serve_rows(cfg: ModelConfig, router: jax.Array,
                experts: Tuple[jax.Array, ...], xf: jax.Array,
                layer: Optional[jax.Array], first) -> jax.Array:
    """xf [N, D] through the experts ``experts`` holds, the first of which
    is expert ``first`` of the router's: float32 [N, D], in chunks of at
    most ``SERVE_CHUNK_TOKENS`` rows."""
    N, D = xf.shape
    if N <= SERVE_CHUNK_TOKENS:
        return _serve_tokens(cfg, router, experts, xf, layer, first)
    n = -(-N // SERVE_CHUNK_TOKENS)
    chunks = jnp.pad(xf, ((0, n * SERVE_CHUNK_TOKENS - N), (0, 0)))
    return jax.lax.map(
        lambda c: _serve_tokens(cfg, router, experts, c, layer, first),
        chunks.reshape(n, SERVE_CHUNK_TOKENS, D)).reshape(-1, D)[:N]


def _serve_tokens(cfg: ModelConfig, router: jax.Array,
                  experts: Tuple[jax.Array, ...], xf: jax.Array,
                  layer: Optional[jax.Array], first) -> jax.Array:
    N, D = xf.shape
    K, held = cfg.experts_per_token, experts[0].shape[-3]
    with jax.named_scope("router"):
        logits = jnp.dot(xf, router.astype(cfg.dtype),
                         preferred_element_type=jnp.float32)      # [N, E]
        gates, eids = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), K)
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
        # each (token, choice) pair's held expert, or `held` if the expert
        # lives on another chip; held pairs sort first, by expert
        local = eids.reshape(-1) - first
        group = jnp.where((local >= 0) & (local < held), local, held)
        order = jnp.argsort(group, stable=True)
        sizes = jnp.bincount(group, length=held + 1)[:held].astype(jnp.int32)
        tok = (order // K).astype(jnp.int32)
        dest = jnp.where(group[order] < held, tok, N)   # N: dropped below
    with jax.named_scope("experts"):
        y = expert_ffn(xf[tok], sizes, *experts, layer)
        y = y.astype(jnp.float32) * gates.reshape(-1)[order][:, None]
        return jnp.zeros((N, D), jnp.float32).at[dest].add(y, mode="drop")
