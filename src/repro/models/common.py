"""Shared model machinery: configs, layer groups, norms, RoPE, init.

Design decisions that matter at scale:

  * **Layer groups.**  Heterogeneous layer stacks (gemma3's 5 local : 1
    global, recurrentgemma's 2 RG-LRU : 1 local-attn, kimi's dense-first
    MoE) are represented as *runs of identical layers*; each run's params
    are stacked on a leading axis and executed with ``lax.scan``.  This
    keeps HLO size O(distinct kinds), not O(layers) — a 94-layer MoE
    compiles as one scanned body.
  * **Logical sharding.**  All tensors are annotated via
    ``repro.distributed.constrain`` with logical axis names; mesh mapping
    comes from the active ``ShardingRules``.
  * **eval_shape-friendly init.**  ``init_params`` builds arrays only under
    ``jax.eval_shape`` in the dry-run path (ShapeDtypeStruct, no host RAM).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..distributed import constrain

PyTree = Any


# --------------------------------------------------------------------------- config
@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                      # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None
    # attention options
    qk_norm: bool = False
    local_window: Optional[int] = None       # window for 'local' layers
    local_global_ratio: Optional[Tuple[int, int]] = None  # e.g. (5, 1)
    rope_theta: float = 10_000.0
    rope_theta_global: Optional[float] = None
    attn_logit_softcap: Optional[float] = None
    # ffn
    ffn_act: str = "silu"                    # silu | geglu | gelu | relu2
    # moe
    n_experts: int = 0
    experts_per_token: int = 0
    moe_d_ff: int = 0
    n_shared_experts: int = 0
    first_dense_layers: int = 0              # kimi: leading dense layers
    moe_capacity_factor: float = 1.25
    # the experts of each layer this chip holds: ``experts_held`` of them
    # from index ``first_held_expert`` (None: all ``n_experts``).  The
    # router keeps all ``n_experts`` outputs; see ``models.moe``.
    experts_held: Optional[int] = None
    first_held_expert: int = 0
    # ssm (mamba2 SSD)
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_chunk: int = 64
    conv_width: int = 4
    expand: int = 2
    # hybrid (recurrentgemma)
    recurrent_ratio: Optional[Tuple[int, int]] = None   # (n_recurrent, n_attn)
    lru_width: Optional[int] = None
    # enc-dec (whisper)
    n_encoder_layers: int = 0
    n_decoder_layers: int = 0
    max_decoder_len: int = 448
    use_rope: bool = True
    norm: str = "rmsnorm"                    # rmsnorm | layernorm
    tie_embeddings: bool = True
    # numerics
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    # serving
    kv_block_tokens: int = 16
    # sub-quadratic? (drives long_500k eligibility)
    sub_quadratic: bool = False
    # per-arch logical->mesh rule overrides, e.g. {"kv_heads": None}
    rule_overrides: Tuple[Tuple[str, Any], ...] = ()

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def n_held_experts(self) -> int:
        return self.n_experts if self.experts_held is None \
            else self.experts_held

    @property
    def q_per_kv(self) -> int:
        return self.n_heads // self.n_kv_heads

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model   # mamba2 inner width

    @property
    def ssm_n_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim


@dataclasses.dataclass(frozen=True)
class LayerGroup:
    """A run of structurally identical layers, executed as one lax.scan."""
    kind: str                 # attn | ssd | rglru | enc_attn | dec_attn
    n_layers: int
    window: Optional[int] = None      # None = global attention
    moe: bool = False
    rope_theta: float = 10_000.0


def layer_groups(cfg: ModelConfig) -> List[LayerGroup]:
    """Derive the run-length-encoded layer pattern from the config."""
    if cfg.family == "ssm":
        return [LayerGroup("ssd", cfg.n_layers)]
    if cfg.family == "encdec":
        return [LayerGroup("enc_attn", cfg.n_encoder_layers),
                LayerGroup("dec_attn", cfg.n_decoder_layers)]
    kinds: List[Tuple[str, Optional[int], bool, float]] = []
    for i in range(cfg.n_layers):
        if cfg.family == "hybrid" and cfg.recurrent_ratio:
            r, a = cfg.recurrent_ratio
            if i % (r + a) < r:
                kinds.append(("rglru", None, False, cfg.rope_theta))
                continue
            kinds.append(("attn", cfg.local_window, False, cfg.rope_theta))
            continue
        window: Optional[int] = None
        theta = cfg.rope_theta
        if cfg.local_global_ratio:
            loc, glob = cfg.local_global_ratio
            if (i % (loc + glob)) < loc:
                window = cfg.local_window
            else:
                theta = cfg.rope_theta_global or cfg.rope_theta
        moe = (cfg.n_experts > 0) and (i >= cfg.first_dense_layers)
        kinds.append(("attn", window, moe, theta))
    groups: List[LayerGroup] = []
    for kind, window, moe, theta in kinds:
        if (groups and groups[-1].kind == kind and groups[-1].window == window
                and groups[-1].moe == moe and groups[-1].rope_theta == theta):
            groups[-1] = dataclasses.replace(groups[-1],
                                             n_layers=groups[-1].n_layers + 1)
        else:
            groups.append(LayerGroup(kind, 1, window, moe, theta))
    assert sum(g.n_layers for g in groups) == cfg.n_layers or cfg.family == "encdec"
    return groups


# --------------------------------------------------------------------------- prims
def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    out = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (out * scale + bias).astype(x.dtype)


def apply_norm(cfg: ModelConfig, x: jax.Array, p: Dict[str, jax.Array]) -> jax.Array:
    if cfg.norm == "layernorm":
        return layer_norm(x, p["scale"], p["bias"])
    return rms_norm(x, p["scale"])


def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [..., seq, heads, head_dim]; positions: [..., seq]."""
    head_dim = x.shape[-1]
    freqs = rope_frequencies(head_dim, theta)                 # [hd/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., seq, hd/2]
    angles = angles[..., None, :]                              # broadcast heads
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def activation(name: str, x: jax.Array, gate: Optional[jax.Array]) -> jax.Array:
    if name == "silu":
        return jax.nn.silu(gate) * x
    if name == "geglu":
        return jax.nn.gelu(gate, approximate=True) * x
    if name == "gelu":
        return jax.nn.gelu(x, approximate=True)
    if name == "relu2":                       # nemotron squared-ReLU
        return jnp.square(jax.nn.relu(x))
    raise ValueError(f"unknown activation {name}")


def ffn_has_gate(name: str) -> bool:
    return name in ("silu", "geglu")


# --------------------------------------------------------------------------- init
def _dense(key: jax.Array, shape: Sequence[int], dtype, scale: float = 1.0
           ) -> jax.Array:
    fan_in = shape[0] if len(shape) > 1 else 1
    std = scale / math.sqrt(fan_in)
    return (jax.random.normal(key, tuple(shape), jnp.float32) * std).astype(dtype)


class KeyGen:
    """Deterministic key splitter with readable call sites."""

    def __init__(self, key: jax.Array):
        self._key = key

    def __call__(self) -> jax.Array:
        self._key, sub = jax.random.split(self._key)
        return sub


def init_norm(cfg: ModelConfig, d: int) -> Dict[str, jax.Array]:
    p = {"scale": jnp.zeros((d,), cfg.param_dtype)}
    if cfg.norm == "layernorm":
        p = {"scale": jnp.ones((d,), cfg.param_dtype),
             "bias": jnp.zeros((d,), cfg.param_dtype)}
    return p


def stack_layer_params(per_layer: List[PyTree]) -> PyTree:
    """Stack a list of identical pytrees on a new leading axis (for scan)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *per_layer)
