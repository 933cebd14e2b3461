"""Logical-axis sharding: models name axes, rules map them to mesh axes.

Model code never mentions mesh axes; it constrains activations with logical
names ('batch', 'heads', 'ff', 'experts', ...).  A ``ShardingRules`` table
maps logical names to mesh axes per deployment:

  * single-pod (16, 16) ('data', 'model')
  * multi-pod (2, 16, 16) ('pod', 'data', 'model') — 'pod' joins the batch
    dimension (pure DP + the numaPTE coherence domain), and the KV pool is
    split over ('pod', 'data') in lockstep with the batch, so each pod holds
    the pools of its own sequences.

This is the MaxText "logical axis rules" pattern, reduced to what we need.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import PartitionSpec as P

Axis = Union[None, str, Tuple[str, ...]]


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """Map logical axis name -> mesh axis (or tuple of mesh axes)."""
    rules: Tuple[Tuple[str, Axis], ...]

    def lookup(self, logical: Optional[str]) -> Axis:
        if logical is None:
            return None
        for name, target in self.rules:
            if name == logical:
                return target
        return None

    def spec(self, logical_axes: Sequence[Optional[str]]) -> P:
        return P(*[self.lookup(a) for a in logical_axes])


#: single-pod production mesh ('data', 'model')
SINGLE_POD_RULES = ShardingRules(rules=(
    ("batch", "data"),
    ("seq", None),
    ("act_seq", None),      # Megatron-SP maps this to 'model' (see specs)
    ("seq_sp", "data"),        # sequence-parallel prefill
    ("embed", None),
    ("heads", "model"),
    ("kv_heads", "model"),
    ("head_dim", None),
    ("ff", "model"),
    ("vocab", "model"),
    ("experts", "model"),
    ("expert_ff", None),
    ("blocks", "data"),        # KV slab pool
    ("pod", None),
))

#: multi-pod production mesh ('pod', 'data', 'model')
MULTI_POD_RULES = ShardingRules(rules=(
    ("batch", ("pod", "data")),
    ("seq", None),
    ("act_seq", None),
    ("seq_sp", "data"),
    ("embed", None),
    ("heads", "model"),
    ("kv_heads", "model"),
    ("head_dim", None),
    ("ff", "model"),
    ("vocab", "model"),
    ("experts", "model"),
    ("expert_ff", None),
    ("blocks", ("pod", "data")),
    ("pod", "pod"),
))

#: FSDP-style variant: parameters additionally sharded over 'data' on their
#: longest non-model axis (ZeRO-3); used by the kimi-scale configs.
FSDP_EXTRA_AXES = ("embed", "expert_ff")

_state = threading.local()


def current_rules() -> ShardingRules:
    return getattr(_state, "rules", SINGLE_POD_RULES)


@contextlib.contextmanager
def use_rules(rules: ShardingRules):
    prev = getattr(_state, "rules", None)
    _state.rules = rules
    try:
        yield rules
    finally:
        if prev is None:
            del _state.rules
        else:
            _state.rules = prev


def logical_spec(*logical_axes: Optional[str]) -> P:
    return current_rules().spec(logical_axes)


def get_active_mesh() -> Optional[jax.sharding.AbstractMesh]:
    """The mesh of the surrounding ``jax.set_mesh`` scope, or None."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def _mesh_axes() -> frozenset:
    mesh = get_active_mesh()
    return frozenset() if mesh is None else frozenset(mesh.axis_names)


def constrain(x: jax.Array, *logical_axes: Optional[str]) -> jax.Array:
    """with_sharding_constraint by logical axis names.  No-op outside a mesh
    context; axes the surrounding mesh lacks are dropped."""
    spec = logical_spec(*logical_axes)
    if all(a is None for a in spec):
        return x
    avail = _mesh_axes()
    if not avail:
        return x

    def keep(a: Axis) -> Axis:
        if a is None:
            return None
        if isinstance(a, tuple):
            kept = tuple(ax for ax in a if ax in avail)
            return kept or None
        return a if a in avail else None

    spec = P(*[keep(a) for a in spec])
    if all(a is None for a in spec):
        return x
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except (ValueError, RuntimeError, NameError, KeyError):
        return x


def param_pspec(path: Tuple[str, ...], shape: Tuple[int, ...]) -> P:
    """Sharding spec for one parameter from its pytree path + shape.

    Convention: parameter names end with axis hints, e.g. 'wq' has shape
    [embed, heads*head_dim] -> P(None, 'model').  We infer from well-known
    leaf names used across repro.models.
    """
    leaf = path[-1]
    rules = current_rules()
    m = rules.lookup("heads")
    f = rules.lookup("ff")
    v = rules.lookup("vocab")
    e = rules.lookup("experts")
    table = {
        # attention
        "wq": P(None, m), "wk": P(None, m), "wv": P(None, m), "wo": P(m, None),
        # dense ffn
        "w_in": P(None, f), "w_gate": P(None, f), "w_out": P(f, None),
        # embeddings / head
        "embedding": P(v, None), "lm_head": P(None, v),
        # moe: experts dim sharded
        "we_in": P(e, None, None), "we_gate": P(e, None, None),
        "we_out": P(e, None, None), "router": P(None, e),
        # mamba / rglru big projections
        "in_proj": P(None, f), "out_proj": P(f, None),
        "conv_w": P(None, f), "conv_b": P(f),
        "a_log": P(f), "dt_bias": P(f), "d_skip": P(f),
        "rg_a": P(f), "rg_in": P(None, f), "rg_gate": P(None, f),
    }
    if leaf in table:
        spec = table[leaf]
        # guard: axes must divide; fall back to replicated on mismatch
        return spec
    # norms, biases, small vectors: replicated
    return P()
