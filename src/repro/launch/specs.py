"""Step builders + ShapeDtypeStruct input specs for every (arch x shape).

Everything here is allocation-free: parameters, optimizer states and decode
caches are ``jax.eval_shape`` results with NamedShardings attached, which
``jax.jit(...).lower()`` accepts directly — the dry-run lowers and compiles
full-scale cells on a 512-device host mesh without materializing a byte.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ..configs import ShapeSpec, get_config
from ..distributed.sharding import (MULTI_POD_RULES, SINGLE_POD_RULES,
                                    ShardingRules, get_active_mesh,
                                    param_pspec, use_rules)
from ..models import (init_decode_state, init_params, layer_groups, lm_loss)
from ..models.common import ModelConfig
from ..models.transformer import decode_step, greedy_sample, prefill, \
    prefill_encdec
from ..optim import adamw_init, adamw_update

PyTree = Any


@dataclasses.dataclass(frozen=True)
class PerfOptions:
    """Hillclimb levers (EXPERIMENTS.md §Perf).  All default to the
    paper-faithful baseline."""
    decode_kernel: str = "ref"      # ref | fused_ref (models the Pallas
    #                                 paged-attention kernel's streaming)
    bf16_grads: bool = False        # cast grads bf16 before optimizer/AR
    seq_parallel: bool = False      # Megatron-SP: residual activations
    #                                 sharded over 'model' between blocks
    coherence: str = "none"         # none | eager | numapte: block-table
    #                                 coherence prologue on the pod axis
    remat: str = "full"             # full | dots (checkpoint policy)
    compress_pod_grads: bool = False  # int8 error-feedback AR on the pod
    #                                 (DCI) axis; in-pod stays full precision

    def tag(self) -> str:
        bits = []
        if self.decode_kernel != "ref":
            bits.append(self.decode_kernel)
        if self.bf16_grads:
            bits.append("bf16g")
        if self.seq_parallel:
            bits.append("sp")
        if self.coherence != "none":
            bits.append(self.coherence)
        if self.remat != "full":
            bits.append("remat-" + self.remat)
        if self.compress_pod_grads:
            bits.append("int8pod")
        return "+".join(bits) or "base"


# --------------------------------------------------------------------------- rules
def make_rules(cfg: ModelConfig, mesh: Mesh,
               opts: Optional["PerfOptions"] = None) -> ShardingRules:
    base = MULTI_POD_RULES if "pod" in mesh.axis_names else SINGLE_POD_RULES
    table = dict(base.rules)
    table.update(dict(cfg.rule_overrides))
    if opts is not None and opts.seq_parallel:
        # Megatron-SP: the residual stream is sequence-sharded over the TP
        # axis between blocks, turning activation all-reduces into
        # reduce-scatter + all-gather pairs (half the wire bytes)
        table["act_seq"] = "model"
    return ShardingRules(rules=tuple(table.items()))


def _divisible(shape: Tuple[int, ...], spec: P, mesh: Mesh) -> P:
    """Drop sharding on dims the axis size doesn't divide (GSPMD would pad;
    we prefer explicit replication so memory analysis stays honest)."""
    fixed = []
    for dim, axis in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if axis is None:
            fixed.append(None)
            continue
        axes = axis if isinstance(axis, tuple) else (axis,)
        size = math.prod(mesh.shape[a] for a in axes)
        fixed.append(axis if dim % size == 0 else None)
    return P(*fixed)


def param_shardings(params_shapes: PyTree, mesh: Mesh) -> PyTree:
    """NamedShardings for a parameter pytree (handles scan-stacked leaves:
    one extra leading layer dim relative to the per-layer spec)."""
    def one(path, leaf):
        names = tuple(str(getattr(k, "key", getattr(k, "idx", k)))
                      for k in path)
        spec = param_pspec(names, leaf.shape)
        if len(spec) and len(leaf.shape) == len(spec) + 1:
            spec = P(None, *spec)
        spec = _divisible(leaf.shape, spec, mesh)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(one, params_shapes)


def _shaped(tree: PyTree, shardings: PyTree) -> PyTree:
    return jax.tree.map(
        lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
        tree, shardings)


def _named(mesh: Mesh, *axes) -> NamedSharding:
    return NamedSharding(mesh, P(*axes))


# --------------------------------------------------------------------------- steps
def build_train_step(cfg: ModelConfig, bf16_grads: bool = False,
                     remat: str = "full",
                     compress_pod_grads: bool = False) -> Callable:
    def train_step(params, opt_state, batch, ef=None):
        if bf16_grads:
            # mixed precision with f32 master weights: differentiate wrt a
            # bf16 copy so the data-parallel gradient all-reduce (inserted
            # by SPMD inside the backward) moves bf16 — half the wire
            # bytes; AdamW's f32 moments recover the precision.
            compute_params = jax.tree.map(
                lambda p: p.astype(jnp.bfloat16)
                if p.dtype == jnp.float32 and p.ndim >= 2 else p, params)
        else:
            compute_params = params
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: lm_loss(cfg, p, batch, remat=remat),
            has_aux=True)(compute_params)
        grads = jax.tree.map(lambda g, p: g.astype(p.dtype), grads, params)
        new_ef = ef
        if compress_pod_grads and ef is not None:
            # the cross-pod (DCI) leg of the gradient reduction runs in
            # int8 with error feedback; batch is constrained to shard only
            # over 'data' inside the loss, so autodiff's AR covers the
            # in-pod leg and this shard_map adds the compressed pod leg.
            from ..distributed.compression import compress_allreduce_pods
            mesh = get_active_mesh()
            if mesh is not None and "pod" in mesh.axis_names:
                from jax.sharding import PartitionSpec as P
                specs = jax.tree.map(
                    lambda g: P(*([None] * g.ndim)), grads)

                def pod_leg(g, e):
                    return compress_allreduce_pods(g, e, axis="pod")

                grads, new_ef = jax.shard_map(
                    pod_leg, mesh=mesh, in_specs=(specs, specs),
                    out_specs=(specs, specs), check_vma=False,
                    axis_names={"pod"})(grads, ef)
        new_params, new_opt, gnorm = adamw_update(params, grads, opt_state)
        metrics = dict(metrics, grad_norm=gnorm)
        if ef is not None:
            return new_params, new_opt, metrics, new_ef
        return new_params, new_opt, metrics
    return train_step


def build_prefill_step(cfg: ModelConfig) -> Callable:
    if cfg.family == "encdec":
        def step(params, state, enc_feats, dec_tokens, phys_blocks):
            logits, state = prefill_encdec(cfg, params, enc_feats, dec_tokens,
                                           state, phys_blocks)
            return greedy_sample(logits), state
        return step

    def step(params, state, tokens, phys_blocks):
        logits, state = prefill(cfg, params, tokens, state, phys_blocks)
        return greedy_sample(logits), state
    return step


def build_serve_step(cfg: ModelConfig, sp: bool = False,
                     kernel: str = "ref", coherence: str = "none") -> Callable:
    def step(params, state, tokens, phys_blocks, *coh_args):
        if coherence != "none" and coh_args:
            coh_out = _coherence_prologue(coherence, *coh_args)
            logits, state = decode_step(cfg, params, state, tokens,
                                        phys_blocks, sp=sp, kernel=kernel)
            return greedy_sample(logits), state, coh_out
        logits, state = decode_step(cfg, params, state, tokens, phys_blocks,
                                    sp=sp, kernel=kernel)
        return greedy_sample(logits), state
    return step


@jax.named_scope("coherence")
def _coherence_prologue(mode: str, entries, sharers, owner, mut_t, mut_i,
                        mut_v, mut_ok, miss):
    """Per-step block-table coherence over the 'pod' axis — the paper's
    mechanism in the jitted step.  EAGER all-gathers every pod's mutation
    buffer every step (Mitosis); NUMAPTE applies only sharer-filtered
    updates and fetches misses from owners with degree-d prefetch."""
    from jax.sharding import PartitionSpec as P
    from ..pagedpt.coherence import (eager_sync, numapte_apply_filtered,
                                     numapte_miss_fetch)
    mesh = get_active_mesh()

    def body(entries, sharers, owner, mut_t, mut_i, mut_v, mut_ok, miss):
        local = entries[0]
        if mode == "eager":
            local = eager_sync(local, mut_t[0], mut_i[0], mut_v[0],
                               mut_ok[0], axis_name="pod")
            return local[None], sharers
        local = numapte_apply_filtered(local, sharers, mut_t[0], mut_i[0],
                                       mut_v[0], mut_ok[0], axis_name="pod")
        local, sharers = numapte_miss_fetch(local, sharers, owner, miss[0],
                                            prefetch_degree=3,
                                            axis_name="pod")
        return local[None], sharers

    f = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P("pod"), P(), P(), P("pod"), P("pod"), P("pod"),
                  P("pod"), P("pod")),
        out_specs=(P("pod"), P()),
        check_vma=False)
    return f(entries, sharers, owner, mut_t, mut_i, mut_v, mut_ok, miss)


# --------------------------------------------------------------------------- specs
@dataclasses.dataclass
class CellSpec:
    """Everything needed to lower one (arch x shape x mesh) cell."""
    arch: str
    shape: ShapeSpec
    cfg: ModelConfig
    step_fn: Callable
    args: Tuple            # ShapeDtypeStructs w/ shardings
    rules: ShardingRules
    donate: Tuple[int, ...] = ()


def _decode_geometry(cfg: ModelConfig, shape: ShapeSpec,
                     data_size: int) -> Tuple[int, int, int]:
    """(n_frames, max_blocks_per_seq, n_pools)."""
    bt = cfg.kv_block_tokens
    mb = -(-shape.seq_len // bt) + 1
    mb = -(-mb // data_size) * data_size     # SP shards table columns evenly
    n_frames = shape.global_batch * mb
    n_pools = data_size
    n_frames = -(-n_frames // n_pools) * n_pools      # divisible pool split
    return n_frames, mb, n_pools


def build_cell(arch: str, shape: ShapeSpec, mesh: Mesh,
               *, remat: bool = True,
               opts: Optional[PerfOptions] = None) -> CellSpec:
    opts = opts or PerfOptions()
    cfg = get_config(arch)
    rules = make_rules(cfg, mesh, opts)
    gb, S = shape.global_batch, shape.seq_len
    data_size = mesh.shape["data"]
    if "pod" in mesh.axis_names:
        data_size *= mesh.shape["pod"]

    with use_rules(rules):
        batch_ax = rules.lookup("batch")
        params_shapes = jax.eval_shape(
            lambda k: init_params(cfg, k),
            jax.ShapeDtypeStruct((2,), jnp.uint32))
        p_shards = param_shardings(params_shapes, mesh)
        params = _shaped(params_shapes, p_shards)

        if shape.step == "train":
            opt_shapes = jax.eval_shape(adamw_init, params_shapes)
            # moments share the param shardings; step counter replicated
            from ..optim import AdamWState
            opt = AdamWState(
                step=jax.ShapeDtypeStruct((), jnp.int32,
                                          sharding=_named(mesh)),
                mu=_shaped(opt_shapes.mu, p_shards),
                nu=_shaped(opt_shapes.nu, p_shards))
            if cfg.family == "encdec":
                batch = {
                    "enc_feats": jax.ShapeDtypeStruct(
                        (gb, S, cfg.d_model), jnp.bfloat16,
                        sharding=_named(mesh, batch_ax)),
                    "tokens": jax.ShapeDtypeStruct(
                        (gb, cfg.max_decoder_len + 1), jnp.int32,
                        sharding=_named(mesh, batch_ax)),
                }
            else:
                batch = {"tokens": jax.ShapeDtypeStruct(
                    (gb, S + 1), jnp.int32, sharding=_named(mesh, batch_ax))}
            args = (params, opt, batch)
            if opts.compress_pod_grads and "pod" in mesh.axis_names:
                ef_shapes = jax.tree.map(
                    lambda l: jax.ShapeDtypeStruct(l.shape, jnp.float32),
                    params_shapes)
                args = args + (_shaped(ef_shapes, p_shards),)
            step = with_rules(rules, build_train_step(
                cfg, opts.bf16_grads, opts.remat, opts.compress_pod_grads))
            return CellSpec(arch, shape, cfg, step, args, rules,
                            donate=(0, 1))

        # serving shapes ------------------------------------------------------
        n_frames, mb, n_pools = _decode_geometry(cfg, shape, data_size)
        sp = shape.step == "decode" and gb < data_size
        enc_len = S if cfg.family == "encdec" else 0
        state_shapes = jax.eval_shape(
            lambda: init_decode_state(cfg, gb, n_frames, mb, enc_len=enc_len,
                                      n_pools=n_pools))
        state = _shaped(state_shapes, state_shardings(
            cfg, state_shapes, mesh, rules, sp=sp))

        if shape.step == "prefill":
            if cfg.family == "encdec":
                args = (params, state,
                        jax.ShapeDtypeStruct((gb, S, cfg.d_model),
                                             jnp.bfloat16,
                                             sharding=_named(mesh, batch_ax)),
                        jax.ShapeDtypeStruct((gb, cfg.max_decoder_len),
                                             jnp.int32,
                                             sharding=_named(mesh, batch_ax)),
                        jax.ShapeDtypeStruct((gb, mb), jnp.int32,
                                             sharding=_named(mesh, batch_ax)))
            else:
                args = (params, state,
                        jax.ShapeDtypeStruct((gb, S), jnp.int32,
                                             sharding=_named(mesh, batch_ax)),
                        jax.ShapeDtypeStruct((gb, mb), jnp.int32,
                                             sharding=_named(mesh, batch_ax)))
            step = with_rules(rules, build_prefill_step(cfg))
            return CellSpec(arch, shape, cfg, step, args, rules, donate=(1,))

        # decode: tokens [gb], block tables [gb, mb]
        blocks_ax = rules.lookup("blocks")
        tbl_sharding = (_named(mesh, None, blocks_ax) if sp
                        else _named(mesh, batch_ax, None))
        tok_sharding = _named(mesh) if sp else _named(mesh, batch_ax)
        args = (params, state,
                jax.ShapeDtypeStruct((gb,), jnp.int32, sharding=tok_sharding),
                jax.ShapeDtypeStruct((gb, mb), jnp.int32,
                                     sharding=tbl_sharding))
        if opts.coherence != "none" and "pod" in mesh.axis_names:
            n_pods = mesh.shape["pod"]
            n_tables = max(1, -(-n_frames // 512))
            mut_budget, miss_budget = 1024, 256
            i32 = jnp.int32
            pod_sh = _named(mesh, "pod")
            args = args + (
                jax.ShapeDtypeStruct((n_pods, n_tables, 512), i32,
                                     sharding=pod_sh),
                jax.ShapeDtypeStruct((n_tables,), jnp.uint32,
                                     sharding=_named(mesh)),
                jax.ShapeDtypeStruct((n_tables,), i32, sharding=_named(mesh)),
                jax.ShapeDtypeStruct((n_pods, mut_budget), i32, sharding=pod_sh),
                jax.ShapeDtypeStruct((n_pods, mut_budget), i32, sharding=pod_sh),
                jax.ShapeDtypeStruct((n_pods, mut_budget), i32, sharding=pod_sh),
                jax.ShapeDtypeStruct((n_pods, mut_budget), jnp.bool_,
                                     sharding=pod_sh),
                jax.ShapeDtypeStruct((n_pods, miss_budget), i32,
                                     sharding=pod_sh),
            )
        step = with_rules(rules, build_serve_step(
            cfg, sp=sp, kernel=opts.decode_kernel, coherence=opts.coherence))
        return CellSpec(arch, shape, cfg, step, args, rules, donate=(1,))


def state_shardings(cfg: ModelConfig, state_shapes, mesh: Mesh,
                    rules: ShardingRules, sp: bool) -> PyTree:
    """NamedShardings for a decode state: the KV pool split over the
    'blocks' axes, per-sequence state over 'batch'."""
    blocks_ax = rules.lookup("blocks")
    batch_ax = rules.lookup("batch") if not sp else None
    kv_ax = None if sp else rules.lookup("kv_heads")
    hd_ax = None if sp else rules.lookup("head_dim")

    def shard_cache(leaf_path, leaf):
        name = str(leaf_path[-1].key) if hasattr(leaf_path[-1], "key") else ""
        nd = len(leaf.shape)
        if name in ("k_slabs", "v_slabs") and nd == 6:   # [L,P,K,F,bt,hd]
            spec = P(None, blocks_ax, kv_ax, None, None, hd_ax)
        elif name in ("k_slabs", "v_slabs"):             # [L,K,F,bt,hd]
            spec = P(None, kv_ax, blocks_ax, None, hd_ax)
        elif name in ("ring_k", "ring_v"):
            spec = P(None, batch_ax, None, kv_ax, hd_ax)
        elif name in ("cross_k", "cross_v"):
            spec = P(None, batch_ax, None, kv_ax, hd_ax)
        elif name == "h" and nd == 5:       # ssd state [L,B,H,n,P]
            spec = P(None, batch_ax, None, None, None)
        elif name == "h":                   # rglru [L,B,W]
            spec = P(None, batch_ax, rules.lookup("ff"))
        elif name == "conv":
            spec = P(None, batch_ax, None, None)
        else:
            spec = P()
        spec = _divisible(leaf.shape, spec, mesh)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(shard_cache, state_shapes)


def with_rules(rules: ShardingRules, step: Callable) -> Callable:
    """``step`` traced under ``rules``: the logical->mesh axis table that
    the model's sharding constraints and shard_map regions read."""
    def run(*args):
        with use_rules(rules):
            return step(*args)
    return run
