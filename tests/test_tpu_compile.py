"""Compile the serving path's kernels and the one-chip decode step for a
described TPU v5e, at qwen3_14b's published widths.

Nothing runs: the TPU compiler refuses here what the chip would refuse
(block shapes off the tiling, too much fast memory, a program larger than
the device), which interpret mode on the CPU never checks.  The topology
is described inside a fixture, so collecting this file loads no TPU
library; every test stays in this one file so that one worker loads it.
"""
from __future__ import annotations

import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_one_chip_config
from repro.kernels.expert_ffn.kernel import expert_ffn_kernel
from repro.kernels.flash_attention.kernel import flash_attention_kernel
from repro.kernels.paged_attention.kernel import paged_attention_kernel
from repro.kernels.pte_gather.kernel import pte_gather_kernel
from repro.launch.specs import build_serve_step
from repro.models import init_decode_state, init_params

V5E_HBM_BYTES = 16 * 10**9
# qwen3_14b: 40 query heads over 8 KV heads of 128; 16-token KV blocks
H, K, HD, BT = 40, 8, 128, 16
BATCH, MAX_BLOCKS = 8, 72


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = jax.sharding.SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def _compile(fn, *args, **jit_kw):
    return jax.jit(fn, **jit_kw).lower(*args).compile()


def test_paged_attention_compiles_for_v5e(spec):
    bf = jnp.bfloat16
    n_frames = BATCH * MAX_BLOCKS
    c = _compile(paged_attention_kernel, spec((BATCH, H, HD), bf),
                 spec((n_frames, BT, K, HD), bf),
                 spec((n_frames, BT, K, HD), bf),
                 spec((BATCH, MAX_BLOCKS), jnp.int32),
                 spec((BATCH,), jnp.int32))
    assert "tpu_custom_call" in c.as_text()


def test_flash_attention_compiles_for_v5e(spec):
    bf = jnp.bfloat16
    S = 2048
    c = _compile(flash_attention_kernel, spec((1, H, S, HD), bf),
                 spec((1, K, S, HD), bf), spec((1, K, S, HD), bf))
    assert "tpu_custom_call" in c.as_text()


def test_pte_gather_compiles_for_v5e(spec):
    # 64 table pages of 512 entries (the PagedKVManager default), 256
    # lookups with the default degree-3 prefetch window
    c = _compile(functools.partial(pte_gather_kernel, prefetch_degree=3),
                 spec((64, 512), jnp.int32), spec((256,), jnp.int32))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("rows", [64 * 8, 2048 * 8])
def test_expert_ffn_compiles_for_v5e(spec, rows):
    """Qwen3-235B-A22B's 8 held experts (d 4096, expert width 1536) in an
    8-layer stack, over a decode step's routed rows (64 x 8) and a prefill
    chunk's (2048 x 8): three Pallas calls, named as the benchmark's
    reader finds them."""
    bf = jnp.bfloat16
    c = _compile(expert_ffn_kernel, spec((rows, 4096), bf),
                 spec((8,), jnp.int32), spec((8, 8, 4096, 1536), bf),
                 spec((8, 8, 4096, 1536), bf), spec((8, 8, 1536, 4096), bf),
                 spec((1,), jnp.int32))
    calls = re.findall(r"%(\S+) = \S+ custom-call\([^\n]*tpu_custom_call",
                       c.as_text())
    assert [re.sub(r"\.\d+$", "", n) for n in calls] == ["gmm"] * 3, calls


def test_expert_decode_step_reads_the_stacked_experts(spec, monkeypatch):
    """qwen3_moe_235b_a22b's one-chip decode step at its cell's shapes (64
    rows, 129-block tables): the grouped-FFN kernel reads each layer's
    experts from the 8-layer stack itself; no operation copies a layer's
    experts out of it first (a scan's slice would be one, every step)."""
    import repro.kernels.expert_ffn.ops as expert_ops
    # the ops wrapper interprets the kernel where JAX's default backend is
    # the CPU; here the step is compiled for the described chip
    monkeypatch.setattr(expert_ops, "interpret_on_cpu", lambda: False)
    cfg = get_one_chip_config("qwen3_moe_235b_a22b")
    batch, max_blocks = 64, 129
    placed = functools.partial(jax.tree.map,
                               lambda l: spec(l.shape, l.dtype))
    params = placed(jax.eval_shape(functools.partial(init_params, cfg),
                                   spec((2,), jnp.uint32)))
    state = placed(jax.eval_shape(functools.partial(
        init_decode_state, cfg, batch, batch * max_blocks, max_blocks)))
    hlo = _compile(build_serve_step(cfg), params, state,
                   spec((batch,), jnp.int32),
                   spec((batch, max_blocks), jnp.int32),
                   donate_argnums=(1,)).as_text()
    kernels = [l for l in hlo.splitlines()
               if re.search(r"%gmm\S* = .*tpu_custom_call", l)]
    assert len(kernels) == 3, kernels
    for l in kernels:   # the 8-layer stack as 64 groups
        assert "bf16[64,4096,1536]" in l or "bf16[64,1536,4096]" in l, l
    one_layer = re.compile(r"= bf16\[8,(4096,1536|1536,4096)\]")
    assert not [l for l in hlo.splitlines() if one_layer.search(l)]


@pytest.mark.parametrize("step", ["decode", "prefill"])
def test_expert_steps_compile_on_an_expert_mesh(topo, monkeypatch, step):
    """Qwen3-235B-A22B's expert layers at published widths on a 2x2 v5e
    mesh, 16 held experts split over 'model' (8 a chip) and the batch
    over 'data': XLA cannot partition the grouped-FFN kernel, so the
    layer runs it in shard_map, where each chip's kernel reads its own 8
    experts."""
    import dataclasses

    import repro.kernels.expert_ffn.ops as expert_ops
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.distributed.sharding import use_rules
    from repro.launch.mesh import make_mesh
    from repro.launch.specs import (build_prefill_step, make_rules,
                                    param_shardings, state_shardings)
    monkeypatch.setattr(expert_ops, "interpret_on_cpu", lambda: False)
    cfg = dataclasses.replace(get_one_chip_config("qwen3_moe_235b_a22b"),
                              n_layers=2, experts_held=16)
    mesh = make_mesh((2, 2), ("data", "model"), devices=topo.devices)
    batch, max_blocks = 16, 9

    def placed(tree, shardings):
        return jax.tree.map(lambda l, s: jax.ShapeDtypeStruct(
            l.shape, l.dtype, sharding=s), tree, shardings)

    def rows(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=NamedSharding(
            mesh, P("data", *(None,) * (len(shape) - 1))))

    rules = make_rules(cfg, mesh)
    with use_rules(rules), jax.set_mesh(mesh):
        params = jax.eval_shape(functools.partial(init_params, cfg),
                                jax.ShapeDtypeStruct((2,), jnp.uint32))
        params = placed(params, param_shardings(params, mesh))
        state = jax.eval_shape(functools.partial(
            init_decode_state, cfg, batch, batch * max_blocks, max_blocks,
            n_pools=2))
        state = placed(state, state_shardings(cfg, state, mesh, rules,
                                              sp=False))
        if step == "decode":
            fn, tokens = build_serve_step(cfg), rows(batch)
        else:
            fn, tokens = build_prefill_step(cfg), rows(batch, 128)
        hlo = _compile(fn, params, state, tokens, rows(batch, max_blocks),
                       donate_argnums=(1,)).as_text()
    kernels = [l for l in hlo.splitlines()
               if re.search(r"%gmm\S* = .*tpu_custom_call", l)]
    assert kernels
    for l in kernels:   # the 2-layer stack of this chip's 8, as 16 groups
        assert re.search(r"bf16\[16,(4096,1536|1536,4096)\]", l), l


def _decode_step(spec, batch, max_blocks):
    """The one-chip decode step compiled at ``batch`` rows of
    ``max_blocks``-block tables over a pool of ``batch * max_blocks``
    frames, and the decode state it updates."""
    cfg = get_one_chip_config("qwen3_14b")
    key = spec((2,), jnp.uint32)

    def placed(tree):
        return jax.tree.map(lambda l: spec(l.shape, l.dtype), tree)

    params = placed(jax.eval_shape(functools.partial(init_params, cfg), key))
    state = placed(jax.eval_shape(functools.partial(
        init_decode_state, cfg, batch, batch * max_blocks, max_blocks)))
    c = _compile(build_serve_step(cfg), params, state,
                 spec((batch,), jnp.int32),
                 spec((batch, max_blocks), jnp.int32), donate_argnums=(1,))
    return c, state


def test_one_chip_decode_step_fits_v5e(spec):
    c, _ = _decode_step(spec, BATCH, MAX_BLOCKS)
    mem = c.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < V5E_HBM_BYTES
    # the donated decode state is updated in place, not copied
    assert mem.alias_size_in_bytes > 0


def _fusions(hlo: str):
    """(shape, op_name, ops of the fused body) of every fusion in ``hlo``,
    the text of a compiled module."""
    bodies, cur = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%(\S+) \(.*\{$", line)
        if head:
            cur = bodies.setdefault(head.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            cur.append(line)
    out = []
    for m in re.finditer(r"= (\w+\[[\d,]*\])\S* fusion\(.*calls=%([\w.]+)"
                         r".*op_name=\"([^\"]*)\"", hlo):
        ops = set(re.findall(r"= \S+ ([\w-]+)\(", "\n".join(bodies[m[2]])))
        out.append((m[1], m[3], ops))
    return out


def test_decode_long_step_gathers_from_the_stack(spec):
    """At decode_long's shapes (16 rows, 129-block tables, 2,064 frames)
    each layer's K and V gathers read the stacked cache itself: no fusion
    of ``kv_gather`` copies a layer's pool out of the stack first."""
    batch, max_blocks = 16, 129
    frames = batch * max_blocks
    c, state = _decode_step(spec, batch, max_blocks)
    hlo = c.as_text()
    pool = f"bf16[{frames},{BT},{K},{HD}]"
    pool_copies = [(shape, name) for shape, name, ops in _fusions(hlo)
                   if "kv_gather/" in name and shape == pool
                   and "dynamic-slice" in ops]
    assert not pool_copies, pool_copies
    gathers = re.findall(
        rf"= bf16\[{batch},{max_blocks},{BT},{K},{HD}\]\S* gather\("
        r"[^\n]*op_name=\"[^\"]*kv_gather/gather\"", hlo)
    assert len(gathers) == 2, gathers          # one for K, one for V
    mem = c.memory_analysis()
    pool_bytes = frames * BT * K * HD * jnp.dtype(jnp.bfloat16).itemsize
    assert mem.temp_size_in_bytes < 2 * pool_bytes
    # the whole donated cache is still updated in place
    state_bytes = sum(l.size * l.dtype.itemsize
                      for l in jax.tree.leaves(state))
    assert mem.alias_size_in_bytes >= state_bytes
