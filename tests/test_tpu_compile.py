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
