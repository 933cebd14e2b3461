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


def test_one_chip_decode_step_fits_v5e(spec):
    cfg = get_one_chip_config("qwen3_14b")
    key = spec((2,), jnp.uint32)

    def placed(tree):
        return jax.tree.map(lambda l: spec(l.shape, l.dtype), tree)

    params = placed(jax.eval_shape(functools.partial(init_params, cfg), key))
    state = placed(jax.eval_shape(functools.partial(
        init_decode_state, cfg, BATCH, BATCH * MAX_BLOCKS, MAX_BLOCKS)))
    c = _compile(build_serve_step(cfg), params, state,
                 spec((BATCH,), jnp.int32),
                 spec((BATCH, MAX_BLOCKS), jnp.int32), donate_argnums=(1,))
    mem = c.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < V5E_HBM_BYTES
    # the donated decode state is updated in place, not copied
    assert mem.alias_size_in_bytes > 0
