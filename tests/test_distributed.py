"""Distributed tests on an 8-device host mesh (subprocess so the main test
process keeps its single CPU device), plus HLO-analyzer unit tests."""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys
import textwrap

import pytest

from repro.launch.hlo_analysis import analyze, parse_module

ROOT = pathlib.Path(__file__).resolve().parents[1]


def run_in_subprocess(code: str) -> str:
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=str(ROOT / "src"))
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_analyzer_counts_scan_trips():
    import jax
    import jax.numpy as jnp

    def f(x, w):
        def body(c, w1):
            return jnp.tanh(c @ w1), None
        out, _ = jax.lax.scan(body, x, w)
        return out

    x = jnp.zeros((64, 128))
    w = jnp.zeros((6, 128, 128))
    hlo = jax.jit(f).lower(x, w).compile().as_text()
    t = analyze(hlo)
    assert t.flops == pytest.approx(2 * 64 * 128 * 128 * 6, rel=0.01)


def test_analyzer_vmem_scope_suppresses_bytes():
    import jax
    import jax.numpy as jnp

    def attn(q, k, v):
        with jax.named_scope("vmem_attn"):
            s = q @ k.T
            p = jax.nn.softmax(s, axis=-1)
            return p @ v

    q = jnp.zeros((256, 64))
    k = jnp.zeros((256, 64))
    v = jnp.zeros((256, 64))
    hlo = jax.jit(attn).lower(q, k, v).compile().as_text()
    t = analyze(hlo)
    # boundary = q,k,v reads + out write (+epsilon); the 256x256 scores /
    # probs (512KB) must NOT appear
    assert t.bytes_rw < 300_000, t.bytes_rw
    assert t.flops == pytest.approx(2 * 2 * 256 * 256 * 64, rel=0.05)


def test_small_mesh_train_and_serve_steps():
    """Lower+compile+RUN a reduced config on a real 8-device mesh."""
    out = run_in_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_smoke_config
        from repro.distributed.sharding import ShardingRules, use_rules
        from repro.launch.mesh import make_mesh
        from repro.launch.specs import param_shardings, build_train_step
        from repro.models import init_params
        from repro.optim import adamw_init
        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = get_smoke_config("yi_6b")
        rules = ShardingRules(rules=(("batch", "data"), ("heads", "model"),
                                     ("ff", "model"), ("vocab", "model"),
                                     ("kv_heads", None), ("experts", "model"),
                                     ("blocks", "data"), ("head_dim", None),
                                     ("seq", None), ("embed", None)))
        with use_rules(rules), jax.set_mesh(mesh):
            params = init_params(cfg, jax.random.PRNGKey(0))
            shards = param_shardings(params, mesh)
            params = jax.tree.map(jax.device_put, params, shards)
            opt = adamw_init(params)
            tokens = jnp.asarray(
                np.random.default_rng(0).integers(0, cfg.vocab_size,
                                                  (4, 33)), jnp.int32)
            tokens = jax.device_put(tokens, NamedSharding(mesh, P("data")))
            step = jax.jit(build_train_step(cfg))
            p2, o2, m = step(params, opt, {"tokens": tokens})
            print("loss", float(m["loss"]))
            assert jnp.isfinite(m["loss"])
    """)
    assert "loss" in out


def test_dryrun_cell_small_mesh():
    """The dry-run machinery works end to end on a small forced mesh."""
    out = run_in_subprocess("""
        import jax
        from repro.launch.mesh import make_mesh
        from repro.launch.specs import build_cell
        from repro.configs import SHAPES
        mesh = make_mesh((2, 4), ("data", "model"))
        cell = build_cell("yi_6b", SHAPES["train_4k"], mesh)
        with jax.set_mesh(mesh):
            compiled = jax.jit(cell.step_fn,
                               donate_argnums=cell.donate).lower(
                *cell.args).compile()
        print("ok", compiled.as_text().count("all-reduce") > 0)
    """)
    assert "ok True" in out


def test_multi_pod_serve_cell():
    out = run_in_subprocess("""
        import jax
        from repro.launch.mesh import make_mesh
        from repro.launch.specs import build_cell
        from repro.configs import SHAPES
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        cell = build_cell("yi_6b", SHAPES["decode_32k"], mesh)
        with jax.set_mesh(mesh):
            compiled = jax.jit(cell.step_fn,
                               donate_argnums=cell.donate).lower(
                *cell.args).compile()
        print("compiled-ok")
    """)
    assert "compiled-ok" in out


def test_gather_readonly_on_pod_mesh_matches_slice_then_gather():
    """``gather_readonly``'s shard_map branch on a (pod=4) mesh: each pool
    lies on its own devices, and every layer's gather reads bit for bit
    what slicing the layer's pool out of the stack and gathering the row's
    frames (-1 entries clamped to its pool's frame 0) would."""
    out = run_in_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.distributed.sharding import MULTI_POD_RULES, use_rules
        from repro.kvcache.gather import gather_readonly
        from repro.launch.mesh import make_mesh
        L, PODS, F, BT, K, HD, B, MB = 3, 4, 6, 4, 2, 8, 8, 5
        rng = np.random.default_rng(0)
        shape = (L, PODS, F, BT, K, HD)
        k_np = rng.normal(size=shape).astype(np.float32)
        v_np = rng.normal(size=shape).astype(np.float32)
        tbl = rng.integers(0, F, (B, MB)).astype(np.int32)
        tbl[0, 3:] = -1
        tbl[3, 1] = -1
        tbl[6] = -1
        mesh = make_mesh((PODS, 1, 2), ("pod", "data", "model"))
        on = lambda *s: NamedSharding(mesh, P(*s))
        stack_on = on(None, ("pod", "data"), None, None, "model", None)
        with use_rules(MULTI_POD_RULES), jax.set_mesh(mesh):
            ks = jax.device_put(jnp.asarray(k_np, jnp.bfloat16), stack_on)
            vs = jax.device_put(jnp.asarray(v_np, jnp.bfloat16), stack_on)
            pb = jax.device_put(jnp.asarray(tbl), on(("pod", "data"), None))

            def body(_, li):
                return None, gather_readonly(ks, vs, li, pb)
            _, (ka, va) = jax.jit(lambda: jax.lax.scan(
                body, None, jnp.arange(L)))()
        assert len(ks.sharding.device_set) == 8
        ka, va = np.asarray(ka), np.asarray(va)
        k_ref, v_ref = np.asarray(ks), np.asarray(vs)
        frames = np.where(tbl >= 0, tbl, 0)
        pool = np.arange(B) // (B // PODS)
        for li in range(L):
            want_k = k_ref[li][pool[:, None], frames]
            want_v = v_ref[li][pool[:, None], frames]
            assert ka[li].shape == (B, MB, BT, K, HD)
            assert ka[li].tobytes() == want_k.tobytes(), li
            assert va[li].tobytes() == want_v.tobytes(), li
        print("pod-gather-ok")
    """)
    assert "pod-gather-ok" in out


def test_pod_mesh_coherence_step_matches_one_device():
    """chip_smoke.py's four-chip phase at smoke size on 4 host devices:
    eager and numaPTE coherence steps on a (pod=4) mesh with the KV pool
    split per pod sample exactly the tokens of one device without the pod
    axis, each pod's pool lies on its own device and holds exactly the
    one device's KV, and every pod replica agrees with the host's
    canonical table."""
    out = run_in_subprocess(f"""
        import sys
        sys.path.insert(0, {str(ROOT)!r})
        import jax
        from chip_smoke import pod_mesh_phase
        from repro.configs import get_smoke_config
        r = pod_mesh_phase(get_smoke_config("qwen3_14b"), jax.devices()[:4],
                           batch=8, prompt_len=40, steps=3)
        assert r["n"] == 4 * 8
        assert r["exact"] == {{"eager": 32, "numapte": 32}}, r["exact"]
        assert r["kv_gap"] == {{m: [0.0] * 2 for m in ("eager", "numapte")}}, \\
            r["kv_gap"]
        print("pod-mesh-ok", sorted(r["tokens"]))
    """)
    assert "pod-mesh-ok ['eager', 'numapte', 'one_device']" in out


def test_expert_layer_on_a_mesh_matches_one_device():
    """The served expert layer on a (data=2, model=4) host mesh, its held
    experts 2-9 split over 'model' as the parameters are (2 a device):
    prefill and a decode step give the logits of one device holding all 8,
    and the decode step sums the shares over 'model'."""
    out = run_in_subprocess("""
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_smoke_config
        from repro.distributed.sharding import use_rules
        from repro.launch.mesh import make_mesh
        from repro.launch.specs import make_rules, param_shardings
        from repro.models import init_decode_state, init_params
        from repro.models.transformer import decode_step, prefill
        cfg = dataclasses.replace(
            get_smoke_config("qwen3_moe_235b_a22b"), n_experts=16,
            experts_held=8, first_held_expert=2, dtype=jnp.float32)
        mesh = make_mesh((2, 4), ("data", "model"))
        B, MB, S = 8, 4, 12
        params = init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)),
                             jnp.int32)
        nxt = jnp.asarray(rng.integers(0, cfg.vocab_size, (B,)), jnp.int32)
        phys = jnp.asarray(np.arange(B * MB, dtype=np.int32).reshape(B, MB))

        def run(params):
            state = init_decode_state(cfg, B, B * MB, MB)
            pre = jax.jit(lambda p, s: prefill(cfg, p, tokens, s, phys))
            dec = jax.jit(lambda p, s: decode_step(cfg, p, s, nxt, phys))
            a, state = pre(params, state)
            b, _ = dec(params, state)
            hlo = dec.lower(params, state).compile().as_text()
            return np.asarray(a), np.asarray(b), hlo

        one = run(params)
        with use_rules(make_rules(cfg, mesh)), jax.set_mesh(mesh):
            placed = jax.tree.map(jax.device_put, params,
                                  param_shardings(params, mesh))
            we_in = placed["groups"][0]["moe"]["we_in"]
            assert we_in.shape[1] == 8
            assert we_in.addressable_shards[0].data.shape[1] == 2
            many = run(placed)
        for got, want in zip(many[:2], one[:2]):
            np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
        print("expert-mesh-ok", "all-reduce" in many[2])
    """)
    assert "expert-mesh-ok True" in out
