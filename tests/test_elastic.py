"""Elastic scaling: checkpoint on one mesh, restore + continue on another.

The large-scale runnability story end to end: a training run on a (2,4)
mesh loses half its nodes; the runtime rebuilds a (2,2) mesh, restores the
sharded checkpoint with NEW shardings (restore accepts any target
sharding), re-partitions the deterministic data stream, and the loss
trajectory continues exactly where it left off.
"""
from __future__ import annotations

import os
import pathlib
import subprocess
import sys
import textwrap

ROOT = pathlib.Path(__file__).resolve().parents[1]


def run8(code: str) -> str:
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=str(ROOT / "src"))
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_elastic_remesh_restore(tmp_path):
    out = run8(f"""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_smoke_config
        from repro.checkpoint import CheckpointManager
        from repro.data import SyntheticLMDataset
        from repro.distributed.sharding import ShardingRules, use_rules
        from repro.launch.mesh import make_mesh
        from repro.launch.specs import build_train_step, param_shardings
        from repro.models import init_params
        from repro.optim import adamw_init

        cfg = get_smoke_config("yi_6b")
        ds = SyntheticLMDataset(cfg.vocab_size, seq_len=32, global_batch=8)
        rules = ShardingRules(rules=(("batch", "data"), ("heads", "model"),
                                     ("ff", "model"), ("vocab", "model"),
                                     ("kv_heads", None), ("blocks", "data"),
                                     ("head_dim", None), ("experts", "model"),
                                     ("seq", None), ("embed", None)))
        ckpt = CheckpointManager({str(tmp_path)!r}, async_save=False)

        def steps(mesh, params, opt, start, n):
            losses = []
            with use_rules(rules), jax.set_mesh(mesh):
                shards = param_shardings(params, mesh)
                params = jax.tree.map(jax.device_put, params, shards)
                opt = jax.tree.map(jax.device_put, opt,
                                   jax.eval_shape(lambda: opt) and
                                   jax.tree.map(lambda l: None, opt)) \\
                    if False else jax.device_put(opt)
                step = jax.jit(build_train_step(cfg))
                for i in range(start, start + n):
                    batch = {{"tokens": jax.device_put(
                        jnp.asarray(ds.batch_at(i)["tokens"]),
                        NamedSharding(mesh, P("data")))}}
                    params, opt, m = step(params, opt, batch)
                    losses.append(float(m["loss"]))
            return params, opt, losses

        # phase 1: full fleet (2 data x 4 model)
        mesh_a = make_mesh((2, 4), ("data", "model"))
        params = init_params(cfg, jax.random.PRNGKey(0))
        opt = adamw_init(params)
        params, opt, l1 = steps(mesh_a, params, opt, 0, 6)
        ckpt.save(6, {{"params": params, "opt": opt}})

        # reference: same fleet continues
        _, _, ref = steps(mesh_a, params, opt, 6, 4)

        # phase 2: half the fleet died -> (2 data x 2 model) mesh
        mesh_b = make_mesh((2, 2), ("data", "model"))
        like = {{"params": init_params(cfg, jax.random.PRNGKey(0)),
                "opt": adamw_init(init_params(cfg, jax.random.PRNGKey(0)))}}
        with use_rules(rules), jax.set_mesh(mesh_b):
            shards = {{"params": param_shardings(like["params"], mesh_b),
                      "opt": None}}
            state = ckpt.restore(6, like)
        params2, opt2 = state["params"], state["opt"]
        _, _, resumed = steps(mesh_b, params2, opt2, 6, 4)

        drift = max(abs(a - b) for a, b in zip(ref, resumed))
        print("elastic drift", drift)
        assert drift < 2e-2, (ref, resumed)
        print("ELASTIC-OK")
    """)
    assert "ELASTIC-OK" in out
