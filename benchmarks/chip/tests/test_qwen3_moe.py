"""The ``qwen3_moe_235b_a22b`` configuration's pieces: its adapter, its
plain reference (``reference/qwen3_moe.py``) against the program's served
expert layer, a smoke-size cell served through ``harness.run_cell``, and
the expert layer's two readers.

- The adapter fits the configuration file as run.
- The reference's shares add up: over the 4 shares of 2 experts each of
  8, its expert outputs sum to those of the uncut layer.
- With a router that sends every choice of every token to the experts
  held here, the program's layer drops nothing and equals the reference.
- The program's MoE smoke preset, cut to 2 of its 8 experts and run in
  float32, is prefilled and decoded through the paged cache by the real
  wave engine and is ``correct`` against the reference's full forward
  pass; with the held experts' part left out, or computed for the wrong
  experts, it is not.
- ``expert_ms_per_step`` and ``expert_roofline`` read a synthetic trace.
"""
import copy
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import harness, trace_reduce
from benchmarks.chip.engines import waves
from benchmarks.chip.engines.waves import Record, Wave
from benchmarks.chip.tests import smoke
from benchmarks.chip.traffic import Request

CELL = "qwen3_moe_235b_a22b.reasoning_long"
SEED = 2**31 + 53
ref = harness.piece("reference", "qwen3_moe")

#: the program's smoke preset at the reference's keys, 2 of 8 experts held
MODEL = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
         "head_dim": 16, "n_experts": 8, "experts_per_token": 2,
         "experts_held": 2, "first_held_expert": 0, "moe_d_ff": 96,
         "norm_topk_prob": True, "n_shared_experts": 0, "vocab_size": 512,
         "qk_norm": True, "ffn_act": "silu", "rope_theta": 10000.0,
         "tie_embeddings": False, "norm": "rmsnorm", "norm_eps": 1e-06,
         "embed_scale": "sqrt_d_model", "kv_block_tokens": 16,
         "dtype": "bfloat16", "param_dtype": "bfloat16"}


def test_adapter_fits_the_file():
    cell = harness.find_cell(harness.load_benchmark(), CELL)
    cfg = waves.program_config(cell.doc)
    assert (cfg.n_layers, cfg.n_experts, cfg.n_held_experts) == (8, 128, 8)
    assert waves.adapter(cell.doc).check(cfg, cell.doc["model"]) == {}
    assert set(cell.doc["reduced"]) == {"n_layers", "experts_held"}


def _layer_weights(m, seed):
    w = ref.init_weights(dict(m, param_dtype="float32"), seed)
    return jax.tree.map(lambda a: a[0], w["layers"])


def test_reference_shares_sum_to_the_uncut_layer():
    whole_m = dict(MODEL, experts_held=8)
    w = _layer_weights(whole_m, 11)
    h = jax.random.normal(jax.random.PRNGKey(12), (9, MODEL["d_model"]))
    whole = ref.expert_share(whole_m, h, w)
    parts = []
    for first in range(0, 8, 2):
        sub = {k: (v[first:first + 2] if k.startswith("we_") else v)
               for k, v in w.items()}
        parts.append(ref.expert_share(
            dict(MODEL, first_held_expert=first), h, sub))
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(whole),
                               rtol=1e-5, atol=1e-6)


def test_program_holding_every_choice_matches_the_reference():
    from repro.configs import get_smoke_config
    from repro.models.moe import moe_serve
    m = dict(MODEL, first_held_expert=3)
    w = _layer_weights(m, 13)
    # a large first coordinate that the router reads into experts 3 and 4
    h = jax.random.normal(jax.random.PRNGKey(14), (48, m["d_model"]))
    h = h.at[:, 0].set(6.0)
    w["router"] = w["router"].at[0].set(0.0).at[0, 3].set(4.0) \
        .at[0, 4].set(3.9)
    g = ref.routing(m, h, w["router"])
    assert float(jnp.sum(g)) == pytest.approx(48.0)      # every choice held
    cfg = dataclasses.replace(
        get_smoke_config("qwen3_moe_235b_a22b"), experts_held=2,
        first_held_expert=3, dtype=jnp.float32, param_dtype=jnp.float32)
    got = moe_serve(cfg, {k: w[k] for k in ("router", "we_in", "we_gate",
                                            "we_out")}, h[None])[0]
    want = ref.expert_share(m, h, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def smoke_cell(gen_len=24, dtype="bfloat16"):
    cell = harness.find_cell(harness.load_benchmark(), CELL)
    doc = copy.deepcopy(cell.doc)
    reduced = {"experts_held": "2 of the preset's 8"}
    if dtype != "bfloat16":
        reduced["dtype"] = "the program computes in the reference's type"
    doc.update(program_size="smoke",
               model=dict(MODEL, dtype=dtype, param_dtype=dtype),
               reduced=reduced, server=dict(doc["server"], batch=4),
               limits={"token_gap": smoke.SMOKE_GAP_LIMIT})
    mix = dict(cell.mix, prompt_len=32, gen_len=gen_len)
    return dataclasses.replace(cell, doc=doc, mix=mix)


def test_smoke_cell_is_correct_against_the_reference():
    # in float32: at smoke widths (8 experts, 2 a token) bfloat16 rounding
    # flips a token's second choice often enough (about 7% of requests
    # read a gap above 0.02 on the CPU) that a sample of two requests
    # would judge the rounding, not the layer; in float32 every served
    # token is the reference's own best (gap 0 in 232 requests)
    r = harness.run_cell(smoke_cell(dtype="float32"), SEED, 1.0, False,
                         jax.devices()[:1], time.perf_counter(), smoke.PEAK)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0
    assert {"output_tok_per_s", "itl_p95_ms", "setup_s"} <= set(r["metrics"])
    assert r["checks"]["token_gap"]["value"] < 1e-3


@pytest.mark.parametrize("fault", ["held_share_zeroed",
                                   "first_held_expert_shifted"])
def test_smoke_cell_with_an_expert_fault_is_not_correct(monkeypatch, fault):
    """A fault confined to the expert layer fails the comparison that
    decides ``correct``: the held experts' part left out, or computed for
    the experts one place further along the router's."""
    import repro.models.moe as moe
    serve = moe._serve_tokens

    def faulty(cfg, router, experts, xf, layer, first):
        if fault == "held_share_zeroed":
            return jnp.zeros(xf.shape, jnp.float32)
        return serve(cfg, router, experts, xf, layer, first + 1)
    monkeypatch.setattr(moe, "_serve_tokens", faulty)
    r = harness.run_cell(smoke_cell(dtype="float32"), SEED, 1.0, False,
                         jax.devices()[:1], time.perf_counter(), smoke.PEAK)
    assert not r["correct"], r["checks"]
    assert r["checks"]["token_gap"]["value"] > \
        r["checks"]["token_gap"]["limit"]


def _reduced(ops, calls=10):
    return trace_reduce.Reduced(
        window_s=1.0, busy_s=1.0, n_devices=1,
        programs={"bench_decode": {"calls": calls, "s": 0.2,
                                   "collective_s": 0.0}},
        ops=ops, idle_by_span=[])


def _ctx(ops, model=None):
    cell = smoke_cell()
    if model:
        cell.doc["model"] = model
    reqs = {i: Request(i, 0.0, np.zeros(4, np.int32), 4) for i in range(3)}
    record = Record(0.0, 1.0, 1.0, False, reqs,
                    [Wave([0, 1], 0.0, steps=6), Wave([2], 0.0, steps=4)],
                    {}, 4)
    return harness.Ctx(cell, record, [], 0.0, 1, smoke.PEAK, _reduced(ops))


def read(name, ctx):
    return harness.piece("metrics", name).read(ctx)


def test_expert_readers_on_a_synthetic_trace():
    ops = [("bench_decode/gmm.3", 0.03),
           ("bench_decode/gmm.11", 0.01),
           ("bench_decode/gmm", 0.004),
           ("bench_decode/fusion.12", 0.5),
           ("bench_decode/gmm_fusion.1", 9.0),     # not the kernel
           ("bench_prefill/gmm.1", 7.0)]           # not in the step
    ctx = _ctx(ops)
    assert read("expert_ms_per_step", ctx) == pytest.approx(4.4)  # 44/10
    m = ctx.cell.doc["model"]
    # 2 rows x 6 steps and 1 row x 4 steps; half a held choice a row
    least = 0.0
    for rows, steps in ((2, 6), (1, 4)):
        r = rows * 2 * 2 / 8
        flops = m["n_layers"] * 2 * 3 * 64 * 96 * r
        nbytes = m["n_layers"] * 2 * (2 * 3 * 64 * 96 + 2 * r * 64)
        assert ref.expert_flops(m, r) == flops
        assert ref.expert_bytes(m, r) == nbytes
        least += steps * max(flops / smoke.PEAK["bf16_flops_per_s"],
                             nbytes / smoke.PEAK["hbm_bytes_per_s"])
    assert read("expert_roofline", ctx) == pytest.approx(
        100 * least / 0.044)
    # a program without the kernel, or a cell whose reference counts no
    # expert layer, reads nothing and does not raise
    assert read("expert_ms_per_step", _ctx(ops[3:5])) is None
    assert read("expert_roofline", _ctx(ops[3:5])) is None
    dense = _ctx(ops)
    dense.cell.doc["reference"] = "dense_lm"
    assert read("expert_roofline", dense) is None


def test_counts_of_the_published_widths():
    doc = json.loads((harness.HERE / "configs" /
                      "qwen3_moe_235b_a22b.json").read_text())
    m = doc["model"]
    # an expert is 18.87M parameters; 8 held, 8 layers, in bf16
    assert ref.expert_bytes(m, 0) == 8 * 2 * 8 * 3 * 4096 * 1536
    assert ref.expert_rows(m, 64) == 32.0
    shapes = jax.tree.leaves(ref.weight_shapes(m),
                             is_leaf=lambda s: isinstance(s, tuple))
    params = sum(int(np.prod(s)) for s in shapes)
    assert params * 2 / 1e9 == pytest.approx(6.05, abs=0.01)
    # the decode step's floor at the mean context of 1,152: 6.0 GB
    assert ref.decode_bytes(m, [1152] * 64) / 1e9 == pytest.approx(
        6.0, abs=0.1)
