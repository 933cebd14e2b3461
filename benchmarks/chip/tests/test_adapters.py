"""The pieces that depend on a model's architecture are found by the
configuration's names: ``reference/<reference>.py`` with its counts, and
``adapters/<reference>.py`` with the program's side of it.

- The dense adapter fits the configuration files as run, and refuses a
  program configuration of another family.
- A family the benchmark has never run joins as new files: the program's
  ``qwen3_moe_235b_a22b`` smoke preset is served through
  ``harness.run_cell`` and the real wave engine, with a reference, an
  adapter and counts that the test writes into a tree of its own, into
  which the real ``engines/`` and ``metrics/`` are linked.  That checks
  the plumbing, not the expert layer: the test's reference hands over
  the program's own ``init_params`` as the weights and reads every served
  token's gap as 0.  Comparing an expert layer with a plain reference is
  the work of the change that adds such a configuration.
"""
import json
import textwrap
import time

import jax
import pytest

from benchmarks.chip import harness, trace_reduce
from benchmarks.chip.engines import waves
from benchmarks.chip.tests import smoke

SEED = 2**31 + 41


@pytest.mark.parametrize("name", ["qwen3_14b", "nemotron_4_15b"])
def test_dense_adapter_fits_the_files(name):
    doc = json.loads((harness.HERE / "configs" / f"{name}.json"
                      ).read_text())
    cfg = waves.program_config(doc)
    assert cfg.n_layers == doc["model"]["n_layers"]
    assert waves.adapter(doc).check(cfg, doc["model"]) == {}


def test_dense_adapter_refuses_another_family():
    doc = smoke.cell().doc
    doc["program_config"] = "qwen3_moe_235b_a22b"
    with pytest.raises(ValueError, match="family"):
        waves.program_config(doc)


MOE_MODEL = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
             "head_dim": 16, "n_experts": 4, "experts_per_token": 2,
             "moe_d_ff": 96, "vocab_size": 512, "kv_block_tokens": 16,
             "dtype": "bfloat16", "param_dtype": "bfloat16"}

MOE_REFERENCE = '''
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_smoke_config
from repro.models import init_params

#: which counts the readers asked for
CALLS = []


def init_weights(m, seed, sharding=None):
    cfg = dataclasses.replace(get_smoke_config("qwen3_moe_235b_a22b"),
                              n_layers=m["n_layers"],
                              n_experts=m["n_experts"],
                              param_dtype=jnp.dtype(m["param_dtype"]))
    kw = {} if sharding is None else {"out_shardings": sharding}
    return jax.jit(lambda k: init_params(cfg, k), **kw)(
        jax.random.PRNGKey(seed & 0x7FFFFFFF))


def logit_gaps(m, weights, prompt, served, control=False):
    return np.zeros(len(served))


def token_flops(m, context):
    CALLS.append("token_flops")
    return 1e9


def prefill_flops(m, prompt_len, rows):
    CALLS.append("prefill_flops")
    return 1e12 * rows


def decode_bytes(m, contexts):
    CALLS.append("decode_bytes")
    return 1e9
'''

MOE_ADAPTER = '''
KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "n_experts",
        "experts_per_token", "moe_d_ff", "vocab_size", "kv_block_tokens")


def check(cfg, m):
    wrong = {k: (getattr(cfg, k), m[k]) for k in KEYS
             if getattr(cfg, k) != m[k]}
    if cfg.family != "moe":
        wrong["family"] = (cfg.family, "moe")
    return wrong


def to_program(cfg, weights):
    return weights
'''


def test_another_family_joins_as_files(tmp_path, monkeypatch):
    for kind in ("engines", "metrics"):
        (tmp_path / kind).symlink_to(harness.HERE / kind)
    for kind, text in (("reference", MOE_REFERENCE),
                       ("adapters", MOE_ADAPTER)):
        (tmp_path / kind).mkdir()
        (tmp_path / kind / "moe_plumbing.py").write_text(
            textwrap.dedent(text))
    monkeypatch.setattr(harness, "HERE", tmp_path)

    cell = smoke.cell(arrival="poisson", rate=60.0)
    cell.doc.update(program_config="qwen3_moe_235b_a22b",
                    reference="moe_plumbing", model=dict(MOE_MODEL),
                    reduced={"n_layers": "the preset's depth",
                             "n_experts": "4 of the preset's 8 held here"})
    cfg = waves.program_config(cell.doc)
    assert (cfg.family, cfg.n_experts) == ("moe", 4)

    # the CPU trace has no device plane: give the decode and prefill
    # programs device time, so that the device readers read
    real_reduce = trace_reduce.reduce

    def reduce(events, names):
        r = real_reduce(events, names)
        r.programs = {
            "bench_decode": {"calls": 1, "s": 2.0, "collective_s": 0.0},
            "bench_prefill": {"calls": 1, "s": 3.0, "collective_s": 0.0}}
        return r
    monkeypatch.setattr(trace_reduce, "reduce", reduce)

    r = harness.run_cell(cell, SEED, 1.5, True, jax.devices()[:1],
                         time.perf_counter(), smoke.PEAK)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert {"decode_mfu", "decode_roofline", "prefill_mfu",
            "walk_ms_per_step", "wave_turnaround_ms"} <= set(m)
    calls = harness.piece("reference", "moe_plumbing").CALLS
    assert {"token_flops", "prefill_flops", "decode_bytes"} <= set(calls)
    # the readers took the test's counts: the prefills count 1e12 FLOPs a
    # row over 3 s, and every row was prefilled once
    flops_per_s = smoke.PEAK["bf16_flops_per_s"]
    assert m["prefill_mfu"] * 3.0 * flops_per_s / 100 / 1e12 == \
        pytest.approx(r["attempted"])
    # 1e9 FLOPs per row and step; 1e9 bytes a step, which bound it
    row_steps = m["decode_mfu"] * 2.0 * flops_per_s / 100 / 1e9
    steps = m["decode_roofline"] * 2.0 * smoke.PEAK[
        "hbm_bytes_per_s"] / 100 / 1e9
    assert row_steps == pytest.approx(round(row_steps)) and row_steps > 0
    assert steps == pytest.approx(round(steps)) and \
        steps <= row_steps <= 4 * steps
