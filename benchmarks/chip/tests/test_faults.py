"""Whole runs with the timed path broken underneath, at smoke size on the
CPU: each fault a serving cell can have makes ``correct`` false.

- a decode step that returns its state unchanged;
- half of the batch left out: the second half of the rows gets the first
  half's tokens;
- the exchange between chips left out: the coherence prologue returns the
  replicas it was given (pod mesh, four virtual devices, ``eager``);
- a token altered where it is produced: every row's token at one position.

The run's look for a chip is skipped; everything else is the harness's.
"""
import time

import jax
import jax.numpy as jnp

from benchmarks.chip import harness
from benchmarks.chip.tests import smoke

SEED = 2**31 + 29


def engine_module():
    return harness.load_module(harness.HERE / "engines" / "waves.py")


def run(cell, chips=1, seconds=1.5):
    return harness.run_cell(cell, SEED, seconds, False,
                            jax.devices()[:chips], time.perf_counter(),
                            smoke.PEAK)


def broken_step(monkeypatch, fault):
    eng = engine_module()
    real = eng.build_serve_step

    def build(cfg, **kw):
        step = real(cfg, **kw)

        def wrapped(params, state, tokens, phys, *coh):
            out = step(params, state, tokens, phys, *coh)
            return fault(out, state)
        return wrapped
    monkeypatch.setattr(eng, "build_serve_step", build)


def test_sound_runs_are_correct():
    assert run(smoke.cell())["correct"]


def test_state_returned_unchanged(monkeypatch):
    broken_step(monkeypatch, lambda out, state: (out[0], state) + out[2:])
    assert not run(smoke.cell())["correct"]


def test_half_the_batch_left_out(monkeypatch):
    def fault(out, state):
        tok = out[0]
        half = tok.shape[0] // 2
        return (jnp.concatenate([tok[:half], tok[:half]]),) + out[1:]
    broken_step(monkeypatch, fault)
    assert not run(smoke.cell())["correct"]


def test_token_altered(monkeypatch):
    def fault(out, state):
        at = state.seq_lens == smoke.cell().mix["prompt_len"] + 5
        tok = jnp.where(at, (out[0] + 1) % smoke.MODEL["vocab_size"],
                        out[0])
        return (tok,) + out[1:]
    broken_step(monkeypatch, fault)
    r = run(smoke.cell())
    assert not r["correct"] and r["checks"]["token_gap"]["value"] > 0.05


def test_exchange_between_chips_left_out(monkeypatch):
    # a first wave longer than the window, which runs to its end and is
    # the window's only one: no table is freed and reused, which the
    # program's own faults on a pod mesh need (PERF.md, Open questions)
    cell = smoke.cell(mesh_pods=4, batch=8, gen_len=300)
    cell.doc["server"]["mode"] = "eager"
    assert run(cell, chips=4, seconds=0.2)["correct"]
    from repro.launch import specs
    monkeypatch.setattr(specs, "_coherence_prologue",
                        lambda mode, entries, sharers, *rest: (entries,
                                                               sharers))
    r = run(cell, chips=4, seconds=0.2)
    assert not r["correct"]
    assert r["checks"]["missing_replica_entries"]["value"] > 0
