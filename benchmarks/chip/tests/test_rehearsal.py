"""A CPU rehearsal of whole runs at smoke size: the wave engine, both
traffic kinds, the pod mesh on four virtual devices, the metric readers and
the correctness check, all through ``harness.run_cell``.  The command
itself refuses the CPU, so the harness is called directly; numbers read
here are CPU numbers and are checked only for shape and arithmetic."""
import json
import time

import jax
import numpy as np
import pytest

from benchmarks.chip import harness, trace_reduce, traffic
from benchmarks.chip.engines.waves import Record, Wave
from benchmarks.chip.tests import smoke

SEED = 2**31 + 17          # wider than 32 signed bits


def run(cell, *, trace=False, seconds=1.5, chips=1, capsys=None):
    result = harness.run_cell(cell, SEED, seconds, trace,
                              jax.devices()[:chips], time.perf_counter(),
                              smoke.PEAK)
    if capsys is not None:
        out = capsys.readouterr().out.strip().splitlines()
        assert json.loads(out[-1]) == json.loads(json.dumps(result))
    return result


def test_offline_end_to_end(capsys):
    r = run(smoke.cell(), capsys=capsys)
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {"output_tok_per_s", "itl_p95_ms",
                                 "setup_s"}
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r)[-1] == "checks"
    assert r["checks"]["token_gap"]["value"] < 0.025


def test_open_loop_traced():
    r = run(smoke.cell(arrival="poisson", rate=60.0), trace=True)
    assert r["correct"], r["checks"]
    # the CPU trace has no TPU plane: device metrics read nothing here
    assert "wave_turnaround_ms" in r["metrics"]
    assert "prefill_ms" not in r["metrics"]
    assert r["device"]["window_s"] >= 1.5
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}


def test_pod_mesh_eager_traced():
    # a first wave longer than the window, which runs to its end and is
    # the window's only one: no table is freed and reused, which the
    # program's own faults on a pod mesh need (PERF.md, Open questions)
    cell = smoke.cell(mesh_pods=4, batch=8, gen_len=300)
    cell.doc["server"]["mode"] = "eager"
    r = run(cell, trace=True, chips=4, seconds=0.2)
    assert r["correct"], r["checks"]
    for k in ("stale_replica_entries", "missing_replica_entries",
              "pools_not_one_per_chip"):
        assert r["checks"][k]["value"] == 0
    assert "walk_ms_per_step" in r["metrics"]


def test_pod_mesh_numapte_leaves_stale_entries():
    """The program's fault, witnessed on four virtual devices: once a
    wave is freed, numapte pods keep entries the host has cleared."""
    cell = smoke.cell(mesh_pods=4, batch=8)
    r = run(cell, chips=4)
    assert r["checks"]["stale_replica_entries"]["value"] > 0
    assert not r["correct"]


def test_poisson_gaps_are_one_set_in_another_order():
    mix = dict(smoke.cell(arrival="poisson", rate=50.0).mix)
    a = list(traffic.requests(mix, 1, 512, 4.0))
    b = list(traffic.requests(mix, 2**40 + 3, 512, 4.0))
    assert len(a) == len(b) == 200
    ga, gb = np.diff([r.due_s for r in a]), np.diff([r.due_s for r in b])
    assert not np.allclose(ga, gb)
    # the seed reorders gaps only inside blocks: whole blocks match
    dues_a = np.array([r.due_s for r in a])
    dues_b = np.array([r.due_s for r in b])
    assert np.allclose(dues_a[::16], dues_b[::16])
    assert a[0].due_s == 0 and max(r.due_s for r in a) < 4.0
    c = list(traffic.requests(mix, 1, 512, 4.0))
    assert all((x.prompt == y.prompt).all() and x.due_s == y.due_s
               for x, y in zip(a, c))


def test_metric_arithmetic():
    """Two waves of two rows, offline, window [0, 1]: tokens at 0.1, 0.2,
    0.4 and 0.9, 1.0, 1.5 (the last after the close)."""
    reqs = {i: traffic.Request(i, 0.0, np.zeros(4, np.int32), 3)
            for i in range(4)}
    w1 = Wave([0, 1], 0.0, 0.05, 0.01, [0.1, 0.2, 0.4],
              [np.zeros(2, np.int32)] * 3, steps=2)
    w2 = Wave([2, 3], 0.5, 0.8, 0.01, [0.9, 1.0, 1.5],
              [np.zeros(2, np.int32)] * 3, steps=2)
    rec = Record(0.0, 1.0, 1.0, False, reqs, [w1, w2], {}, 4)
    cell = smoke.cell()
    # the four decode steps took 0.5 s of device time in the trace
    trace = trace_reduce.Reduced(
        window_s=1.0, busy_s=0.9, n_devices=1, ops=[], idle_by_span=[],
        programs={"bench_decode": {"calls": 4, "s": 0.5,
                                   "collective_s": 0.0}})
    ctx = harness.Ctx(cell, rec, [("walk", 0.0, 0.002),
                                  ("decode_dispatch", 0.002, 0.003),
                                  ("walk", 1.2, 1.3)], 5.0, 1, smoke.PEAK,
                      trace)
    read = lambda name: harness.load_module(  # noqa: E731
        harness.HERE / "metrics" / f"{name}.py").read(ctx)
    assert read("output_tok_per_s") == 10          # 5 reads x 2 rows / 1 s
    # gaps 0.1, 0.2, 0.1 (the 0.5 one closes after the window), 2 rows each
    assert read("itl_p95_ms") == pytest.approx(200.0)
    assert read("walk_ms_per_step") == pytest.approx(2.0)
    assert read("setup_s") == 5.0
    # (0.01 + 0.8 - 0.5) s after the first wave
    assert read("wave_turnaround_ms") == pytest.approx(310.0)
    m = cell.doc["model"]
    # each wave's steps feed positions 4 and 5: contexts 5 and 6, 2 rows
    want = 2 * 2 * (smoke_flops(m, 5) + smoke_flops(m, 6))
    assert read("decode_mfu") == pytest.approx(
        100 * want / 0.5 / smoke.PEAK["bf16_flops_per_s"])
    assert read("decode_step_ms") == pytest.approx(125.0)
    assert harness.finished(rec) == [(0, 0), (0, 1), (1, 0), (1, 1)]


def smoke_flops(m, context):
    return harness.piece("reference", "dense_lm").token_flops(m, context)


def test_decode_roofline_arithmetic():
    """The record of ``test_metric_arithmetic``: four decode steps of two
    rows at contexts 5 and 6, in 0.5 s of device time.  At smoke size a
    step's bytes bound it, as at the cells' sizes."""
    reqs = {i: traffic.Request(i, 0.0, np.zeros(4, np.int32), 3)
            for i in range(4)}
    waves = [Wave([2 * i, 2 * i + 1], 0.0, steps=2) for i in range(2)]
    rec = Record(0.0, 1.0, 1.0, False, reqs, waves, {}, 4)
    trace = trace_reduce.Reduced(
        window_s=1.0, busy_s=0.9, n_devices=1, ops=[], idle_by_span=[],
        programs={"bench_decode": {"calls": 4, "s": 0.5,
                                   "collective_s": 0.0}})
    ctx = harness.Ctx(smoke.cell(), rec, [], 0.0, 1, smoke.PEAK, trace)
    m = smoke.MODEL
    # weights once a step, but the embedding table: 2 layers x (wq, wo
    # 64 x 64; wk, wv 64 x 32; w_in, w_gate, w_out 64 x 160; four gains
    # of 64 and 16) + the head 64 x 512 + the final gain, 2 B each
    weights = 2 * (2 * (2 * 64 * 64 + 2 * 64 * 32 + 3 * 64 * 160
                        + 2 * 64 + 2 * 16) + 64 * 512 + 64)
    # two embedding rows, and K + V (2 x 2 heads x 16 x 2 B) per layer
    # and position
    step = {c: weights + 2 * 64 * 2 + 2 * 128 * 2 * c for c in (5, 6)}
    assert harness.piece("reference", "dense_lm").decode_bytes(
        m, [5, 5]) == step[5]
    flops = {c: 2 * smoke_flops(m, c) for c in (5, 6)}
    assert all(flops[c] / smoke.PEAK["bf16_flops_per_s"] < 0.1 * step[c]
               / smoke.PEAK["hbm_bytes_per_s"] for c in (5, 6))
    want = 2 * (step[5] + step[6]) / smoke.PEAK["hbm_bytes_per_s"]
    value = harness.piece("metrics", "decode_roofline").read(ctx)
    assert value == pytest.approx(100 * want / 0.5)
    # a trace with no decode call has nothing to read
    ctx.trace.programs["bench_decode"] = {"calls": 0, "s": 0.0,
                                          "collective_s": 0.0}
    assert harness.piece("metrics", "decode_roofline").read(ctx) is None
