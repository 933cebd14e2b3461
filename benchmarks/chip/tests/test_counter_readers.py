"""The readers of the program's own counters: ``walk_entries_per_step``
and ``invalidation_pods_per_round``, by hand-made records and on a CPU
rehearsal of a traced open-loop run at smoke size."""
import math
import time

import jax
import numpy as np
import pytest

from benchmarks.chip import harness, traffic
from benchmarks.chip.engines.waves import Record, Wave
from benchmarks.chip.tests import smoke

SEED = 2**31 + 29


def read(name, record):
    ctx = harness.Ctx(smoke.cell(), record, [], 0.0, 1, smoke.PEAK)
    return harness.load_module(
        harness.HERE / "metrics" / f"{name}.py").read(ctx)


def record(counters, steps=(3, 2)):
    reqs = {i: traffic.Request(i, 0.0, np.zeros(4, np.int32), 4)
            for i in range(2 * len(steps))}
    waves = [Wave([2 * i, 2 * i + 1], 0.0, steps=s)
             for i, s in enumerate(steps)]
    return Record(0.0, 1.0, 1.0, False, reqs, waves, counters, 4)


def test_counter_reader_arithmetic():
    rec = record({"entries_walked": 60, "invalidations_sent": 7,
                  "invalidation_rounds": 4})
    assert read("walk_entries_per_step", rec) == 12.0       # 60 / 5 steps
    assert read("invalidation_pods_per_round", rec) == 1.75
    # a window that freed nothing has no round to average
    assert read("invalidation_pods_per_round", record(
        {"invalidations_sent": 0, "invalidation_rounds": 0})) is None
    # a program without the counters reads nothing, and does not raise
    old = record({"invalidations_sent": 3})
    assert read("walk_entries_per_step", old) is None
    assert read("invalidation_pods_per_round", old) is None


def test_counter_readers_on_a_traced_rehearsal():
    cell = smoke.cell(arrival="poisson", rate=60.0)
    r = harness.run_cell(cell, SEED, 1.5, True, jax.devices()[:1],
                         time.perf_counter(), smoke.PEAK)
    assert r["correct"], r["checks"]
    m = {k: v["value"] for k, v in r["metrics"].items()}
    # every row's table holds its prompt's blocks and at most one block
    # per 16 generated tokens more; padding rows add nothing
    P, G = cell.mix["prompt_len"], cell.mix["gen_len"]
    rows = cell.doc["server"]["batch"]
    assert 0 < m["walk_entries_per_step"] <= rows * math.ceil((P + G) / 16)
    # rows are homed on pod row % 4; the driver pod 0 joins the masks of
    # the rows it walks tails for: 1 pod for row 0, 2 for the others
    assert 1.0 < m["invalidation_pods_per_round"] <= 2.0
    assert r["metrics"]["walk_entries_per_step"]["unit"] == "entries"
    assert r["metrics"]["invalidation_pods_per_round"]["unit"] == "pods"


@pytest.mark.parametrize("mode", ["eager", "local"])
def test_broadcast_modes_reach_every_pod(mode):
    cell = smoke.cell(arrival="poisson", rate=60.0)
    cell.doc["server"]["mode"] = mode
    r = harness.run_cell(cell, SEED, 1.0, True, jax.devices()[:1],
                         time.perf_counter(), smoke.PEAK)
    assert r["correct"], r["checks"]
    assert r["metrics"]["invalidation_pods_per_round"]["value"] == 4.0
