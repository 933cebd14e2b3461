"""The readings that the limit on ``token_gap`` is set from, in one
process on the chip: for each seed, a window of the cell's own traffic at
its own size, then the comparison that decides a benchmark run's
``correct`` (``harness.compare`` and ``harness.is_correct``), once for the
program's served tokens and once for the control's.  The control is the
plain reference computed in float8, put in the program's place at the
same positions of the same requests: the gap of the token it puts first.
The benchmark's own runs never run it.

    python3 benchmarks/chip/control.py --workload qwen3_14b.decode_long \
        --seconds 26 --seeds 101 102 103

The window has to finish the mix's longest requests (decode_long: one
whole wave).  One JSON line per seed, each side's numbers beside their
limits and its ``correct``, then a summary line.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

sys.path[:0] = [str(pathlib.Path(__file__).resolve().parents[2]),
                str(pathlib.Path(__file__).resolve().parents[2] / "src")]


def readings(cell, devices, seeds, seconds: float) -> list:
    """Per seed: the program's and the control's numbers, each (value,
    limit), and the ``correct`` each makes; the tokens compared."""
    import jax
    from benchmarks.chip import harness, traffic
    ref = harness.piece("reference", cell.doc["reference"])
    eng = harness.piece("engines", cell.doc["engine"]).Engine(
        cell.doc, cell.mix, devices, ref)
    out = []
    for i, seed in enumerate(seeds):
        eng.load(seed)
        if i == 0:
            eng.warm_up()
        record = eng.run(traffic.requests(
            cell.mix, seed, cell.doc["model"]["vocab_size"], seconds),
            seconds, traffic.open_loop(cell.mix))
        exact = eng.checks()
        eng.free()
        weights = jax.tree.map(lambda a: a.addressable_shards[0].data,
                               eng.weights)
        row = {"seed": seed}
        for side, control in (("program", False), ("control", True)):
            checks, n = harness.compare(cell, record, exact, weights, ref,
                                        seed, control=control)
            row[side] = {"correct": harness.is_correct(checks),
                         "checks": checks}
        row["tokens_compared"] = n
        del weights
        print(json.dumps(row), flush=True)
        out.append(row)
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    from benchmarks.chip import harness
    from benchmarks.chip.run import enable_compile_cache, require_tpu
    cell = harness.find_cell(harness.load_benchmark(), args.workload)
    devices = require_tpu(cell.entry["chips"])
    enable_compile_cache()
    rows = readings(cell, devices, args.seeds, args.seconds)
    gap = lambda r, side: r[side]["checks"]["token_gap"][0]  # noqa: E731
    print(json.dumps({
        "workload": args.workload,
        "program_max": max(gap(r, "program") for r in rows),
        "control_min": min(gap(r, "control") for r in rows),
        "limit": cell.doc["limits"]["token_gap"],
        "program_correct": [r["program"]["correct"] for r in rows],
        "control_correct": [r["control"]["correct"] for r in rows]}),
        flush=True)


if __name__ == "__main__":
    main()
