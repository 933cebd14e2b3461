"""The knee of an open-loop mix: the highest rate of requests the wave
engine sustains at the mix's shapes, found once by a sweep on the chip.

    python3 benchmarks/chip/knee.py --workload qwen3_14b.chat_poisson \
        --seconds 15 --factors 0.6 0.8 0.9 1.0 1.1

First an offline window at the mix's shapes (a queue that is never
empty) gives the capacity in requests/s; then an open-loop window at each
factor times that capacity gives the tails and whether the queue grows
(the admission lag of the last quarter of arrivals against the first).
One JSON line per window.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

import numpy as np

sys.path[:0] = [str(pathlib.Path(__file__).resolve().parents[2]),
                str(pathlib.Path(__file__).resolve().parents[2] / "src")]


def sweep(cell, devices, seconds: float, factors, seed: int = 1) -> list:
    from benchmarks.chip import harness, traffic
    ref = harness.piece("reference", cell.doc["reference"])
    eng = harness.piece("engines", cell.doc["engine"]).Engine(
        cell.doc, cell.mix, devices, ref)
    eng.load(seed)
    eng.warm_up()
    vocab = cell.doc["model"]["vocab_size"]
    offline = dict(cell.mix, arrival="offline")
    rec = eng.run(traffic.requests(offline, seed, vocab, seconds), seconds,
                  False)
    done = harness.finished(rec)
    waves = [w for w in rec.waves if not w.cut]
    capacity = len(done) / seconds
    rows = [{"window": "offline", "seconds": seconds,
             "requests_finished": len(done), "capacity_req_per_s": capacity,
             "wave_s_mean": float(np.mean([w.reads[-1] - w.admit_t
                                           for w in waves]))}]
    print(json.dumps(rows[-1]), flush=True)
    for f in factors:
        mix = dict(cell.mix, rate_per_s=f * capacity)
        rec = eng.run(traffic.requests(mix, seed, vocab, seconds), seconds,
                      True)
        due = {rid: rec.t0 + r.due_s for rid, r in rec.requests.items()}
        ttft = np.array([w.reads[0] - due[rid] for w in rec.waves
                         for rid in w.rids])
        lag = {rid: w.admit_t - due[rid] for w in rec.waves
               for rid in w.rids}
        order = sorted(lag, key=lambda rid: due[rid])
        q = max(len(order) // 4, 1)
        rows.append({
            "window": "open_loop", "factor": f, "rate_per_s": f * capacity,
            "requests": len(order),
            "ttft_p50_ms": float(np.percentile(ttft, 50) * 1e3),
            "ttft_p95_ms": float(np.percentile(ttft, 95) * 1e3),
            "lag_first_quarter_ms": float(np.mean(
                [lag[r] for r in order[:q]]) * 1e3),
            "lag_last_quarter_ms": float(np.mean(
                [lag[r] for r in order[-q:]]) * 1e3),
            "served_past_close_s": max(w.reads[-1] for w in rec.waves)
            - rec.t_end})
        print(json.dumps(rows[-1]), flush=True)
    return rows


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--factors", type=float, nargs="+", required=True)
    args = ap.parse_args()
    from benchmarks.chip import harness
    from benchmarks.chip.run import enable_compile_cache, require_tpu
    cell = harness.find_cell(harness.load_benchmark(), args.workload)
    devices = require_tpu(cell.entry["chips"])
    enable_compile_cache()
    sweep(cell, devices, args.seconds, args.factors)


if __name__ == "__main__":
    main()
