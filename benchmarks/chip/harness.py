"""The benchmark's core, below the command line: finds a cell's pieces by
name, builds the engine, times set-up, runs the measured window (traced or
not), reads the cell's metrics through their readers, decides ``correct``
against the plain reference, and prints the result.

Every piece is found by the name ``BENCHMARK.json`` gives it:

- ``configs/<config>.json``: the configuration as run; it names its
  ``engine`` (``engines/<engine>.py``) and its ``reference``;
- ``reference/<reference>.py``: the plain reference, which makes the
  weights from the seed, computes the logits the served tokens are judged
  by, and counts the model's FLOPs and bytes beside its equations;
- ``adapters/<reference>.py``: the program's side of that reference:
  which program configuration it fits, and its weights in the program's
  tree;
- ``traffic/<mix>.json``: the mix's parameters, read by ``traffic.py``;
- ``metrics/<metric>.py``: one reader per metric, ``read(ctx)`` returning
  a number or None (nothing to read here).

So a configuration of another architecture joins as new files: its
configuration, reference, adapter and metrics.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import pathlib
import sys
import time
from typing import List, Optional

import numpy as np

from benchmarks.chip import traffic, trace_reduce

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def load_module(path: pathlib.Path):
    """A piece of the benchmark from its file (names may hold dots)."""
    name = "bench_" + "_".join(path.relative_to(HERE).with_suffix("").parts
                               ).replace(".", "_")
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


def piece(kind: str, name: str):
    """The piece ``<kind>/<name>.py`` of the benchmark."""
    return load_module(HERE / kind / f"{name}.py")


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


@dataclasses.dataclass
class Cell:
    name: str
    entry: dict               # the workload entry
    doc: dict                 # the configuration file
    mix: dict                 # the traffic mix
    end_to_end: List[dict]    # metric entries this cell reports
    per_layer: List[dict]


def find_cell(bench: dict, name: str, root: pathlib.Path = ROOT) -> Cell:
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"no workload named {name!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    doc = json.loads((root / conf["file"]).read_text())

    def mine(metric):
        return name in metric.get("workloads", [name])

    return Cell(name, entry, doc, traffic.load_mix(entry["traffic"]),
                [m for m in bench["end_to_end"] if mine(m)],
                [m for m in bench["per_layer"] if mine(m)])


class Spans:
    """The harness's host spans around each call into the program, kept in
    memory and written into the profiler's trace (``TraceAnnotation``) so
    that they share the device trace's clock."""

    NAMES = ("admit", "prefill", "walk", "decode_dispatch", "token_read",
             "finish", "wait", "window")

    def __init__(self):
        import jax
        self._annotate = jax.profiler.TraceAnnotation
        self.log: List[tuple] = []          # (name, start_s, end_s)

    @contextlib.contextmanager
    def __call__(self, name: str):
        t = time.perf_counter()
        with self._annotate(name):
            yield
        self.log.append((name, t, time.perf_counter()))


#: the longest window a ``--trace 1`` run traces.  The TPU profiler drops
#: device operations past a limit: 51 s of chat waves overflow it, a 24 s
#: decode window (2.3 million operations) does not.  Every per-layer
#: metric is a rate or a share, so a shorter window reads the same.
TRACE_SECONDS = 20.0


def trace_options():
    """The profiler's options: the host's annotations (the spans) and the
    device's operations, but not every Python call, which the profiler
    traces by default: on a TPU v5e host that tracer slowed qwen3_14b
    decode_long's walk from 1.66 to 2.86 ms a step and put 3.4 million
    events in a 24 s trace."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def check_trace_whole(reduced, record) -> None:
    """Raise if the trace holds fewer program calls than the window
    dispatched: a truncated trace reads the device idle and its rates
    high.  (The CPU backend traces no device plane: nothing to check.)"""
    if not reduced.n_devices:
        return
    want = {"bench_decode": sum(w.steps for w in record.waves),
            "bench_prefill": len(record.waves)}
    got = {k: reduced.program(k)["calls"] for k in want}
    if any(got[k] < want[k] for k in want):
        raise RuntimeError(f"the device trace lost events: program calls "
                           f"{got}, dispatched {want}")


@dataclasses.dataclass
class Ctx:
    """What a metric reader may read."""
    cell: Cell
    record: object                     # the engine's Record
    spans: List[tuple]
    setup_s: float
    chips: int
    peaks: dict
    trace: Optional[trace_reduce.Reduced] = None

    @property
    def counts(self):
        """The cell's reference module, whose ``token_flops``,
        ``prefill_flops`` and ``decode_bytes`` count the model's work."""
        return piece("reference", self.cell.doc["reference"])


def tokens_in_window(record) -> int:
    """Tokens of real requests read on the host inside the window."""
    n = 0
    for w in record.waves:
        n += len(w.rids) * sum(1 for t in w.reads if t <= record.t_end)
    return n


def finished(record) -> List[tuple]:
    """(wave index, row) of every request that got all its tokens."""
    return [(i, r) for i, w in enumerate(record.waves)
            if not w.cut and len(w.tokens) == record.requests[
                w.rids[0]].gen_len for r in range(len(w.rids))]


def sample_finished(record, n: int, seed: int) -> List[tuple]:
    """``n`` finished requests drawn from the seed, one from each of ``n``
    equal groups of batch rows (on a pod mesh: one per pod)."""
    rng = np.random.default_rng([seed, 0xC4EC])
    done = finished(record)
    out = []
    for g in range(n):
        group = [(i, r) for i, r in done
                 if r * n // len(record.waves[i].rids) == g]
        if group:
            out.append(group[rng.integers(len(group))])
    return out


def token_gap(cell: Cell, record, weights, ref, seed: int,
              control: bool = False) -> tuple:
    """The widest relative logit gap of a served token below the plain
    reference's best, over a sample of finished requests; and how many
    tokens were compared.  ``control``: the gap of the token the fp8
    reference puts first at the same positions instead.  With no
    finished request to compare it reads 2.0, the most a relative gap
    can be, so the run is not correct."""
    # some hundreds of served tokens, and on a pod mesh one row per pod
    n = max(cell.mix["check_requests"],
            cell.doc["server"].get("mesh_pods", 1))
    picks = sample_finished(record, n, seed)
    if not picks:
        return 2.0, 0
    worst, n = 0.0, 0
    for i, r in picks:
        w = record.waves[i]
        req = record.requests[w.rids[r]]
        served = np.stack(w.tokens)[:, r]
        gaps = ref.logit_gaps(cell.doc["model"], weights, req.prompt, served,
                              control=control)
        worst, n = max(worst, float(np.max(gaps))), n + len(gaps)
    return worst, n


def out_of_vocab(record, vocab: int) -> int:
    bad = 0
    for w in record.waves:
        if w.tokens:
            toks = np.stack(w.tokens)[:, :len(w.rids)]
            bad += int(((toks < 0) | (toks >= vocab)).any(0).sum())
    return bad


def compare(cell: Cell, record, checks: dict, weights, ref, seed: int,
            control: bool = False) -> tuple:
    """Every number that decides ``correct``, each (value, limit): the
    engine's exact ``checks``, the served tokens in the vocabulary, and
    ``token_gap`` (``control``: the fp8 reference's tokens in the served
    ones' place); and how many tokens were compared."""
    out = dict(checks)
    out["tokens_out_of_vocab"] = (
        out_of_vocab(record, cell.doc["model"]["vocab_size"]), 0)
    gap, n = token_gap(cell, record, weights, ref, seed, control=control)
    out["token_gap"] = (gap, cell.doc["limits"]["token_gap"])
    return out, n


def is_correct(checks: dict) -> bool:
    return all(v <= lim for v, lim in checks.values())


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, devices,
             t_start: float, peaks: dict) -> dict:
    import jax

    marks = {"start": time.perf_counter() - t_start}
    ref = piece("reference", cell.doc["reference"])
    eng_mod = piece("engines", cell.doc["engine"])
    spans = Spans()
    engine = eng_mod.Engine(cell.doc, cell.mix, devices, ref, span=spans)
    marks["engine"] = time.perf_counter() - t_start
    engine.load(seed)
    marks["load"] = time.perf_counter() - t_start
    engine.warm_up()
    marks["warm_up"] = time.perf_counter() - t_start
    # what set-up made lives as long as the process: keep the collector
    # from walking it again in the window (servers do the same after
    # warm-up); collections of what the window allocates still run
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start
    pauses = GcPauses()

    if trace:
        seconds = min(seconds, TRACE_SECONDS)
    reqs = traffic.requests(cell.mix, seed, cell.doc["model"]["vocab_size"],
                            seconds)
    spans.log.clear()
    reduced = None
    if trace:
        session = trace_reduce.Session(trace_options())
        with spans("window"):
            record = engine.run(reqs, seconds, traffic.open_loop(cell.mix))
        pauses.stop()
        reduced = trace_reduce.reduce(
            trace_reduce.events_from_xspace(session.stop(), Spans.NAMES),
            Spans.NAMES)
        check_trace_whole(reduced, record)
    else:
        record = engine.run(reqs, seconds, traffic.open_loop(cell.mix))
        pauses.stop()
    used = engine.devices
    checks = engine.checks()
    # the CPU backend reports no memory statistics (rehearsals only)
    mem_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in used)

    ctx = Ctx(cell, record, spans.log, setup_s, len(used), peaks, reduced)
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        value = piece("metrics", m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    # correctness, once the window has closed and the pool is freed
    engine.free()
    t_check = time.perf_counter()
    weights = jax.tree.map(lambda a: a.addressable_shards[0].data,
                           engine.weights)
    checks, n_compared = compare(cell, record, checks, weights, ref, seed)
    check_s = time.perf_counter() - t_check
    bad = checks["tokens_out_of_vocab"][0]
    correct = is_correct(checks)

    d = used[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem_peak}
    result = {"correct": correct,
              "attempted": sum(len(w.rids) for w in record.waves),
              "failed": bad, "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["breakdown"] = reduced.breakdown()
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}

    print(json.dumps({"counters": record.counters,
                      "compiles_in_window": record.compiles_in_window,
                      "waves": len(record.waves),
                      "tokens_in_window": tokens_in_window(record),
                      "admission_lag_ms_p95": admission_lag_p95(record),
                      "longest_gaps": longest_gaps(record),
                      "gc_s_in_window": pauses.total_s,
                      "tokens_compared": n_compared,
                      "reference_s": check_s,
                      "setup_marks_s": marks,
                      "setup_s": setup_s}), flush=True)
    for k, (v, lim) in checks.items():
        print(f"check {k}: {v} (limit {lim})", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return result


class GcPauses:
    """Seconds the garbage collector held the process, from now until
    ``stop``."""

    def __init__(self):
        self.total_s, self._t = 0.0, None
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.total_s += time.perf_counter() - self._t

    def stop(self):
        gc.callbacks.remove(self._on)


def longest_gaps(record, n: int = 3) -> List[dict]:
    """The ``n`` longest gaps between two tokens of one request inside the
    window (the stalls that a tail hides): ms, wave, the token that ended
    it, and when it ended, s after the window opened."""
    gaps = [(b - a, i, j + 1, b - record.t0)
            for i, w in enumerate(record.waves)
            for j, (a, b) in enumerate(zip(w.reads, w.reads[1:]))
            if b <= record.t_end]
    return [{"ms": g * 1e3, "wave": i, "token": j, "at_s": at}
            for g, i, j, at in sorted(gaps, reverse=True)[:n]]


def admission_lag_p95(record) -> Optional[float]:
    """How long after its due time a request was admitted into a wave, ms
    (the arrivals are due times, so the generator itself is never late;
    this is the queueing before a wave)."""
    lags = [w.admit_t - (record.t0 + record.requests[r].due_s)
            for w in record.waves for r in w.rids]
    return float(np.percentile(lags, 95) * 1e3) if lags else None
