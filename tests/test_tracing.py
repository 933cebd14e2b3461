"""The serving path's own instrumentation: named scopes in both compiled
steps, profiler spans around the KV manager's calls, and the host
protocol's counters of walk and shootdown work."""
from __future__ import annotations

import dataclasses
import functools
import glob
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_smoke_config
from repro.kvcache import PagedKVManager
from repro.launch.mesh import make_mesh
from repro.launch.specs import (build_prefill_step, build_serve_step,
                                make_rules, with_rules)
from repro.models import init_decode_state, init_params
from repro.pagedpt import BlockTableSpec, HostBlockManager
from repro.pagedpt.blocktable import CoherenceMode

#: the device scopes of the served path, one fixed set of names
SCOPES = {"embed", "attn_qkv", "kv_gather", "attn", "ffn", "kv_commit",
          "kv_scatter", "lm_head", "coherence"}
BATCH, MAX_BLOCKS, FRAMES, PROMPT = 2, 4, 8, 8


def _i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def _optimized_hlo(step: str) -> str:
    """The CPU-optimized HLO of a smoke step: ``decode``, ``prefill``, or
    ``coherence`` (the decode step with the numapte prologue, on a pod
    mesh of this one device)."""
    cfg = get_smoke_config("qwen3_14b")
    params = jax.eval_shape(functools.partial(init_params, cfg),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    state = jax.eval_shape(functools.partial(
        init_decode_state, cfg, BATCH, FRAMES, MAX_BLOCKS))
    tables = _i32(BATCH, MAX_BLOCKS)
    if step == "prefill":
        return jax.jit(build_prefill_step(cfg)).lower(
            params, state, _i32(BATCH, PROMPT), tables).compile().as_text()
    if step == "decode":
        return jax.jit(build_serve_step(cfg)).lower(
            params, state, _i32(BATCH), tables).compile().as_text()
    spec = BlockTableSpec(n_pods=1, n_tables=4, entries_per_table=16)
    mut = _i32(1, spec.mutation_budget)
    coh = (_i32(1, spec.n_tables, spec.entries_per_table),
           jax.ShapeDtypeStruct((spec.n_tables,), jnp.uint32),
           _i32(spec.n_tables), mut, mut, mut,
           jax.ShapeDtypeStruct(mut.shape, jnp.bool_),
           _i32(1, spec.miss_budget))
    mesh = make_mesh((1, 1, 1), ("pod", "data", "model"))
    step_fn = with_rules(make_rules(cfg, mesh),
                         build_serve_step(cfg, coherence="numapte"))
    with jax.set_mesh(mesh):
        return jax.jit(step_fn).lower(params, state, _i32(BATCH), tables,
                                      *coh).compile().as_text()


def _ops(hlo: str):
    """(opcode, result shape, op_name) of every instruction."""
    pat = re.compile(r"= (\w+)\[([\d,]*)\][^ ]* ([a-z][\w-]*)\(.*?"
                     r'op_name="([^"]*)"')
    return [(m.group(3), m.group(2), m.group(4))
            for m in map(pat.search, hlo.splitlines()) if m]


@pytest.mark.parametrize("step,want", [
    ("decode", SCOPES - {"kv_scatter", "coherence"}),
    ("prefill", SCOPES - {"kv_gather", "kv_commit", "coherence"}),
    ("coherence", SCOPES - {"kv_scatter"}),
])
def test_step_hlo_carries_every_scope(step, want):
    ops = _ops(_optimized_hlo(step))
    found = {part for _, _, name in ops for part in name.split("/")}
    assert want <= found, want - found
    # outside the scopes: the scans' loop machinery and the step's own
    # bookkeeping (positions, lengths, layer indices) and nothing else
    rest = {name.split("/", 1)[1] for _, _, name in ops
            if name.startswith("jit(") and not set(name.split("/")) & SCOPES
            and "/while" not in name}
    assert rest <= {"add", "iota", "broadcast_in_dim"}, rest
    # the dry-run byte model's markers stay, inside the scope they model
    for _, _, name in ops:
        if "vmem_paged_attn/" in name or "vmem_attn/" in name:
            assert "attn/vmem_" in name, name


def test_kv_gather_and_commit_are_scoped():
    """Every read of KV blocks out of the cache is ``kv_gather``; every
    write of the decode step into the stacked cache is ``kv_commit``."""
    cfg = get_smoke_config("qwen3_14b")
    block = f"{cfg.kv_block_tokens},{cfg.resolved_head_dim}"
    stack = f"{cfg.n_layers},{cfg.n_kv_heads},{FRAMES},{block}"
    ops = _ops(_optimized_hlo("decode"))
    gathers = [n for op, shape, n in ops
               if op == "gather" and shape.endswith(block)]
    commits = [n for op, shape, n in ops
               if op == "scatter" and shape == stack]
    assert gathers and commits
    assert all("/kv_gather/" in n for n in gathers), gathers
    assert all("/kv_commit/" in n for n in commits), commits


def _manager(n_pods=4, mode="numapte"):
    return PagedKVManager(n_frames=64, block_tokens=4, max_blocks_per_seq=8,
                          n_pods=n_pods, mode=CoherenceMode(mode))


def _host_events(trace_dir):
    from jax.profiler import ProfileData
    out = []
    for path in glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True):
        for plane in ProfileData.from_file(path).planes:
            if plane.name.startswith("/host:"):
                for line in plane.lines:
                    out += [(e.name, dict(e.stats)) for e in line.events]
    return out


def test_kv_spans_carry_their_sequence_in_a_profiler_trace(tmp_path):
    kv = _manager(n_pods=2)
    jax.profiler.start_trace(str(tmp_path))
    try:
        kv.start_sequence(7, prompt_len=12, pod=1)   # 3 blocks of 4
        kv.maybe_extend(7, 12)                       # no block: no span
        kv.maybe_extend(7, 13)                       # a 4th block
        kv.physical_tables([7, -1])
        kv.finish_sequence(7)
    finally:
        jax.profiler.stop_trace()
    events = _host_events(tmp_path)
    by_name = {}
    for name, stats in events:
        by_name.setdefault(name, []).append(stats)
    for name in ("kv.start", "kv.extend", "kv.finish"):
        assert by_name.get(name) == [{"seq": 7, "pod": 1}], (name, by_name)
    assert [s["rows"] for s in by_name["kv.walk"]] == [2]
    assert len(by_name["pt.invalidate"]) == 1


def test_entries_walked_counts_valid_entries_and_padding_adds_nothing():
    def walked(rows, record=True):
        kv = _manager()
        kv.start_sequence(0, prompt_len=12, pod=0)   # 3 blocks
        kv.start_sequence(1, prompt_len=5, pod=1)    # 2 blocks
        kv.maybe_extend(1, 9)                        # 3 blocks
        kv.physical_tables(rows, record=record)
        kv.physical_tables(rows[:1], record=record)
        return kv.host.counters.entries_walked

    assert walked([0, 1]) == 3 + 3 + 3
    assert walked([0, -1, 1, -1]) == 9
    assert walked([0, 1], record=False) == 9


def _serve_like_wave(mode, n_pods=4, rows=8):
    """One wave as serve() and the benchmark run it: row r homed on pod
    r % n_pods, a recorded walk (the driver pod walks every tail), then
    every sequence freed."""
    kv = _manager(n_pods=n_pods, mode=mode)
    for r in range(rows):
        kv.start_sequence(r, prompt_len=6, pod=r % n_pods)
    kv.physical_tables(list(range(rows)))
    for r in range(rows):
        kv.finish_sequence(r)
    kv.host.check_invariants()
    return kv.host.counters


@pytest.mark.parametrize("mode", ["numapte", "eager", "local"])
def test_invalidation_pods_per_round(mode):
    c = _serve_like_wave(mode)
    assert c.invalidation_rounds == 8                # one per free
    per_round = c.invalidations_sent / c.invalidation_rounds
    if mode == "numapte":
        # pod-0 rows reach their home pod; the rest also the driver pod
        assert per_round == 1.75 <= 4
    else:
        assert per_round == 4


def test_table_pages_peak_outlives_the_wave():
    spec = BlockTableSpec(n_pods=4, n_tables=16, entries_per_table=32,
                          miss_budget=8, prefetch_degree=2)
    mgr = HostBlockManager(spec, CoherenceMode.NUMAPTE)
    blocks = mgr.alloc_sequence(0, 4, pod=1)
    mgr.alloc_sequence(1, 4, pod=2)
    mgr.record_access(3, blocks[0])                  # pod 3 joins table
    mgr.check_invariants()                           # count == rescan
    live = mgr.footprint_table_pages()
    assert live == 3
    mgr.free_sequence(0)
    mgr.free_sequence(1)
    mgr.check_invariants()
    assert mgr.footprint_table_pages() == 0
    assert mgr.counters.table_pages_peak == live
    # counters stay deterministic counts: no field is a time
    assert all(isinstance(v, int)
               for v in dataclasses.asdict(mgr.counters).values())


def test_serve_reports_peak_table_pages():
    from repro.launch.serve import serve

    r = serve("qwen3_14b", n_requests=4, prompt_len=8, gen_len=2, batch=2,
              n_pods=2, mode="numapte", verbose=False)
    assert r["table_pages"] > 0
