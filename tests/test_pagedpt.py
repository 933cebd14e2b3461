"""Host block manager + device block-table substrate tests (incl.
hypothesis sequences over the serving protocol)."""
from __future__ import annotations

import pytest

pytest.importorskip(
    "hypothesis", reason="property tests need the 'test' extra "
    "(pip install -e .[test])")

import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import HealthCheck, given, settings

from repro.pagedpt import (BlockTableSpec, HostBlockManager, lookup_blocks)
from repro.pagedpt.blocktable import CoherenceMode, unpack_entry

SPEC = BlockTableSpec(n_pods=4, n_tables=16, entries_per_table=32,
                      miss_budget=8, prefetch_degree=2)


def test_alloc_translate_free_roundtrip():
    mgr = HostBlockManager(SPEC, CoherenceMode.NUMAPTE)
    blocks = mgr.alloc_sequence(0, 10, pod=1)
    assert len(blocks) == 10
    for b in blocks:
        mgr.record_access(1, b)     # owner: local
    assert mgr.counters.translation_miss == 0
    for b in blocks[:3]:
        mgr.record_access(2, b)     # remote: lazy fetch + prefetch
    assert mgr.counters.fetches >= 1
    assert mgr.counters.prefetched >= 1
    mgr.check_invariants()
    mgr.free_sequence(0)
    mgr.check_invariants()
    assert mgr.footprint_table_pages() == 0


def test_sharer_filter_scopes_invalidations():
    mgr_n = HostBlockManager(SPEC, CoherenceMode.NUMAPTE)
    mgr_e = HostBlockManager(SPEC, CoherenceMode.EAGER)
    for mgr in (mgr_n, mgr_e):
        mgr.alloc_sequence(0, 6, pod=0)
        mgr.free_sequence(0)
    # eager must broadcast to all pods; numaPTE only to the single sharer
    assert mgr_e.counters.invalidations_sent == SPEC.n_pods
    assert mgr_n.counters.invalidations_sent == 1
    assert mgr_n.counters.invalidations_filtered == SPEC.n_pods - 1


def test_drain_pod_buffers_rows_follow_the_owning_pod():
    """Pod p's row carries the mutations of the tables p owned when they
    changed, a free's invalidations included, and the misses p recorded."""
    mgr = HostBlockManager(SPEC, CoherenceMode.NUMAPTE)
    blocks = mgr.alloc_sequence(0, 2, pod=1)
    mgr.alloc_sequence(1, 1, pod=3)
    mgr.record_access(2, blocks[0])
    mgr.free_sequence(0)
    mut_t, mut_i, mut_v, mut_ok, miss = mgr.drain_pod_buffers()
    assert mut_t.shape == mut_v.shape == (SPEC.n_pods, SPEC.mutation_budget)
    assert mut_ok.sum(1).tolist() == [0, 4, 0, 1]
    assert (mut_v[1][mut_ok[1]][:2] >= 0).all()
    assert (mut_v[1][mut_ok[1]][2:] == -1).all()
    assert (mut_v[~mut_ok] == -1).all() and (mut_t[~mut_ok] == 0).all()
    assert miss.shape == (SPEC.n_pods, SPEC.miss_budget)
    assert miss[2, 0] == blocks[0] and (miss[2, 1:] == -1).all()
    assert (miss[[0, 1, 3]] == -1).all()
    assert not mgr.drain_pod_buffers()[3].any()


op = st.tuples(st.sampled_from(["alloc", "extend", "access", "protect",
                                "free"]),
               st.integers(0, 5), st.integers(0, 3), st.integers(1, 8))


@settings(max_examples=50, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(ops=st.lists(op, min_size=3, max_size=40),
       mode=st.sampled_from(list(CoherenceMode)))
def test_host_manager_invariants(ops, mode):
    mgr = HostBlockManager(BlockTableSpec(n_pods=4, n_tables=32,
                                          entries_per_table=16,
                                          prefetch_degree=1), mode)
    live = {}
    next_id = 0
    for kind, sel, pod, n in ops:
        try:
            if kind == "alloc":
                mgr.alloc_sequence(next_id, n, pod)
                live[next_id] = pod
                next_id += 1
            elif kind == "extend" and live:
                sid = list(live)[sel % len(live)]
                mgr.extend_sequence(sid, n)
            elif kind == "access" and live:
                sid = list(live)[sel % len(live)]
                blocks = mgr.seqs[sid].logical_blocks
                mgr.record_access(pod, blocks[(sel + n) % len(blocks)])
            elif kind == "protect" and live:
                sid = list(live)[sel % len(live)]
                mgr.protect_prefix(sid, n)
            elif kind == "free" and live:
                sid = list(live).pop(sel % len(live))
                del live[sid]
                mgr.free_sequence(sid)
        except MemoryError:
            break
        mgr.check_invariants()
    mgr.check_invariants()


def test_device_lookup_matches_host():
    mgr = HostBlockManager(SPEC, CoherenceMode.NUMAPTE)
    blocks = mgr.alloc_sequence(0, 12, pod=0)
    entries = jnp.asarray(mgr.canonical)
    logical = jnp.asarray(blocks, jnp.int32)
    frames, ok = lookup_blocks(entries, logical)
    assert bool(ok.all())
    epb = SPEC.entries_per_table
    for b, f in zip(blocks, np.asarray(frames)):
        raw = mgr.canonical[b // epb, b % epb]
        assert (raw & ((1 << 28) - 1)) == f
    # unmapped / invalid blocks translate to misses
    frames2, ok2 = lookup_blocks(entries, jnp.asarray([-1, 10_000], jnp.int32))
    assert not bool(ok2.any())
    assert (np.asarray(frames2) == -1).all()
