"""Regression tests for the serving driver's padding / pod / timer bugs.

Three historical bugs in ``repro.launch.serve``:

* wave padding duplicated the last live seq id to fill the fixed batch,
  so a partial final wave double-walked (and double-wrote) that
  sequence — padding must be inactive rows (seq id -1, all-(-1) tables)
  that the device masks out of update/gather entirely;
* every row was translated through pod 0, so the NUMAPTE modes never
  generated a single cross-pod fetch no matter how many pods the run
  claimed — rows must walk through their *home* pod, with the driver
  pod's tail-block walk supplying the real cross-pod traffic;
* the jitted prefill/decode functions were first called inside the
  timed window, so JIT compile time dominated ``tok_per_s``; both are
  now compiled ahead of it and the window compiles nothing.
"""
from __future__ import annotations

import dataclasses

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.kvcache import PagedKVManager  # noqa: E402
from repro.pagedpt.blocktable import CoherenceMode  # noqa: E402


def _manager(n_pods, **kw):
    return PagedKVManager(n_frames=64, block_tokens=4,
                          max_blocks_per_seq=8, n_pods=n_pods,
                          mode=CoherenceMode("numapte"), **kw)


# --------------------------------------------------------------- padding
def test_padding_rows_are_inert_in_tables_and_counters():
    """A -1 seq id is wave padding: its logical and physical rows are
    all -1, and translating a batch with padding produces *exactly* the
    same host-side counter deltas as translating the live rows alone —
    padding can never double-count record_access (the old duplicate-sid
    bug walked the last live row once per padding slot)."""
    def run(batch_ids):
        kv = _manager(n_pods=2)
        kv.start_sequence(0, prompt_len=12, pod=1)
        assert (kv.logical_tables([-1]) == -1).all()
        tables = kv.physical_tables(batch_ids)
        return tables, dataclasses.asdict(kv.host.counters)

    solo, c_solo = run([0])
    padded, c_pad = run([0, -1, -1, -1])
    assert (padded[0] == solo[0]).all()
    assert (padded[1:] == -1).all()
    assert c_pad == c_solo


def test_padding_rows_never_write_device_kv():
    """Device-side half of the padding fix: rows whose current block is
    unmapped (-1) must leave the KV slabs byte-identical — the old clamp
    redirected their writes into frame 0, corrupting whichever live
    sequence owned it."""
    from repro.kvcache.gather import (commit_token_writes, scatter_prefill,
                                      update_gather_plain)

    F, bt, K, hd, B = 6, 4, 2, 8, 3
    rng = np.random.default_rng(0)
    # heads before frames: [K, F, bt, hd]
    k_slabs = jnp.asarray(rng.normal(size=(K, F, bt, hd)), jnp.float32)
    v_slabs = jnp.asarray(rng.normal(size=(K, F, bt, hd)), jnp.float32)
    k_new = jnp.asarray(rng.normal(size=(B, K, hd)), jnp.float32)
    v_new = jnp.asarray(rng.normal(size=(B, K, hd)), jnp.float32)
    # row 0 live in frame 2; rows 1-2 are padding (all -1 tables)
    phys = jnp.asarray([[2, 3], [-1, -1], [-1, -1]], jnp.int32)
    pos = jnp.zeros((B,), jnp.int32)

    k2, v2, _, _ = update_gather_plain(k_slabs, v_slabs, k_new, v_new,
                                       phys, pos, bt)
    assert jnp.array_equal(k2[:, 2, 0], k_new[0])
    # frames 0 and 1 (and everything but the live write) untouched
    assert jnp.array_equal(k2[:, :2], k_slabs[:, :2])
    assert jnp.array_equal(v2[:, :2], v_slabs[:, :2])

    # stacked-layer commit path
    L = 2
    k_stack = jnp.stack([k_slabs, v_slabs])
    v_stack = jnp.stack([v_slabs, k_slabs])
    kn = jnp.asarray(rng.normal(size=(L, B, K, hd)), jnp.float32)
    vn = jnp.asarray(rng.normal(size=(L, B, K, hd)), jnp.float32)
    ks2, vs2 = commit_token_writes(k_stack, v_stack, kn, vn, phys, pos, bt)
    assert jnp.array_equal(ks2[:, :, :2], k_stack[:, :, :2])
    assert jnp.array_equal(vs2[:, :, :2], v_stack[:, :, :2])
    assert jnp.array_equal(ks2[0, :, 2, 0], kn[0, 0])

    # prefill scatter: padding tokens are dropped, not clamped to frame 0
    S = 4
    kp = jnp.asarray(rng.normal(size=(B, S, K, hd)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(B, S, K, hd)), jnp.float32)
    kp2, vp2 = scatter_prefill(k_stack, v_stack, jnp.stack([kp, vp]),
                               jnp.stack([vp, kp]), phys)
    assert jnp.array_equal(kp2[:, :, :2], k_stack[:, :, :2])
    assert jnp.array_equal(vp2[:, :, :2], v_stack[:, :, :2])
    assert jnp.array_equal(kp2[0, :, 2], jnp.swapaxes(kp[0], 0, 1))


# ----------------------------------------------------------- pod locality
def test_numapte_fetches_nonzero_across_pods():
    """Home-pod translation with the driver-pod tail walk: for n_pods > 1
    the scheduler's walk of each off-driver row's tail block misses its
    local replica and fetches — the cross-pod traffic the coherence
    benchmark measures.  With one pod there is nothing to fetch.  (The
    old bug walked everything through pod 0: fetches were always 0.)"""
    kv = _manager(n_pods=4)
    for sid in range(4):
        kv.start_sequence(sid, prompt_len=12, pod=sid % 4)
    kv.physical_tables([0, 1, 2, 3])
    assert kv.host.counters.fetches > 0
    # the common-case walk stays replica-local (the home pod owns it)
    assert kv.host.counters.translation_local > 0
    kv.host.check_invariants()

    solo = _manager(n_pods=1)
    for sid in range(4):
        solo.start_sequence(sid, prompt_len=12, pod=0)
    solo.physical_tables([0, 1, 2, 3])
    assert solo.host.counters.fetches == 0

    # an explicit pod keeps the legacy single-pod walk: no driver tail walk
    legacy = _manager(n_pods=4)
    for sid in range(4):
        legacy.start_sequence(sid, prompt_len=12, pod=0)
    legacy.physical_tables([0, 1, 2, 3], pod=0)
    assert legacy.host.counters.fetches == 0


def test_serve_partial_final_wave_and_pod_fetches():
    """End-to-end on the real jitted driver: a request count that leaves
    a partial final wave completes cleanly (padding rows inert, host
    invariants checked inside serve), emits exactly n_requests * gen_len
    tokens, and — with multiple pods — reports nonzero NUMAPTE fetches."""
    from repro.launch.serve import serve

    r = serve("qwen3_14b", n_requests=3, prompt_len=8, gen_len=4,
              batch=2, n_pods=2, mode="numapte", verbose=False)
    assert r["tokens"] == 3 * 4
    assert r["n_pods"] == 2
    assert r["fetches"] > 0
    assert r["invalidations_filtered"] >= 0


# ------------------------------------------------------------------ timer
def test_serve_warms_jit_before_timer(monkeypatch):
    """Both steps (prefill and decode) are compiled before the tok_per_s
    window opens, and nothing compiles inside it: the window is the last
    pair of ``time.perf_counter()`` reads, and every XLA compilation the
    run makes comes before the first of them."""
    import time as time_mod

    from repro.launch import serve as serve_mod

    events = []
    real_pc = time_mod.perf_counter

    def spy_pc():
        events.append("timer")
        return real_pc()

    def on_duration(event, duration, **kwargs):
        if event == "/jax/core/compile/backend_compile_duration":
            events.append("compile")

    monkeypatch.setattr(time_mod, "perf_counter", spy_pc)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        r = serve_mod.serve("qwen3_14b", n_requests=2, prompt_len=8,
                            gen_len=2, batch=2, n_pods=1, mode="local",
                            verbose=False)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    timers = [i for i, e in enumerate(events) if e == "timer"]
    assert events[:timers[-2]].count("compile") >= 2
    assert "compile" not in events[timers[-2]:]
    assert r["prefill_compile_s"] > 0 and r["decode_compile_s"] > 0
