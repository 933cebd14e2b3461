"""End-to-end behaviour tests for the whole system (paper mechanism
composed with the serving/training stack)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_smoke_config
from repro.core import NumaSim, PAPER_8SOCKET, Policy
from repro.launch.serve import serve
from repro.models import greedy_sample


def test_end_to_end_serving_generates_same_tokens_under_all_policies():
    """Coherence policy is performance-transparent: generated tokens are
    identical under LOCAL / EAGER / NUMAPTE (translation correctness)."""
    outs = {}
    for mode in ("local", "eager", "numapte"):
        outs[mode] = serve("gemma3_4b", n_requests=4, prompt_len=20,
                           gen_len=5, batch=2, n_pods=2, mode=mode,
                           verbose=False)
    assert len({o["tokens"] for o in outs.values()}) == 1
    for mode in ("eager", "numapte"):
        np.testing.assert_array_equal(outs[mode]["generated"],
                                      outs["local"]["generated"])


def test_numapte_scales_with_sockets():
    """The mprotect cost under numaPTE is independent of the number of
    OTHER sockets running threads (the paper's scalability claim)."""
    def cost(n_busy_sockets):
        sim = NumaSim(PAPER_8SOCKET, Policy.NUMAPTE, tlb_filter=True)
        main = sim.spawn_thread(0)
        for node in range(1, 1 + n_busy_sockets):
            t = sim.spawn_thread(node * sim.topo.hw_threads_per_node)
            v = sim.mmap(t, 1)
            sim.touch(t, v.start_vpn, write=True)
        vma = sim.mmap(main, 1)
        sim.touch(main, vma.start_vpn, write=True)
        t0 = sim.thread_time_ns(main)
        from repro.core.pagetable import PERM_R
        for _ in range(50):
            sim.mprotect(main, vma.start_vpn, 1, PERM_R)
        return sim.thread_time_ns(main) - t0

    assert abs(cost(7) - cost(1)) / cost(1) < 0.02


def test_linux_does_not_scale():
    def cost(policy, n_busy):
        sim = NumaSim(PAPER_8SOCKET, policy)
        main = sim.spawn_thread(0)
        for node in range(1, 1 + n_busy):
            for i in range(8):
                t = sim.spawn_thread(node * sim.topo.hw_threads_per_node + i)
                v = sim.mmap(t, 1)
                sim.touch(t, v.start_vpn, write=True)
        vma = sim.mmap(main, 1)
        sim.touch(main, vma.start_vpn, write=True)
        from repro.core.pagetable import PERM_R
        t0 = sim.thread_time_ns(main)
        for _ in range(50):
            sim.mprotect(main, vma.start_vpn, 1, PERM_R)
        return sim.thread_time_ns(main) - t0

    assert cost(Policy.LINUX, 7) > 1.5 * cost(Policy.LINUX, 1)
