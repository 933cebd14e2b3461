"""Serving driver: batched decode over the numaPTE paged-KV substrate.

Runs a real request loop: sequences arrive, prefill, decode in lockstep
batches, finish and free — every mutation flowing through the
HostBlockManager so the run reports exact coherence/shootdown counters for
each policy.  ``--size smoke`` (the default) serves the arch's smoke
config, which the CPU tests use; ``--size one_chip`` serves its published
widths cut to one TPU v5e chip (``repro.configs.get_one_chip_config``).

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3_14b \
        --requests 24 --mode numapte
    PYTHONPATH=src python -m repro.launch.serve --arch qwen3_14b \
        --size one_chip --batch 8 --prompt-len 512 --gen-len 32
"""
from __future__ import annotations

import argparse
import functools
import os
import pathlib
import time

import jax
import numpy as np

from ..configs import ARCH_IDS, get_one_chip_config, get_smoke_config
from ..kvcache import PagedKVManager
from ..models import init_decode_state, init_params
from ..pagedpt.blocktable import CoherenceMode
from .specs import build_prefill_step, build_serve_step

SIZES = ("smoke", "one_chip")

#: where compiled programs persist when JAX_COMPILATION_CACHE_DIR is unset:
#: a fixed path inside the checkout, so a later run finds them again
CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> None:
    """Turn on JAX's persistent compilation cache for an entry point.
    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX keeps the cache there
    itself; otherwise it goes to ``CACHE_DIR``."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def model_config(arch: str, size: str):
    if size == "one_chip":
        return get_one_chip_config(arch)
    if size == "smoke":
        return get_smoke_config(arch)
    raise ValueError(f"size must be one of {SIZES}, got {size!r}")


def serve(arch: str, *, size: str = "smoke", n_requests: int = 16,
          prompt_len: int = 32, gen_len: int = 16, batch: int = 4,
          n_pods: int = 4, mode: str = "numapte", seed: int = 0,
          verbose: bool = True):
    """Serve ``n_requests`` random prompts in waves of ``batch``.

    Weights and prompts come from ``seed``.  The result holds the host's
    coherence counters, ``generated`` ([n_requests, gen_len + 1] token
    ids: the prefill's token, then one per decode step), the compile
    seconds of both steps and ``tok_per_s`` over the compiled waves."""
    cfg = model_config(arch, size)
    key = jax.random.PRNGKey(seed)
    # one program with the config's dtype as output: no float32 copy of a
    # whole weight lives on the device
    params = jax.jit(functools.partial(init_params, cfg))(key)
    param_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    bt = cfg.kv_block_tokens
    max_blocks = -(-(prompt_len + gen_len) // bt) + 1
    n_frames = batch * max_blocks * 4
    kv = PagedKVManager(n_frames=n_frames, block_tokens=bt,
                        max_blocks_per_seq=max_blocks, n_pods=n_pods,
                        mode=CoherenceMode(mode))
    state = init_decode_state(cfg, batch, n_frames, max_blocks)

    # both steps consume the decode state (donated: the KV pool is updated
    # in place) and return sampled tokens, so the loop below dispatches
    # nothing but these two compiled programs
    pre = jax.jit(build_prefill_step(cfg), donate_argnums=(1,))
    step = jax.jit(build_serve_step(cfg), donate_argnums=(1,))
    tables = np.full((batch, max_blocks), -1, np.int32)
    tokens = np.zeros((batch,), np.int32)
    t = time.perf_counter()
    pre = pre.lower(params, state, np.zeros((batch, prompt_len), np.int32),
                    tables).compile()
    prefill_compile_s = time.perf_counter() - t
    t = time.perf_counter()
    step = step.lower(params, state, tokens, tables).compile()
    decode_compile_s = time.perf_counter() - t

    generated = np.zeros((n_requests, gen_len + 1), np.int32)
    seq_id = 0
    rng = np.random.default_rng(seed)
    t0 = time.perf_counter()
    while seq_id < n_requests:
        wave = list(range(seq_id, min(seq_id + batch, n_requests)))
        seq_id += len(wave)
        # pad the wave to the fixed batch with inactive rows (-1 tables):
        # their device writes are masked off, so a partial final wave can
        # neither decode into a live sequence's KV frames nor double-count
        # record_access on its blocks
        active = wave + [-1] * (batch - len(wave))
        for i, sid in enumerate(wave):
            kv.start_sequence(sid, prompt_len, pod=i % n_pods)
        prompts = rng.integers(0, cfg.vocab_size,
                               (batch, prompt_len)).astype(np.int32)
        # pod=None: each row walks through its home pod, and the driver
        # pod commits tails through its own replica (cross-pod fetches)
        tokens, state = pre(params, state, prompts,
                            kv.physical_tables(active))
        outs = [tokens]
        for t in range(gen_len):
            for i, sid in enumerate(wave):
                kv.maybe_extend(sid, prompt_len + t + 1)
            phys = kv.physical_tables(active, record=(t % 4 == 0))
            tokens, state = step(params, state, tokens, phys)
            outs.append(tokens)
        generated[wave] = np.stack(jax.device_get(outs), 1)[:len(wave)]
        for sid in wave:
            kv.finish_sequence(sid)      # munmap analogue -> invalidations
        kv.host.check_invariants()
    dt = time.perf_counter() - t0
    c = kv.host.counters
    result = {
        "mode": mode, "n_pods": n_pods, "tokens": n_requests * gen_len,
        "tok_per_s": n_requests * gen_len / dt,
        "invalidations_sent": c.invalidations_sent,
        "invalidations_filtered": c.invalidations_filtered,
        "coherence_bytes": c.coherence_bytes,
        "fetches": c.fetches, "prefetched": c.prefetched,
        # the peak: every table page is freed by the time the run ends
        "table_pages": c.table_pages_peak,
        "n_layers": cfg.n_layers, "param_bytes": param_bytes,
        "prefill_compile_s": prefill_compile_s,
        "decode_compile_s": decode_compile_s,
        "generated": generated,
    }
    if verbose:
        print({k: (round(v, 1) if isinstance(v, float) else v)
               for k, v in result.items() if k != "generated"})
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="qwen3_14b")
    ap.add_argument("--size", choices=SIZES, default="smoke")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--pods", type=int, default=4)
    ap.add_argument("--mode", choices=[m.value for m in CoherenceMode],
                    default="numapte")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()
    serve(args.arch, size=args.size, n_requests=args.requests,
          prompt_len=args.prompt_len, gen_len=args.gen_len,
          batch=args.batch, n_pods=args.pods, mode=args.mode,
          seed=args.seed)


if __name__ == "__main__":
    main()
