"""Smoke check that the serving path runs on a TPU at qwen3_14b's widths.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the multi-pod coherence step, 4 chips

One chip: ``serve()`` answers 16 random requests (batch 8, 512-token
prompts, 32 generated tokens) with the one-chip qwen3_14b configuration
(every published width, bf16 weights from ``--seed``, 8 of 40 layers) once
per coherence mode.  Every generated token must lie in the vocabulary and
the three modes must generate the same tokens: coherence never changes the
output.  Then prefill + one decode step must reproduce the plain forward
pass's last-position logits, as ``tests/test_models.py`` checks on the CPU.

Four chips: prefill and 4 decode steps with the block-table coherence
prologue (``eager`` and ``numapte``) run on a (pod=4, data=1, model=1)
mesh, the KV pool split per pod and the mutation/miss buffers drained
from the host block manager.  Every token they sample must be the argmax
of the same steps on one device without the pod axis, up to bf16
rounding (``ROUNDING``); every pool must live on its own chip and hold,
frame by frame, the one-device run's KV up to ``KV_ROUNDING``; and every
pod's table replica must agree with the host's.

The script exits non-zero, printing no result, unless JAX finds a TPU.
Tokens/s here is a smoke number, not a benchmark.  The last line of
standard output is the JSON result.
"""
from __future__ import annotations

import argparse
import functools
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np

ARCH = "qwen3_14b"
MODES = ("local", "eager", "numapte")


def require_tpu(n_chips: int) -> list:
    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU: JAX found {len(devices)} "
                 f"{d.platform} device(s) ({d.device_kind})")
    if len(devices) < n_chips:
        sys.exit(f"chip_smoke: needs {n_chips} TPU chips, JAX found "
                 f"{len(devices)}")
    return devices[:n_chips]


def report(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: check failed: {what}")


def serve_phase(seed: int) -> None:
    from repro.configs import get_one_chip_config
    from repro.launch.serve import serve

    vocab = get_one_chip_config(ARCH).vocab_size
    generated = {}
    for mode in MODES:
        r = serve(ARCH, size="one_chip", n_requests=16, prompt_len=512,
                  gen_len=32, batch=8, n_pods=4, mode=mode, seed=seed,
                  verbose=False)
        gen = r["generated"]
        check(gen.shape == (16, 33), f"{mode}: generated {gen.shape}")
        check(bool(((gen >= 0) & (gen < vocab)).all()),
              f"{mode}: a token outside the vocabulary")
        generated[mode] = gen
        report(f"serve_{mode}", n_layers=r["n_layers"],
               param_bytes=r["param_bytes"],
               prefill_compile_s=r["prefill_compile_s"],
               decode_compile_s=r["decode_compile_s"],
               smoke_tok_per_s=r["tok_per_s"],
               invalidations_sent=r["invalidations_sent"],
               invalidations_filtered=r["invalidations_filtered"],
               fetches=r["fetches"], distinct_tokens=len(np.unique(gen)))
    for mode in MODES[1:]:
        check(np.array_equal(generated[mode], generated["local"]),
              f"{mode} generated other tokens than local")
    report("serve_modes_agree", modes=list(MODES))


def reference_phase(seed: int) -> None:
    """Prefill + decode_step logits against forward_lm's at the last
    position: B=2, 64 tokens, the one-chip config and its bf16 weights."""
    from repro.configs import get_one_chip_config
    from repro.models import (decode_step, forward_lm, init_decode_state,
                              init_params, prefill)

    cfg = get_one_chip_config(ARCH)
    params = jax.jit(functools.partial(init_params, cfg))(
        jax.random.PRNGKey(seed))
    B, S = 2, 64
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 1), (B, S), 0,
                                cfg.vocab_size)
    want = jax.jit(lambda p, t: forward_lm(cfg, p, t, remat=False)[0][:, -1]
                   )(params, tokens).astype(jnp.float32)
    mb = -(-S // cfg.kv_block_tokens) + 1
    phys = jnp.arange(B * mb, dtype=jnp.int32).reshape(B, mb)

    @jax.jit
    def cached(p, state, t, ph):
        _, state = prefill(cfg, p, t[:, :-1], state, ph)
        return decode_step(cfg, p, state, t[:, -1], ph)[0]

    got = cached(params, init_decode_state(cfg, B, B * mb, mb), tokens,
                 phys).astype(jnp.float32)
    rel = float(jnp.max(jnp.abs(want - got)) / jnp.max(jnp.abs(want)))
    check(bool(jnp.isfinite(got).all()), "non-finite decode logits")
    # the tolerance of tests/test_models.py::test_decode_matches_forward:
    # bf16 activations, summed in another order by the two paths
    check(rel < 0.03, f"decode vs forward logits: rel error {rel}")
    report("decode_matches_forward", rel_err=rel, logits_shape=got.shape)


#: how far below the one-device run's largest logit (relative to the row's
#: largest magnitude) the token the pod mesh samples may score: one bf16
#: step.  The logits are bf16, ties at the top are common over 151,936
#: rows, and the two runs sum in another order once the batch is split
#: over pods, so a tie may fall either way.  On four v5e chips every
#: token that differed was an exact tie (worst gap 0.0).
ROUNDING = 2.0 ** -8

#: how far a pod's KV pool may stray from the one-device run's, per layer,
#: relative to that layer's largest magnitude in the pool: the bf16
#: tolerance of ``reference_phase`` and ``tests/test_models.py`` for two
#: paths that sum in another order.  On the TPU the mesh's per-chip
#: programs (batch 2) tile their matmuls otherwise than one device's
#: (batch 8), and the rounding compounds with depth: the deepest layer
#: strayed 0.011 on four v5e chips.  A pod reading another pod's pool, or
#: every row reading pool 0, strays 0.9 or more.
KV_ROUNDING = 0.03


def pod_mesh_phase(cfg, devices, *, batch: int = 8, prompt_len: int = 512,
                   steps: int = 4, seed: int = 0) -> dict:
    """Prefill + ``steps`` decode steps with the coherence prologue on a
    (pod=len(devices), data=1, model=1) mesh, per mode, against the same
    steps on ``devices[0]`` alone without the pod axis.

    The one-device run goes first; its greedy tokens are the inputs of
    every decode step of every run, so each step is compared on its own.
    Raises unless every token the mesh samples is the one-device run's
    argmax up to ``ROUNDING``, each pod's KV pool lives on its own device
    and matches the one-device run's pool up to ``KV_ROUNDING``, and every
    pod's block-table replica agrees with the host's canonical table.
    Returns the sampled tokens per run ([steps + 1, batch]) and, per mode,
    the count of tokens equal to the one device's (``exact``, out of
    ``n``), the worst relative logit gap (``worst_gap``) and the worst
    relative KV gap of each layer (``kv_gap``)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.kvcache import PagedKVManager
    from repro.launch.mesh import make_mesh
    from repro.launch.specs import (build_prefill_step, build_serve_step,
                                    make_rules, state_shardings, with_rules)
    from repro.models import (decode_step, init_decode_state, init_params,
                              prefill)
    from repro.pagedpt.blocktable import CoherenceMode

    n_pods = len(devices)
    mesh = make_mesh((n_pods, 1, 1), ("pod", "data", "model"),
                     devices=devices)
    rules = make_rules(cfg, mesh)
    batch_ax = rules.lookup("batch")
    on_mesh = lambda *spec: NamedSharding(mesh, P(*spec))  # noqa: E731
    bt = cfg.kv_block_tokens
    max_blocks = -(-(prompt_len + steps) // bt) + 1
    # each pod's pool holds F frames; the host hands out ids below
    # batch * max_blocks, so every id is a valid frame of every pool
    frames_per_pool = batch * max_blocks
    rows = list(range(batch))
    prompts = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (batch, prompt_len)).astype(np.int32)

    def host_run(mode):
        """Sequence b lives on pod b // (batch / n_pods): its batch row,
        its KV pool and its home replica are on the same chip."""
        kv = PagedKVManager(n_frames=frames_per_pool, block_tokens=bt,
                            max_blocks_per_seq=max_blocks, n_pods=n_pods,
                            mode=CoherenceMode(mode))
        for sid in rows:
            kv.start_sequence(sid, prompt_len, pod=sid * n_pods // batch)
        return kv

    def walk(kv, t):
        for sid in rows:
            kv.maybe_extend(sid, prompt_len + t + 1)
        return kv.physical_tables(rows)

    new_state = functools.partial(init_decode_state, cfg, batch,
                                  n_pods * frames_per_pool, max_blocks,
                                  n_pools=n_pods)

    def kv_slabs(state):
        """The KV pools, [L, n_pods, K, F, bt, hd] each."""
        return [c[k] for c in state.caches for k in sorted(c)
                if k.endswith("_slabs")]

    def kv_gap(state, want, mode):
        """Per layer, the worst gap between ``state``'s pools and
        ``want``'s, over K and V and pool by pool, relative to the layer's
        largest magnitude in that pool."""
        per_slab = []
        for got, ref_pool in zip(kv_slabs(state), want):
            shards = got.addressable_shards
            check(sorted(s.index[1].start for s in shards)
                  == list(range(n_pods))
                  and len({s.device for s in shards}) == n_pods,
                  f"{mode}: the KV pools do not lie one per device")
            worst = np.zeros(got.shape[0])
            for s in shards:
                a = np.asarray(s.data, np.float32)[:, 0]
                b = np.asarray(ref_pool[:, s.index[1].start], np.float32)
                axes = tuple(range(1, b.ndim))
                scale = np.maximum(np.abs(b).max(axes),
                                   np.finfo(np.float32).tiny)
                worst = np.maximum(worst, np.abs(a - b).max(axes) / scale)
            per_slab.append(worst)
        # a layer group's K and V pools come in pairs
        return [float(g) for k, v in zip(per_slab[::2], per_slab[1::2])
                for g in np.maximum(k, v)]

    params = jax.jit(functools.partial(init_params, cfg),
                     out_shardings=on_mesh())(jax.random.PRNGKey(seed))

    # one device, no pod axis (the pools collapse onto it)
    one = jax.tree.map(lambda x: next(s.data for s in x.addressable_shards
                                      if s.device == devices[0]), params)
    put = functools.partial(jax.device_put, device=devices[0])
    ref_pre = jax.jit(lambda p, s, t, ph: prefill(cfg, p, t, s, ph),
                      donate_argnums=(1,))
    ref_step = jax.jit(lambda p, s, t, ph: decode_step(cfg, p, s, t, ph),
                       donate_argnums=(1,))
    kv = host_run("local")
    state = jax.jit(new_state, out_shardings=jax.sharding.SingleDeviceSharding(
        devices[0]))()
    logits, state = ref_pre(one, state, put(prompts),
                            put(kv.physical_tables(rows)))
    ref = [np.asarray(logits, np.float32)]
    for t in range(steps):
        logits, state = ref_step(one, state,
                                 put(ref[-1].argmax(-1).astype(np.int32)),
                                 put(walk(kv, t)))
        ref.append(np.asarray(logits, np.float32))
    ref_kv = jax.device_get(kv_slabs(state))
    ref_lens = np.asarray(state.seq_lens)
    del state
    ref = np.stack(ref)                                   # [steps+1, B, V]
    inputs = ref.argmax(-1).astype(np.int32)
    result = {"tokens": {"one_device": inputs}, "exact": {},
              "worst_gap": {}, "kv_gap": {}, "n": inputs.size}

    pooled = state_shardings(cfg, jax.eval_shape(new_state), mesh, rules,
                             sp=False)
    with jax.set_mesh(mesh):
        pre = jax.jit(with_rules(rules, build_prefill_step(cfg)),
                      donate_argnums=(1,))
        for mode in ("eager", "numapte"):
            step = jax.jit(
                with_rules(rules, build_serve_step(cfg, coherence=mode)),
                donate_argnums=(1,))
            kv = host_run(mode)
            host = kv.host
            spec = host.spec
            entries = jax.device_put(
                np.full((n_pods, spec.n_tables, spec.entries_per_table), -1,
                        np.int32), on_mesh("pod"))
            state = jax.jit(new_state, out_shardings=pooled)()
            tok, state = pre(params, state,
                             jax.device_put(prompts, on_mesh(batch_ax)),
                             jax.device_put(kv.physical_tables(rows),
                                            on_mesh(batch_ax)))
            out = [tok]
            for t in range(steps):
                phys = jax.device_put(walk(kv, t), on_mesh(batch_ax))
                pod_args = [jax.device_put(a, on_mesh("pod"))
                            for a in host.drain_pod_buffers()]
                tok, state, (entries, _) = step(
                    params, state, jax.device_put(inputs[t], on_mesh(batch_ax)),
                    phys, entries, jax.device_put(host.sharers, on_mesh()),
                    jax.device_put(host.owner, on_mesh()), *pod_args)
                out.append(tok)
            gap_kv = kv_gap(state, ref_kv, mode)
            check(np.array_equal(np.asarray(state.seq_lens), ref_lens),
                  f"{mode}: sequence lengths differ from the one device's")
            del state
            result["kv_gap"][mode] = gap_kv
            check(max(gap_kv) <= KV_ROUNDING,
                  f"{mode}: a KV pool on the pod mesh strays {gap_kv} (per "
                  "layer) from the one-device run's")
            toks = np.stack(jax.device_get(out))
            picked = np.take_along_axis(ref, toks[..., None], -1)[..., 0]
            gap = (ref.max(-1) - picked) / np.abs(ref).max(-1)
            result["tokens"][mode] = toks
            result["exact"][mode] = int((toks == inputs).sum())
            result["worst_gap"][mode] = float(gap.max())
            check(float(gap.max()) <= ROUNDING,
                  f"{mode} on the pod mesh sampled a token {gap.max()} below "
                  "the one-device argmax")
            replicas = np.asarray(entries)
            valid = replicas >= 0
            check(bool((replicas[valid] == np.broadcast_to(
                host.canonical, replicas.shape)[valid]).all()),
                  f"{mode}: a pod replica holds a stale entry")
            check(bool(valid[host.present].all()),
                  f"{mode}: a pod lacks an entry the host installed")
            if mode == "eager":
                check(bool((replicas == host.canonical).all()),
                      "eager: a pod replica differs from the canonical table")
    return result


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the multi-pod coherence step on 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    devices = require_tpu(4 if args.four_chips else 1)

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))
    from repro.launch.serve import enable_compile_cache
    enable_compile_cache()
    d = devices[0]
    report("device", platform=d.platform, kind=d.device_kind,
           count=len(jax.devices()))
    if args.four_chips:
        from repro.configs import get_one_chip_config
        r = pod_mesh_phase(get_one_chip_config(ARCH), devices,
                           seed=args.seed)
        report("pod_mesh_matches_one_device", n_tokens=r["n"],
               exact=r["exact"], worst_gap=r["worst_gap"],
               kv_gap=r["kv_gap"],
               tokens={k: v.tolist() for k, v in r["tokens"].items()})
    else:
        serve_phase(args.seed)
        reference_phase(args.seed)
    report("memory", peak_bytes_in_use=[
        dev.memory_stats()["peak_bytes_in_use"] for dev in devices])
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
