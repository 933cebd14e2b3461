"""Wave engine: drives the program's own paged-KV serving pieces with the
wave policy of ``repro.launch.serve.serve()``.

The program has no request-level API, so this engine calls its pieces:

- ``repro.kvcache.PagedKVManager`` (``start_sequence``, ``maybe_extend``,
  ``physical_tables``, ``finish_sequence``): the host page walk and the
  numaPTE host protocol;
- ``repro.launch.specs.build_prefill_step`` / ``build_serve_step``, jitted
  with the decode state donated, under the names ``bench_prefill`` and
  ``bench_decode`` that the trace reduction looks for;
- on a pod mesh (``server.mesh_pods`` > 1): the ``numapte`` coherence
  prologue in every decode step, fed by
  ``HostBlockManager.drain_pod_buffers``, as ``chip_smoke.pod_mesh_phase``
  does.

The wave policy is serve()'s: admit up to ``batch`` queued requests, pad
the wave with -1 rows, one prefill, ``gen_len - 1`` decode steps (each:
``maybe_extend``, ``physical_tables(record=(t % 4 == 0))``, the step),
then free every sequence of the wave.  Two changes: ``check_invariants``
runs after the window, in the correctness check, and each step's tokens
are read on the host one step behind dispatch, so one step stays in
flight, as a server that streams tokens to its clients must.

What depends on the model's architecture is not here: the adapter of the
configuration's reference (``adapters/<reference>.py``) says which of the
program's configurations the reference fits and maps the reference's
weights into the program's tree.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import sys
import time
from collections import deque
from typing import Callable, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import harness
from repro.configs import get_config, get_smoke_config
from repro.kvcache import PagedKVManager
from repro.launch.specs import (build_prefill_step, build_serve_step,
                                make_rules, state_shardings, with_rules)
from repro.models import init_decode_state, init_params
from repro.pagedpt.blocktable import CoherenceMode

def adapter(doc: dict):
    """The program's side of the configuration's reference
    (``adapters/<reference>.py``): ``check(cfg, model)`` and
    ``to_program(cfg, weights)``."""
    return harness.piece("adapters", doc["reference"])


def program_config(doc: dict):
    """The program's ModelConfig for a configuration file: its published
    config (``program_config``; ``program_size: smoke`` takes the CPU
    smoke preset instead) with the file's weight type and each key the
    file lists under ``reduced``.  Every other size the adapter checks,
    and the head size and activation type, has to be the file's already,
    or this raises."""
    m = doc["model"]
    get = get_smoke_config if doc.get("program_size") == "smoke" \
        else get_config
    cfg = dataclasses.replace(get(doc["program_config"]),
                              param_dtype=jnp.dtype(m["param_dtype"]),
                              **{k: m[k] for k in doc["reduced"]})
    wrong = adapter(doc).check(cfg, m)
    if cfg.resolved_head_dim != m["head_dim"]:
        wrong["head_dim"] = (cfg.resolved_head_dim, m["head_dim"])
    if jnp.dtype(cfg.dtype) != jnp.dtype(m["dtype"]):
        wrong["dtype"] = (cfg.dtype, m["dtype"])
    if wrong:
        raise ValueError(f"the program's {doc['program_config']} differs "
                         f"from the configuration file (program, file): "
                         f"{wrong}")
    return cfg


def check_tree(cfg, params: dict) -> None:
    """Raise unless ``params`` has the tree, shapes and types of the
    program's own ``init_params``."""
    want = jax.eval_shape(functools.partial(init_params, cfg),
                          jax.ShapeDtypeStruct((2,), jnp.uint32))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    if jax.tree.structure(got, is_leaf=lambda x: isinstance(x, tuple)) != \
            jax.tree.structure(want) or jax.tree.leaves(
                got, is_leaf=lambda x: isinstance(x, tuple)) != [
                (a.shape, a.dtype) for a in jax.tree.leaves(want)]:
        raise ValueError("the program's parameter tree changed; the "
                         "adapter no longer fits it")


@dataclasses.dataclass
class Wave:
    rids: List[int]                  # the wave's requests, rows 0..n-1
    admit_t: float                   # admission starts (perf_counter)
    prefill_t: float = 0.0           # prefill dispatch
    finish_s: float = 0.0            # host time freeing the wave
    reads: List[float] = dataclasses.field(default_factory=list)
    tokens: List[np.ndarray] = dataclasses.field(default_factory=list)
    cut: bool = False                # the window closed inside the wave
    steps: int = 0                   # decode steps dispatched


@dataclasses.dataclass
class Record:
    t0: float
    t_end: float
    seconds: float
    open_loop: bool
    requests: Dict[int, object]      # rid -> traffic.Request
    waves: List[Wave]
    counters: dict
    prompt_len: int
    compiles_in_window: int = 0


class WaveEngine:
    def __init__(self, doc: dict, mix: dict, devices: list, ref,
                 span: Callable = None):
        self.doc, self.ref = doc, ref
        self.cfg = cfg = program_config(doc)
        srv = doc["server"]
        self.batch, self.n_pods = srv["batch"], srv["n_pods"]
        self.mode = CoherenceMode(srv["mode"])
        self.mesh_pods = srv.get("mesh_pods", 1)
        self.devices = devices[:self.mesh_pods]
        self.span = span or (lambda name: contextlib.nullcontext())
        self.P, self.G = mix["prompt_len"], mix["gen_len"]
        bt = cfg.kv_block_tokens
        self.max_blocks = -(-(self.P + self.G) // bt) + 1
        # the pool holds every block of a full wave at the longest context
        self.n_frames = self.batch * self.max_blocks
        self.weights = self.params = self.state = self.entries = None
        self._max_frame, self._live, self._finished_one = -1, None, False

        coherence = srv["mode"] if self.mesh_pods > 1 else "none"
        pre_fn = build_prefill_step(cfg)
        step_fn = build_serve_step(cfg, coherence=coherence)
        if self.mesh_pods > 1:
            from repro.launch.mesh import make_mesh
            self.mesh = make_mesh((self.mesh_pods, 1, 1),
                                  ("pod", "data", "model"),
                                  devices=self.devices)
            self.rules = make_rules(cfg, self.mesh)
            pre_fn = with_rules(self.rules, pre_fn)
            step_fn = with_rules(self.rules, step_fn)

        def bench_prefill(params, state, tokens, phys):
            return pre_fn(params, state, tokens, phys)

        def bench_decode(params, state, tokens, phys, *coh):
            return step_fn(params, state, tokens, phys, *coh)

        self.pre = jax.jit(bench_prefill, donate_argnums=(1,))
        self.step = jax.jit(bench_decode, donate_argnums=(1,))

    # ------------------------------------------------------------ placement
    def _on(self, *spec):
        from jax.sharding import NamedSharding, PartitionSpec as P
        return NamedSharding(self.mesh, P(*spec))

    def mesh_scope(self):
        return (jax.set_mesh(self.mesh) if self.mesh_pods > 1
                else contextlib.nullcontext())

    def _put_rows(self, a):
        if self.mesh_pods > 1:
            return jax.device_put(a, self._on(self.rules.lookup("batch")))
        return jax.device_put(a, self.devices[0])

    def home_pod(self, row: int) -> int:
        if self.mesh_pods > 1:
            # a row's batch shard, KV pool and home replica share a chip
            return row * self.n_pods // self.batch
        return row % self.n_pods            # serve()'s placement

    # ------------------------------------------------------------ set-up
    def weight_sharding(self):
        if self.mesh_pods > 1:
            return self._on()
        return jax.sharding.SingleDeviceSharding(self.devices[0])

    def load(self, seed: int) -> None:
        """Weights from ``seed`` and an empty decode state (the old ones
        are dropped first: two sets would not fit a chip)."""
        self.weights = self.params = self.state = None
        self.weights = self.ref.init_weights(self.doc["model"], seed,
                                             self.weight_sharding())
        self.params = adapter(self.doc).to_program(self.cfg, self.weights)
        check_tree(self.cfg, self.params)
        self._new_state()

    def _new_state(self):
        n_pools = self.mesh_pods
        new_state = functools.partial(
            init_decode_state, self.cfg, self.batch,
            self.n_frames * n_pools, self.max_blocks, n_pools=n_pools)
        if self.mesh_pods > 1:
            sh = state_shardings(self.cfg, jax.eval_shape(new_state),
                                 self.mesh, self.rules, sp=False)
        else:
            sh = jax.sharding.SingleDeviceSharding(self.devices[0])
        self.state = jax.jit(new_state, out_shardings=sh)()

    def _new_kv(self) -> PagedKVManager:
        kv = PagedKVManager(n_frames=self.n_frames,
                            block_tokens=self.cfg.kv_block_tokens,
                            max_blocks_per_seq=self.max_blocks,
                            n_pods=self.n_pods, mode=self.mode)
        if self.mesh_pods > 1:
            spec = kv.host.spec
            self.entries = jax.device_put(
                np.full((self.n_pods, spec.n_tables, spec.entries_per_table),
                        -1, np.int32), self._on("pod"))
        return kv

    def free(self) -> None:
        """Drop the decode state (the KV pool); the weights stay."""
        self.state = None
        self.entries = None

    def warm_up(self) -> None:
        """Compile and run the cell's prefill and decode shapes: two waves
        of ``batch`` rows, each a prefill and three decode steps (the
        second wave's prefill takes the state as a decode step left it,
        which the program may lay out otherwise than a fresh one)."""
        from benchmarks.chip.traffic import Request
        rng = np.random.default_rng(0)
        reqs = [Request(i, 0.0, rng.integers(
            0, self.cfg.vocab_size, self.P, dtype=np.int32), 4)
            for i in range(self.batch)]
        with self.mesh_scope():
            kv = self._new_kv()
            for _ in range(2):
                self._finish(kv, self._wave(
                    kv, [r.rid for r in reqs], {r.rid: r for r in reqs},
                    steps=3, t_end=None))
            self._sync_replicas(kv)
            jax.block_until_ready(self.state)

    # ------------------------------------------------------------ the window
    def run(self, reqs, seconds: float, open_loop: bool) -> Record:
        """Serve ``reqs`` (in order of due time) for ``seconds``.  No wave
        is admitted after the close.  An offline window closes inside the
        running wave (the first wave always runs to its end); an open loop
        serves its running wave to its end."""
        from jax import monitoring
        compiles = []

        def on_event(name, *_, **__):
            if "compile" in name or "trace" in name:
                compiles.append(name)

        it = iter(reqs)
        queue: deque = deque()
        known: Dict[int, object] = {}
        waves: List[Wave] = []
        self._max_frame, self._finished_one = -1, False
        monitoring.register_event_duration_secs_listener(on_event)
        try:
            with self.mesh_scope():
                kv = self._new_kv()
                t0 = time.perf_counter()
                t_end = t0 + seconds
                exhausted = False
                live = None         # the last wave, freed at the next one
                while True:
                    now = time.perf_counter()
                    while not exhausted and len(queue) < self.batch:
                        nxt = next(it, None)
                        if nxt is None:
                            exhausted = True
                            break
                        queue.append(nxt)
                        known[nxt.rid] = nxt
                    if now >= t_end:
                        break
                    if not queue:
                        # an open loop that has served every arrival early
                        # still spans the whole window
                        if now < t_end:
                            with self.span("wait"):
                                time.sleep(t_end - now)
                        break
                    if live is not None:
                        self._finish(kv, live)
                        live = None
                    if t0 + queue[0].due_s > now:
                        with self.span("wait"):
                            time.sleep(t0 + queue[0].due_s - now)
                        continue
                    wave = []
                    while queue and len(wave) < self.batch and \
                            t0 + queue[0].due_s <= now:
                        wave.append(queue.popleft().rid)
                    live = self._wave(kv, wave, known, steps=self.G - 1,
                                      t_end=None if open_loop else t_end)
                    waves.append(live)
        finally:
            monitoring.unregister_event_duration_listener(on_event)
        # the last wave stays live for the checks, which free it
        self._kv, self._live = kv, live
        return Record(t0=t0, t_end=t_end, seconds=seconds,
                      open_loop=open_loop, requests=known, waves=waves,
                      counters=dataclasses.asdict(kv.host.counters),
                      prompt_len=self.P,
                      compiles_in_window=len(compiles))

    def _wave(self, kv, rids, known, *, steps: int, t_end) -> Wave:
        span, B, P = self.span, self.batch, self.P
        wave = Wave(list(rids), time.perf_counter())
        with span("admit"):
            for i, rid in enumerate(rids):
                kv.start_sequence(rid, P, pod=self.home_pod(i))
            active = list(rids) + [-1] * (B - len(rids))
            prompts = np.zeros((B, P), np.int32)
            for i, rid in enumerate(rids):
                prompts[i] = known[rid].prompt
        with span("walk"):
            phys = self._walk(kv, active, record=True)
        wave.prefill_t = time.perf_counter()
        with span("prefill"):
            pending, self.state = self.pre(self.params, self.state,
                                           self._put_rows(prompts), phys)
        for t in range(steps):
            with span("walk"):
                for rid in rids:
                    kv.maybe_extend(rid, P + t + 1)
                phys = self._walk(kv, active, record=(t % 4 == 0))
                coh = self._drain(kv)
            with span("decode_dispatch"):
                out = self.step(self.params, self.state, pending, phys, *coh)
            wave.steps += 1
            self.state = out[1]
            if coh:
                self.entries = out[2][0]
            with span("token_read"):
                wave.tokens.append(np.asarray(pending))
                wave.reads.append(time.perf_counter())
            pending = out[0]
            # the window cuts the running wave, unless no wave has finished
            # yet: then it runs on past the close (its later tokens do not
            # count), so that the correctness check has finished requests
            if t_end is not None and wave.reads[-1] >= t_end and \
                    self._finished_one:
                wave.cut = True
                break
        with span("token_read"):
            last = np.asarray(pending)
            if not wave.cut:
                wave.tokens.append(last)
                wave.reads.append(time.perf_counter())
                self._finished_one = True
        return wave

    def _finish(self, kv, wave: Wave) -> None:
        """Free every sequence of ``wave`` (the munmap analogue)."""
        t = time.perf_counter()
        with self.span("finish"):
            for rid in wave.rids:
                kv.finish_sequence(rid)
        wave.finish_s = time.perf_counter() - t

    def _walk(self, kv, active, record):
        phys = kv.physical_tables(active, record=record)
        self._max_frame = max(self._max_frame, int(phys.max()))
        self._last_tables = phys
        return self._put_rows(phys)

    def _drain(self, kv) -> tuple:
        if self.mesh_pods == 1:
            return ()
        host = kv.host
        bufs = [jax.device_put(a, self._on("pod"))
                for a in host.drain_pod_buffers()]
        return (self.entries, jax.device_put(host.sharers, self._on()),
                jax.device_put(host.owner, self._on()), *bufs)

    # ------------------------------------------------------------ checks
    def _sync_replicas(self, kv) -> None:
        """On a pod mesh, apply what the host still holds for the replicas
        (the last wave's frees, mutations over one step's budget): decode
        steps of the compiled program with every row inactive, until a
        drain comes back empty."""
        if self.mesh_pods == 1:
            return
        B = self.batch
        idle = self._put_rows(np.full((B, self.max_blocks), -1, np.int32))
        tokens = self._put_rows(np.zeros((B,), np.int32))
        for _ in range(16):
            coh = self._drain(kv)
            mut_ok, miss = np.asarray(coh[6]), np.asarray(coh[7])
            if not mut_ok.any() and (miss < 0).all():
                return
            out = self.step(self.params, self.state, tokens, idle, *coh)
            self.state, self.entries = out[1], out[2][0]
        raise RuntimeError("the host's coherence buffers did not drain")

    def checks(self) -> Dict[str, tuple]:
        """Exact checks of the last window, each (value, limit), all limits
        0.  The host walk: its invariants and the frames it handed out.  On
        a pod mesh, the device replicas against the host's canonical table
        twice: with the last wave's sequences live, and once they are
        freed; and each KV pool on its own chip."""
        kv, out = self._kv, {}
        stale = missing = 0
        with self.mesh_scope():
            for freeing in (False, True):
                if freeing and self._live is not None:
                    self._finish(kv, self._live)
                    self._live = None
                self._sync_replicas(kv)
                if self.mesh_pods > 1:
                    s, m = self._replica_gaps(kv.host)
                    stale, missing = stale + s, missing + m
        try:
            kv.host.check_invariants()
            out["invariant_errors"] = (0, 0)
        except AssertionError as e:
            print(f"check_invariants: {e}", file=sys.stderr, flush=True)
            out["invariant_errors"] = (1, 0)
        out["frames_out_of_pool"] = (
            int(self._max_frame >= self.n_frames), 0)
        if self.mesh_pods > 1:
            out["stale_replica_entries"] = (stale, 0)
            out["missing_replica_entries"] = (missing, 0)
            off = 0
            for c in self.state.caches:
                for k in ("k_slabs", "v_slabs"):
                    shards = c[k].addressable_shards
                    pools = sorted(s.index[1].start or 0 for s in shards)
                    off += int(pools != list(range(self.mesh_pods))
                               or len({s.device for s in shards})
                               != self.mesh_pods)
            out["pools_not_one_per_chip"] = (off, 0)
        return out

    def _replica_gaps(self, host) -> tuple:
        """(entries a pod holds that differ from the canonical table,
        entries the host counts as present on a pod that its replica
        lacks)."""
        rep = np.asarray(self.entries)
        valid = rep >= 0
        canon = np.broadcast_to(host.canonical, rep.shape)
        return (int((rep[valid] != canon[valid]).sum()),
                int((~valid[host.present]).sum()))

Engine = WaveEngine
