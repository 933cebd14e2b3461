"""Paged KV-cache manager: sequences -> logical blocks -> physical frames.

The serving-side owner of the numaPTE substrate.  Each active sequence holds
a list of *logical* blocks (stable ids, the VMA analogue); the
``HostBlockManager`` maps them to physical KV frames and maintains the
per-pod replicas, sharer masks and invalidation filtering.  Every decode
step translates the logical tables to physical tables (the page walk; on
device via ``repro.kernels.pte_gather`` or ``repro.pagedpt.lookup_blocks``)
and hands the physical tables to the paged-attention kernel.

Each call that changes or walks the tables is a profiler span on the
device trace's clock (``jax.profiler.TraceAnnotation``): ``kv.start`` and
``kv.finish`` per sequence, ``kv.extend`` when a block is added, ``kv.walk``
per ``physical_tables``.  Outside a trace a span costs about a microsecond.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np
from jax.profiler import TraceAnnotation

from ..pagedpt import BlockTableSpec, HostBlockManager
from ..pagedpt.blocktable import CoherenceMode


class PagedKVManager:
    """Host-side manager for a fixed-capacity paged KV pool."""

    def __init__(self, *, n_frames: int, block_tokens: int = 16,
                 max_blocks_per_seq: int, n_pods: int = 1,
                 mode: CoherenceMode = CoherenceMode.NUMAPTE,
                 entries_per_table: int = 512, prefetch_degree: int = 3):
        # table pages are metadata (one per active sequence at minimum, each
        # sequence opens its own VMA/table): keep a healthy pool
        n_tables = max(64, -(-n_frames // entries_per_table))
        self.spec = BlockTableSpec(
            n_pods=n_pods, n_tables=n_tables,
            entries_per_table=entries_per_table,
            prefetch_degree=prefetch_degree)
        self.host = HostBlockManager(self.spec, mode,
                                     block_tokens=block_tokens)
        self.block_tokens = block_tokens
        self.max_blocks = max_blocks_per_seq
        self.n_frames = n_frames
        self._seq_pod: Dict[int, int] = {}
        #: the scheduler's pod: it walks every row's tail block to commit
        #: appended tokens (see ``physical_tables``)
        self.driver_pod = 0

    # ------------------------------------------------------------- lifecycle
    def start_sequence(self, seq_id: int, prompt_len: int, pod: int = 0
                       ) -> None:
        n_blocks = max(1, -(-prompt_len // self.block_tokens))
        with TraceAnnotation("kv.start", seq=seq_id, pod=pod):
            self.host.alloc_sequence(seq_id, n_blocks, pod)
        self._seq_pod[seq_id] = pod

    def maybe_extend(self, seq_id: int, new_len: int) -> None:
        have = len(self.host.seqs[seq_id].logical_blocks)
        need = -(-new_len // self.block_tokens)
        if need > have:
            with TraceAnnotation("kv.extend", seq=seq_id,
                                 pod=self._seq_pod[seq_id]):
                self.host.extend_sequence(seq_id, need - have)

    def finish_sequence(self, seq_id: int) -> None:
        pod = self._seq_pod.pop(seq_id, None)
        with TraceAnnotation("kv.finish", seq=seq_id, pod=pod):
            self.host.free_sequence(seq_id)

    # ------------------------------------------------------------ tables
    def logical_tables(self, seq_ids: List[int]) -> np.ndarray:
        """[len(seq_ids), max_blocks] logical block ids, -1 padded.  A
        negative seq id is an inactive batch row (wave padding): its table
        stays all -1 so the device masks it out of update and gather."""
        out = np.full((len(seq_ids), self.max_blocks), -1, np.int32)
        for r, sid in enumerate(seq_ids):
            if sid < 0:
                continue
            blocks = self.host.seqs[sid].logical_blocks
            out[r, :len(blocks)] = blocks[:self.max_blocks]
        return out

    def physical_tables(self, seq_ids: List[int],
                        pod: Optional[int] = None,
                        record: bool = True) -> np.ndarray:
        """Translate to physical frame ids (the page walk).

        ``pod=None`` (the serving default) walks each row through its
        *home* pod — the attention shard that owns the sequence's pool, so
        the common-case walk is replica-local — and additionally records
        the driver pod's walk of the row's tail block (the scheduler
        commits the appended token through its own replica).  The driver
        walks are what generate real cross-pod fetch/prefetch traffic
        under NUMAPTE once sequences are homed off pod 0.  An explicit
        ``pod`` keeps the legacy single-pod walk.  Misses trigger the
        numaPTE on-demand fetch protocol; negative seq ids (padding rows)
        are skipped entirely.  Every valid entry translated counts in
        ``HostCounters.entries_walked``."""
        with TraceAnnotation("kv.walk", rows=len(seq_ids), record=record):
            logical = self.logical_tables(seq_ids)
            epb = self.spec.entries_per_table
            # padding rows are all -1: they add nothing
            self.host.counters.entries_walked += int((logical >= 0).sum())
            out = np.full_like(logical, -1)
            for r, sid in enumerate(seq_ids):
                if sid < 0:
                    continue
                walk_pod = self._seq_pod[sid] if pod is None else pod
                tail_lb = -1
                for c in range(logical.shape[1]):
                    lb = int(logical[r, c])
                    if lb < 0:
                        continue
                    if record:
                        self.host.record_access(walk_pod, lb)
                    tid, slot = divmod(lb, epb)
                    raw = int(self.host.canonical[tid, slot])
                    out[r, c] = raw & ((1 << 28) - 1) if raw >= 0 else -1
                    tail_lb = lb
                if (pod is None and record and tail_lb >= 0
                        and walk_pod != self.driver_pod):
                    self.host.record_access(self.driver_pod, tail_lb)
            return out
