"""The one traffic generator: reads a mix's parameters from
``traffic/<mix>.json`` and yields requests with due times, from a seed.

Arrival kinds:

- ``offline``: a queue that is never empty; every request is due when
  the window opens.  Prompts are drawn lazily, as the server admits them.
- ``poisson``: an open loop.  The gaps between arrivals are exponential at
  ``rate_per_s``.  The gaps come from the mix's own ``gap_seed``; the
  run's seed only reorders them inside consecutive blocks of
  ``order_block`` gaps (about one wave's arrivals).  So every seed offers
  the same arrivals in each block, in another order, and a tail of
  latency measures the server, not where one seed put its bursts.

Prompt and output lengths are fixed per mix (``prompt_len``, ``gen_len``):
the wave engine runs a wave in lockstep.  Prompt tokens are uniform over
the configuration's vocabulary.  A seed may be any non-negative integer.
"""
from __future__ import annotations

import dataclasses
import itertools
import json
import pathlib
from typing import Iterator

import numpy as np

TRAFFIC_DIR = pathlib.Path(__file__).resolve().parent / "traffic"


@dataclasses.dataclass
class Request:
    rid: int
    due_s: float            # seconds after the window opens
    prompt: np.ndarray      # [prompt_len] int32
    gen_len: int            # tokens to generate, the prefill's included


def load_mix(name: str) -> dict:
    mix = json.loads((TRAFFIC_DIR / f"{name}.json").read_text())
    mix["name"] = name
    return mix


def open_loop(mix: dict) -> bool:
    return mix["arrival"] != "offline"


def requests(mix: dict, seed: int, vocab: int, seconds: float
             ) -> Iterator[Request]:
    """Requests in order of due time.  Offline mixes never end; open
    loops end with the last arrival due before ``seconds``."""
    rng = np.random.default_rng([seed, 0x7A11])
    P, G = mix["prompt_len"], mix["gen_len"]

    def make(rid, due):
        return Request(rid, due, rng.integers(0, vocab, P, dtype=np.int32), G)

    if mix["arrival"] == "offline":
        return (make(rid, 0.0) for rid in itertools.count())
    if mix["arrival"] == "poisson":
        gaps = poisson_gaps(mix, seconds)
        order_rng = np.random.default_rng([seed, 0x6A95])
        b = mix["order_block"]
        gaps = gaps[np.concatenate([
            i + order_rng.permutation(min(b, len(gaps) - i))
            for i in range(0, len(gaps), b)])]
        # the first request is due when the window opens, the last one
        # gap before it closes
        dues = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        return (make(rid, float(d)) for rid, d in enumerate(dues))
    raise ValueError(f"unknown arrival kind {mix['arrival']!r}")


def poisson_gaps(mix: dict, seconds: float) -> np.ndarray:
    """The mix's fixed set of gaps for a window of ``seconds``:
    ``round(rate_per_s * seconds)`` exponential gaps from ``gap_seed``,
    scaled to fill the window exactly, so the offered rate is
    ``rate_per_s`` in every run."""
    n = max(1, round(mix["rate_per_s"] * seconds))
    gaps = np.random.default_rng(mix["gap_seed"]).exponential(1.0, n)
    return gaps * (seconds / gaps.sum())
