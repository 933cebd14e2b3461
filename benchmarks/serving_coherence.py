"""TPU-substrate benchmark: block-table coherence traffic per serving mode.

The device-level analogue of Figs 13/14: the same request churn driven
through the real JAX serving path (smoke model on CPU) under LOCAL / EAGER
(Mitosis) / NUMAPTE block-table coherence, reporting exact invalidation
messages, filtered fraction, fetch/prefetch counts, and host coherence
bytes — plus the steady-state per-step collective bytes each mode adds to
the jitted serve step (from repro.pagedpt budget model).
"""
from __future__ import annotations

from repro.launch.serve import serve
from repro.pagedpt import BlockTableSpec, eager_sync_bytes, numapte_fetch_bytes

from .common import csv


N_PODS = 4
#: the serve() result fields each mode's row reports
ROW_FIELDS = ("mode", "n_pods", "tokens", "tok_per_s", "invalidations_sent",
              "invalidations_filtered", "coherence_bytes", "fetches",
              "prefetched", "table_pages")


def main(quick: bool = False) -> list:
    rows = []
    for mode in ("local", "eager", "numapte"):
        r = serve("qwen3_14b", n_requests=8 if quick else 24,
                  prompt_len=32, gen_len=8 if quick else 16, batch=4,
                  n_pods=N_PODS, mode=mode, verbose=False)
        rows.append({k: (round(r[k], 1) if isinstance(r[k], float)
                         else r[k]) for k in ROW_FIELDS})
    # the budget-model row runs the same pod count as the serve rows above
    # (and carries it), so the eager/numapte ratio is comparable to them
    spec = BlockTableSpec(n_pods=N_PODS, n_tables=512)
    rows.append({"mode": "per-step-collective-bytes", "n_pods": N_PODS,
                 "eager": eager_sync_bytes(spec),
                 "numapte": numapte_fetch_bytes(spec),
                 "ratio": round(eager_sync_bytes(spec)
                                / numapte_fetch_bytes(spec), 1)})
    return csv("serving_coherence", rows)


if __name__ == "__main__":
    main()
