"""itl_p95_ms: the 95th percentile of every gap between two consecutive
tokens of one request, as read on the host, in ms.  Offline windows count
the gaps that close inside the window; open loops every gap of every
request due in the window."""
import numpy as np


def read(ctx):
    r = ctx.record
    gaps = []
    for w in r.waves:
        reads = w.reads if r.open_loop else [t for t in w.reads
                                             if t <= r.t_end]
        gaps.extend(np.repeat(np.diff(reads), len(w.rids)))
    return float(np.percentile(gaps, 95) * 1e3) if gaps else None
