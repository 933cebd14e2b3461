"""expert_roofline: the least time the chip could take for the expert
layer of every decode step the window dispatched, over the device time of
the expert kernel's operations in the trace (``expert_ms_per_step``'s
rule), in %.  A step's least time is the larger of the reference's
``expert_flops`` over the bf16 peak and its ``expert_bytes`` over the HBM
peak, for the rows a step routes here on average (``expert_rows``: each
real row's choices that land on the held experts).  Bytes bound it: every
held expert's three matrices once a layer.  They are a floor where every
held expert is chosen by some row in every step: at 64 rows a router
chose 7.85 of 8 on average on a TPU v5e, so the bytes count about 2%
more than the kernel has to read.  A program without the kernel reads
nothing."""
from benchmarks.chip import harness


def read(ctx):
    s = harness.piece("metrics", "expert_ms_per_step").kernel_s(ctx.trace)
    counts, m = ctx.counts, ctx.cell.doc["model"]
    if not s or not hasattr(counts, "expert_bytes"):
        return None
    least = 0.0
    for w in ctx.record.waves:
        rows = counts.expert_rows(m, len(w.rids))
        least += w.steps * max(
            counts.expert_flops(m, rows) / ctx.peaks["bf16_flops_per_s"],
            counts.expert_bytes(m, rows) / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (s * ctx.chips)
