"""decode_roofline: the least time the chip could take for every
decode step the window dispatched, over the device time of the
``bench_decode`` calls in the trace, in %.  A step's least time is the
larger of its model FLOPs over the bf16 peak (the reference's
``token_flops`` for each real row) and the bytes it has to move over the
HBM peak (the reference's ``decode_bytes``: the weights once, each row's
K and V).  Bytes bound it: at batch 16 the FLOP bound is under a tenth of
the byte bound in every cell.  Both are floors, so it reads above 100%
only where a count is too high."""


def read(ctx):
    r, m, counts = ctx.record, ctx.cell.doc["model"], ctx.counts
    p = ctx.trace.program("bench_decode")
    if not p["s"]:
        return None
    flops_per_s = ctx.peaks["bf16_flops_per_s"]
    bytes_per_s = ctx.peaks["hbm_bytes_per_s"]
    least = 0.0
    for w in r.waves:
        for j in range(w.steps):
            # step j feeds position prompt_len + j: every row attends to
            # prompt_len + j + 1 positions
            contexts = [r.prompt_len + j + 1] * len(w.rids)
            least += max(sum(counts.token_flops(m, c) for c in contexts)
                         / flops_per_s,
                         counts.decode_bytes(m, contexts) / bytes_per_s)
    return 100.0 * least / (p["s"] * ctx.chips)
