"""decode_mfu: the model FLOPs of every decode step the window dispatched
(the reference's ``token_flops`` for each real row at the step's live
context) over the device time of the ``bench_decode`` calls in the trace,
as a share of the chips' bf16 peak, in %."""


def read(ctx):
    r, m = ctx.record, ctx.cell.doc["model"]
    p = ctx.trace.program("bench_decode")
    if not p["s"]:
        return None
    # step j feeds position prompt_len + j and attends to everything
    # before it and itself
    total = sum(len(w.rids) * ctx.counts.token_flops(m, r.prompt_len + j + 1)
                for w in r.waves for j in range(w.steps))
    return 100.0 * total / (p["s"] * ctx.chips
                            * ctx.peaks["bf16_flops_per_s"])
