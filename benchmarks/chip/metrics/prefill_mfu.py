"""prefill_mfu: the model FLOPs of every prefill's real rows (the
reference's ``prefill_flops``) over the device time of the
``bench_prefill`` calls, as a share of the chips' bf16 peak, in %."""


def read(ctx):
    r, m = ctx.record, ctx.cell.doc["model"]
    p = ctx.trace.program("bench_prefill")
    if not p["s"]:
        return None
    total = sum(ctx.counts.prefill_flops(m, r.prompt_len, len(w.rids))
                for w in r.waves)
    return 100.0 * total / (p["s"] * ctx.chips
                            * ctx.peaks["bf16_flops_per_s"])
