"""decode_step_ms: device time of one ``bench_decode`` call, in ms, from
the profiler trace (its module events, averaged over the chips)."""


def read(ctx):
    p = ctx.trace.program("bench_decode")
    return p["s"] / p["calls"] * 1e3 if p["calls"] else None
