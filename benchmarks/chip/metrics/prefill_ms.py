"""prefill_ms: device time of one ``bench_prefill`` call, in ms, from
the profiler trace."""


def read(ctx):
    p = ctx.trace.program("bench_prefill")
    return p["s"] / p["calls"] * 1e3 if p["calls"] else None
