"""coherence_ms_per_step: device time of the collective operations inside
``bench_decode`` per call, in ms, averaged over the chips.  On one chip
there is no exchange and nothing to read."""


def read(ctx):
    p = ctx.trace.program("bench_decode")
    if ctx.chips < 2 or not p["calls"]:
        return None
    return p["collective_s"] / p["calls"] * 1e3
