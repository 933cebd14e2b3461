"""invalidation_pods_per_round: pods one shootdown round of the host
protocol reached, on average: the program's
``HostCounters.invalidations_sent`` over its ``invalidation_rounds`` (a
round per freed sequence).  numaPTE's sharer masks shrink it; a broadcast
reaches every pod.  Nothing to read where no round ran (a window that
freed nothing) or the program lacks the counter."""


def read(ctx):
    c = ctx.record.counters
    rounds = c.get("invalidation_rounds")
    return c["invalidations_sent"] / rounds if rounds else None
