"""walk_ms_per_step: host time of the page walk per decode step, in ms:
the harness's ``walk`` spans (``maybe_extend`` + ``physical_tables``, and
on a pod mesh ``drain_pod_buffers`` with its transfers) over the decode
steps dispatched in the window.  Each wave's prefill walk is included."""


def read(ctx):
    t_end = ctx.record.t_end
    walk = sum(e - s for n, s, e in ctx.spans if n == "walk" and s <= t_end)
    steps = sum(1 for n, s, _ in ctx.spans
                if n == "decode_dispatch" and s <= t_end)
    return walk / steps * 1e3 if steps else None
