"""walk_entries_per_step: table entries the host page walk translated per
decode step: the program's ``HostCounters.entries_walked`` over the decode
steps the run dispatched.  Each wave's prefill walk is included, as in
``walk_ms_per_step``.  A program without the counter reads nothing."""


def read(ctx):
    r = ctx.record
    walked = r.counters.get("entries_walked")
    steps = sum(w.steps for w in r.waves)
    return walked / steps if walked is not None and steps else None
