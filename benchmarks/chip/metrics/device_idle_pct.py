"""Share of the traced window in which no operation ran on the device, in
%: 100 * (1 - union of busy intervals / window), averaged over the chips."""


def read(ctx):
    t = ctx.trace
    return 100.0 * (1.0 - t.busy_s / t.window_s)
