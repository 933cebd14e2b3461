"""output_tok_per_s: generated tokens of real requests read on the host
inside the window, over the window's length.  Padding rows do not count."""
from benchmarks.chip.harness import tokens_in_window


def read(ctx):
    r = ctx.record
    return tokens_in_window(r) / r.seconds
