"""setup_s: seconds from the process's start to the window's opening:
loading JAX and the program, making the weights, the KV pool, and the
warm-up wave (which compiles, or loads from the compile cache)."""


def read(ctx):
    return ctx.setup_s
