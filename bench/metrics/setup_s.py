"""setup_s: seconds from the process's start to the first timed call:
importing, building the world and weights from the seed, compiling (or
loading compiled programs from the cache) and the warm-up call."""


def read(ctx):
    return ctx.setup_s
