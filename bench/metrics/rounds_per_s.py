"""rounds_per_s: simulated FL rounds completed over the whole window."""


def read(ctx):
    if "rounds" not in ctx.counters:
        return None
    return ctx.units / ctx.window_s
