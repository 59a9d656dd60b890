"""idle_share.decision: the share of the traced window in which no
operation ran on the device, in a control-plane cell (%)."""


def read(ctx):
    if ctx.trace is None or "decisions" not in ctx.counters:
        return None
    return 100.0 * ctx.trace.idle_share
