"""decision_s: the window over the decisions made in it; one decision is
mobility, the channel planes, DAGSA selection and the Eq. (11) split."""


def read(ctx):
    if "decisions" not in ctx.counters:
        return None
    return ctx.window_s / ctx.units
