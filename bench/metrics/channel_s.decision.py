"""channel_s.decision: device seconds of mobility and the [N, M] channel
planes a decision: the self time under spans ``round.mobility`` and
``round.channel`` in the traced window, over the decisions traced."""

SPANS = ("round.mobility", "round.channel")


def read(ctx):
    secs = ctx.span_total(SPANS)
    if secs is None or "decisions" not in ctx.counters:
        return None
    return secs / ctx.counters["decisions"]
