"""aggregate_s.learning: device seconds of Eq. (2) aggregation a round:
the self time under span ``round.aggregate`` in the traced window (the
sync FedAvg, or a hierarchical round's edge aggregation and its global
sync every ``tau_global`` rounds), over the rounds traced."""

SPANS = ("round.aggregate",)


def read(ctx):
    secs = ctx.span_total(SPANS)
    if secs is None or "rounds" not in ctx.counters:
        return None
    return secs / ctx.counters["rounds"]
