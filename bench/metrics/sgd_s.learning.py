"""sgd_s.learning: device seconds of local SGD a round: the self time of
the operations under span ``round.sgd`` in the traced window
(``bench.spans``: an operation with no ``op_name`` of its own counts under
its nearest named caller), over the rounds traced."""

SPANS = ("round.sgd",)


def read(ctx):
    secs = ctx.span_total(SPANS)
    if secs is None or "rounds" not in ctx.counters:
        return None
    return secs / ctx.counters["rounds"]
