"""select_s.decision: device seconds of DAGSA's selection a decision: the
self time under span ``dagsa.select`` (each greedy step's best-BS and
candidate passes, ``select_topk``'s kernels on the Pallas route) in the
traced window, over the decisions traced."""

SPANS = ("dagsa.select",)


def read(ctx):
    secs = ctx.span_total(SPANS)
    if secs is None or "decisions" not in ctx.counters:
        return None
    return secs / ctx.counters["decisions"]
