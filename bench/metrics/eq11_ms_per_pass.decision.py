"""eq11_ms_per_pass.decision: device milliseconds of an Eq. (11) pass: the
self time under span ``dagsa.eq11`` in the traced window over the passes
the traced decisions made, each decision's greedy steps (the program's
``greedy_steps`` counter) and its final split.  The count is the
program's, never the number of traced kernel calls, so that a pass that
leaves the kernel still counts."""

SPANS = ("dagsa.eq11",)


def read(ctx):
    secs = ctx.span_total(SPANS)
    if secs is None or "decisions" not in ctx.counters:
        return None
    steps = ctx.counters.get("greedy_steps")
    if steps is None or len(steps) != ctx.counters["decisions"]:
        raise RuntimeError(f"the program counted no greedy steps for each "
                           f"traced decision ({steps!r}) in {ctx.cell.name}")
    return 1000.0 * secs / sum(s + 1 for s in steps)
