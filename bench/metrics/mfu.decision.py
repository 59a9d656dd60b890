"""mfu.decision: the traced decisions' share of the chip's roofline (%).

The work counted is what any implementation must do: write and read the
[N, M] SNR and Eq. (11) coefficient planes once at their storage width,
plus the O(N) state.  It is bound by HBM bytes, so the share is that
traffic at the peak bandwidth over the traced window.
"""
from bench import counting

PLANE_ITEMSIZE = {"f32": 4, "bf16": 2, "int8": 1}


def read(ctx):
    if ctx.trace is None or "decisions" not in ctx.counters:
        return None
    c = ctx.cell.config
    per = counting.decision_bytes(c["n_users"], c["n_bs"],
                                  PLANE_ITEMSIZE[c["channel_dtype"]])
    least = counting.roofline_seconds(
        0.0, per * ctx.counters["decisions"], ctx.peaks)
    return 100.0 * least / ctx.trace.window_s
