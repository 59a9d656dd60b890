"""peak_hbm_gb: the most HBM the run held, in GB: the device's peak bytes
in use plus its peak bytes reserved after the window (``memory_stats()``,
fullest chip).  The TPU runtime reserves a loaded program's temporaries,
the [N, M] channel planes of a decision among them, apart from the bytes
it counts in use."""


def read(ctx):
    return None if ctx.peak_bytes is None else ctx.peak_bytes / 1e9
