"""mfu.learning: useful model FLOPs of the traced rounds over the chip's
bf16 peak (%).

Useful work is the local SGD of the clients each round scheduled (forward
and backward, 3x the forward FLOPs, over the samples that fill whole
batches, every epoch) plus the round's evaluation forward pass.  SGD of
clients the round did not schedule is discarded by Eq. (2) and does not
count.  ``n_selected`` comes from the run's own records.
"""
from bench import counting


def read(ctx):
    if ctx.trace is None or "rounds" not in ctx.counters:
        return None
    c = ctx.cell.config
    model = c["model"]
    per_user = c["shards_per_user"] * (
        c["n_train"] // (c["n_users"] * c["shards_per_user"]))
    client = counting.client_round_flops(model, per_user, c["batch_size"],
                                         c["local_epochs"])
    evaluation = counting.cnn_forward_flops(model) * c["n_test"]
    rounds = ctx.counters["n_selected"]
    useful = sum(n * client + evaluation for n in rounds)
    return 100.0 * useful / (ctx.trace.window_s
                             * ctx.peaks["bf16_flops_per_s"])
