"""useful_sgd_share.learning: the share of the local SGD the traced rounds
ran that Eq. (2) kept (%): the rounds' scheduled clients over the clients
whose SGD the round ran, both counted by the program (``n_selected`` and
``n_trained`` of its round records)."""


def read(ctx):
    if ctx.trace is None or "rounds" not in ctx.counters:
        return None
    trained = ctx.counters.get("n_trained")
    if not trained or min(trained) < 1:
        raise RuntimeError(f"the program counted no clients trained "
                           f"({trained!r}) in {ctx.cell.name}")
    return 100.0 * sum(ctx.counters["n_selected"]) / sum(trained)
