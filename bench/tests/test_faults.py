"""The harness drives a whole run with the timed path broken underneath
and sees ``correct`` come out false, once for each fault a cell can have:
a step that returns its state unchanged, half of the scheduled clients
left out of the Eq. (2) mean, and an answer (the round's latency) altered
by one percent where the scheduler produces it; in the hierarchical cell
also a global sync left out.  All cells take one chip, so no exchange
between chips exists to leave out."""
import jax
import jax.numpy as jnp
import pytest

from bench.tests.conftest import run_tiny, tiny_cell

SYNC, HFL, DECISION = ("paper-cnn.sync", "paper-cnn.hfl-high-mobility",
                       "mega-fleet.dagsa")


def _first_half(selected):
    rank = jnp.cumsum(selected.astype(jnp.int32))
    return selected & (rank <= (jnp.sum(selected) + 1) // 2)


def unchanged(monkeypatch, cell):
    from repro.fl import rounds
    if cell == SYNC:
        monkeypatch.setattr(rounds, "train_and_aggregate",
                            lambda loss_fn, params, *a, **k: params)
        return
    orig = rounds.hierarchical_round

    def stuck(loss_fn, g, edge, edge_w, prev_bs, *a, **k):
        out = orig(loss_fn, g, edge, edge_w, prev_bs, *a, **k)
        return (g, edge, edge_w) + tuple(out[3:])
    monkeypatch.setattr(rounds, "hierarchical_round", stuck)


def half_batch(monkeypatch, cell):
    from repro.fl import rounds
    name = "train_and_aggregate" if cell == SYNC else "hierarchical_round"
    orig = getattr(rounds, name)
    pos = 5 if cell == SYNC else 9        # the ``selected`` argument

    def halved(*a, **k):
        k["delivered"] = _first_half(a[pos])
        return orig(*a, **k)
    monkeypatch.setattr(rounds, name, halved)


def altered_answer(monkeypatch, cell):
    from repro.core import dagsa_jit
    orig = dagsa_jit._schedule

    def altered(*a, **k):
        out = orig(*a, **k)
        return tuple(out[:4]) + (out[4] * 1.01,) + tuple(out[5:])
    monkeypatch.setattr(dagsa_jit, "_schedule", altered)


def sync_skipped(monkeypatch, cell):
    from repro.fl import rounds
    orig = rounds.hierarchical_round

    def never(*a, **k):
        k["tau_global"] = 10**6
        return orig(*a, **k)
    monkeypatch.setattr(rounds, "hierarchical_round", never)


@pytest.fixture
def fresh_traces():
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("cell,fault", [
    (SYNC, unchanged), (SYNC, half_batch), (SYNC, altered_answer),
    (HFL, unchanged), (HFL, half_batch), (HFL, altered_answer),
    (HFL, sync_skipped),
    (DECISION, altered_answer)],
    ids=lambda x: x if isinstance(x, str) else x.__name__)
def test_fault_makes_the_run_incorrect(cell, fault, monkeypatch,
                                       fresh_traces):
    fault(monkeypatch, cell)
    res = run_tiny(tiny_cell(cell))
    assert res["correct"] is False
    failing = [n for n, c in res["checks"].items()
               if not c["value"] <= c["limit"]]
    assert failing, res["checks"]
