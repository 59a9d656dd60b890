"""DAGSA-X (compiled) vs host DAGSA: constraints + latency parity."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import WirelessConfig, channel, dagsa, mobility
from repro.core.dagsa_jit import dagsa_schedule_jit
from repro.core.latency import round_latency

CFG = WirelessConfig()


def make_problem(seed, cfg=CFG):
    key = jax.random.PRNGKey(seed)
    k0, k1 = jax.random.split(key)
    st = mobility.init_positions_grid_bs(k0, cfg)
    # one prior participation each -> nobody Eq. (8g)-necessary yet (zero
    # counts at round 0 would make everyone necessary: select-all, no greedy)
    counts = jnp.ones((cfg.n_users,))
    return channel.make_problem(k1, st, cfg, counts, 0)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_jit_dagsa_constraints(seed):
    prob = make_problem(seed)
    res = dagsa_schedule_jit(prob, jax.random.PRNGKey(seed))
    assign = np.asarray(res.assign)
    assert (assign.sum(axis=1) <= 1).all()                  # Eq. (8d)
    assert int(res.selected.sum()) >= prob.min_participants  # Eq. (8h)
    bw_per_bs = (np.asarray(res.bw)[:, None] * assign).sum(axis=0)
    assert (bw_per_bs <= np.asarray(prob.bs_bw) + 1e-3).all()  # Eq. (8f)
    np.testing.assert_allclose(float(round_latency(prob, res)),
                               float(res.t_round), rtol=1e-3)


def test_jit_dagsa_includes_necessary():
    key = jax.random.PRNGKey(3)
    k0, k1 = jax.random.split(key)
    st = mobility.init_positions_grid_bs(k0, CFG)
    counts = jnp.zeros((CFG.n_users,))
    prob = channel.make_problem(k1, st, CFG, counts, 10)  # all necessary
    res = dagsa_schedule_jit(prob, key)
    assert bool(res.selected.all())


def test_jit_dagsa_latency_parity_with_host():
    """Compiled greedy must land within 25% of the host greedy's latency
    (different-but-valid greedy order) and beat Select-All."""
    from repro.core import baselines
    ratios = []
    for seed in range(6):
        prob = make_problem(seed)
        t_host = float(dagsa.dagsa_schedule(prob, seed=seed).t_round)
        t_jit = float(dagsa_schedule_jit(prob,
                                         jax.random.PRNGKey(seed)).t_round)
        t_sa = float(baselines.sa_schedule(prob).t_round)
        assert t_jit < t_sa
        ratios.append(t_jit / t_host)
    assert np.mean(ratios) < 1.25


def test_jit_dagsa_latency_parity_single_bs():
    """Host-vs-jit parity extends to m == 1: both greedy orders are fully
    determined (no feasible-BS choice, no step-4 draw), so the schedules
    must agree exactly."""
    cfg = WirelessConfig(n_bs=1)
    for seed in range(3):
        prob = make_problem(seed, cfg=cfg)
        host = dagsa.dagsa_schedule(prob, seed=seed)
        jit = dagsa_schedule_jit(prob, jax.random.PRNGKey(seed))
        np.testing.assert_array_equal(np.asarray(host.assign),
                                      np.asarray(jit.assign))
        np.testing.assert_allclose(float(host.t_round), float(jit.t_round),
                                   rtol=2e-3)


def test_host_dagsa_single_bs_consumes_no_step4_entropy(monkeypatch):
    """m == 1 regression: the step-4 BS draw is determined, so the host
    greedy must not consume Generator entropy for it (the contract that
    keeps host/jit draw counts in lockstep).  Fails on the pre-fix code,
    which called ``rng.integers(m)`` anyway."""
    from repro.core import dagsa as dagsa_mod
    from repro.core.types import SchedulingProblem

    rng = np.random.default_rng(0)
    n = 12
    snr = jnp.asarray(rng.lognormal(2.0, 2.0, (n, 1)), jnp.float32)
    prob = SchedulingProblem(
        snr=snr, tcomp=jnp.asarray(rng.uniform(0.1, 0.11, n), jnp.float32),
        bs_bw=jnp.ones((1,), jnp.float32), coeff=0.5 / jnp.log2(1.0 + snr),
        necessary=jnp.zeros(n, dtype=bool), min_participants=n // 2)

    real_rng = np.random.default_rng

    def strict_rng(seed=None):
        inner = real_rng(seed)

        class NoIntegers:
            def shuffle(self, *a, **k):       # step-1 order is legitimate
                return inner.shuffle(*a, **k)

            def integers(self, *a, **k):
                raise AssertionError(
                    "step-4 rng.integers consumed entropy on an m==1 "
                    "problem (the draw is determined)")

        return NoIntegers()

    monkeypatch.setattr(dagsa_mod.np.random, "default_rng", strict_rng)
    res = dagsa_mod.dagsa_schedule(prob, seed=3)   # forces step-4 adds
    assert int(res.selected.sum()) >= n // 2


def test_jit_dagsa_vmappable():
    """The point of DAGSA-X: schedule a fleet of cells in one call."""
    probs = [make_problem(s) for s in range(4)]
    snr = jnp.stack([p.snr for p in probs])
    coeff = jnp.stack([p.coeff for p in probs])
    tcomp = jnp.stack([p.tcomp for p in probs])
    bs_bw = jnp.stack([p.bs_bw for p in probs])
    nec = jnp.stack([p.necessary for p in probs])
    keys = jax.random.split(jax.random.PRNGKey(0), 4)

    from repro.core.dagsa_jit import _schedule
    outs = jax.vmap(lambda *a: _schedule(*a[:-1],
                                         probs[0].min_participants, a[-1]),
                    in_axes=(0, 0, 0, 0, 0, 0))(
        snr, coeff, tcomp, bs_bw, nec, keys)
    t_rounds = outs[4]
    assert t_rounds.shape == (4,)
    assert np.isfinite(np.asarray(t_rounds)).all()
