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


# ------------------------------------------- compact member-list trials --
def _fleet_problem(seed, necessary_share):
    """N=512 users on M=8 BSs, more users than the compact width holds;
    ``necessary_share`` of them are Eq. (8g)-necessary (step 1)."""
    from repro.core.types import MobilityState
    n, m = 512, 8
    cfg = WirelessConfig(n_users=n, n_bs=m, rho1=0.1, rho2=0.1)
    k_pos, k_bs, k_nec, k_prob = jax.random.split(jax.random.PRNGKey(seed),
                                                  4)
    state = MobilityState(
        user_pos=jax.random.uniform(k_pos, (n, 2)) * cfg.area_m,
        bs_pos=jax.random.uniform(k_bs, (m, 2)) * cfg.area_m)
    nec = jax.random.bernoulli(k_nec, necessary_share, (n,))
    return channel.make_problem(k_prob, state, cfg,
                                jnp.where(nec, 0.0, 10.0), 5)


# name -> (seed, necessary share).  "open" never fills a BS past 7
# members; "seeded" starts with up to 9 necessary users on a BS and ends
# with 14; "open2" is another world like "open".
FLEET = {"open": (0, 0.0), "seeded": (0, 0.06), "open2": (2, 0.0)}
DENSE = 512          # a width >= N: the dense loop alone
BACKENDS = pytest.mark.parametrize("backend", ["jax", "pallas"])


@pytest.fixture(scope="module")
def decide():
    """``decide(backend, world, width)``: the greedy's outputs as numpy,
    with the compact width patched to ``width``; memoised, since every
    width is a compilation of its own."""
    from repro.core import dagsa_jit
    memo = {}

    def run(backend, world, width):
        if (backend, world, width) not in memo:
            p = _fleet_problem(*FLEET[world])
            with pytest.MonkeyPatch.context() as mp:
                mp.setattr(dagsa_jit, "_MEMBER_WIDTH", width)
                jax.clear_caches()
                out = dagsa_jit._schedule(
                    p.snr, p.coeff, p.tcomp, p.bs_bw, p.necessary,
                    p.min_participants, jax.random.PRNGKey(7),
                    backend=backend)
                memo[backend, world, width] = [np.asarray(o) for o in out]
            jax.clear_caches()
        return memo[backend, world, width]
    return run


def _assert_same_decision(got, want):
    np.testing.assert_array_equal(got[0], want[0])          # assign
    np.testing.assert_array_equal(got[1], want[1])          # selected
    np.testing.assert_allclose(got[4], want[4], rtol=1e-5)  # t_round
    assert int(got[5]) == int(want[5])                      # greedy steps


@BACKENDS
@pytest.mark.parametrize("world", ["open", "seeded"])
def test_compact_trials_decide_as_the_dense_mask(decide, backend, world):
    _assert_same_decision(decide(backend, world, 127),
                          decide(backend, world, DENSE))


@BACKENDS
def test_no_dense_step_when_no_bs_fills_the_width(decide, backend):
    for world in ("open", "seeded"):
        *_, steps, dense_steps = decide(backend, world, 127)
        assert int(steps) > 0 and int(dense_steps) == 0
        # the dense loop alone counts every step as dense
        *_, steps, dense_steps = decide(backend, world, DENSE)
        assert int(dense_steps) == int(steps)


@BACKENDS
def test_hand_over_to_the_dense_loop_keeps_the_decision(decide, backend):
    got = decide(backend, "open", 2)
    assert 0 < int(got[6]) < int(got[5])     # both loops took steps
    _assert_same_decision(got, decide(backend, "open", DENSE))


@BACKENDS
def test_necessary_users_past_the_width_go_dense_from_the_start(decide,
                                                                 backend):
    got = decide(backend, "seeded", 4)        # up to 9 seeded on a BS
    assert int(got[6]) == int(got[5]) > 0     # the compact loop took none
    _assert_same_decision(got, decide(backend, "seeded", DENSE))


@BACKENDS
def test_batch_with_one_overflow_equals_single_calls(decide, backend,
                                                     monkeypatch):
    from repro.core import dagsa_jit
    worlds = ["open", "seeded", "open2"]
    singles = [decide(backend, w, 10) for w in worlds]
    # only "seeded" (9 necessary users, 14 at the end) fills the width
    assert [int(s[6]) > 0 for s in singles] == [False, True, False]
    monkeypatch.setattr(dagsa_jit, "_MEMBER_WIDTH", 10)
    jax.clear_caches()
    try:
        res = dagsa_jit.dagsa_schedule_batch(
            [_fleet_problem(*FLEET[w]) for w in worlds],
            jnp.stack([jax.random.PRNGKey(7)] * 3), backend=backend)
    finally:
        jax.clear_caches()
    for i, single in enumerate(singles):
        np.testing.assert_array_equal(np.asarray(res.assign[i]), single[0])
        np.testing.assert_array_equal(np.asarray(res.selected[i]), single[1])
        np.testing.assert_array_equal(np.asarray(res.t_round[i]), single[4])
