"""Fleet-scale scheduling engine tests: safeguarded Newton Eq. (11) solver
parity, warm-started brackets, batched DAGSA-X equivalence, determinism."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import WirelessConfig, channel, dagsa, mobility, schedule_batch
from repro.core import bandwidth
from repro.core.dagsa_jit import (dagsa_schedule_batch, dagsa_schedule_jit,
                                  stack_problems)
from repro.core.types import SchedulingProblem
from repro.kernels.bandwidth_solve import bandwidth_solve

CFG = WirelessConfig()


def make_problem(seed):
    key = jax.random.PRNGKey(seed)
    k0, k1 = jax.random.split(key)
    st = mobility.init_positions_grid_bs(k0, CFG)
    # one prior participation each -> nobody Eq. (8g)-necessary yet (zero
    # counts at round 0 would make everyone necessary: a trivial greedy)
    return channel.make_problem(k1, st, CFG, jnp.ones((CFG.n_users,)), 0)


def _kkt_resid(t, coeff, tcomp, mask, bw):
    """Relative Eq. (11) residual |demand(t) - B| / B."""
    if not mask.any():
        return 0.0
    demand = np.sum(coeff[mask] / np.maximum(t - tcomp[mask], 1e-12))
    return abs(demand - bw) / bw


def _random_instance(rng, n):
    coeff = rng.uniform(0.005, 10.0, n)
    tcomp = rng.uniform(0.01, 0.5, n)
    mask = rng.random(n) < 0.7
    bw = float(rng.uniform(0.1, 5.0))
    return coeff, tcomp, mask, bw


# ------------------------------------------------- Newton vs bisection ----
def test_newton_matches_bisection_roots():
    """Root agreement across random masks incl. empty-BS and single-user."""
    rng = np.random.default_rng(0)
    cases = []
    for _ in range(40):
        cases.append(_random_instance(rng, int(rng.integers(1, 60))))
    # edge cases: empty BS, single user
    c, t, _, bw = _random_instance(rng, 8)
    cases.append((c, t, np.zeros(8, dtype=bool), bw))
    c, t, _, bw = _random_instance(rng, 1)
    cases.append((c, t, np.ones(1, dtype=bool), bw))
    for coeff, tcomp, mask, bw in cases:
        args = (jnp.asarray(coeff, jnp.float32), jnp.asarray(tcomp,
                jnp.float32), jnp.asarray(mask), jnp.float32(bw))
        t_b = float(bandwidth.bs_time(*args, method="bisect", iters=60))
        t_n = float(bandwidth.bs_time(*args, method="newton"))
        t_np = dagsa._bs_time_np(coeff, tcomp, mask, bw)
        if not mask.any():
            assert t_b == t_n == t_np == 0.0
            continue
        np.testing.assert_allclose(t_n, t_b, rtol=1e-5)
        np.testing.assert_allclose(t_np, t_b, rtol=1e-5)
        # KKT residual: Newton (<=16 iters, the default) must be at least
        # as tight as the seed's 60-iteration bisection (rel. 1e-4 bound).
        assert _kkt_resid(t_n, coeff, tcomp, mask, bw) <= max(
            1e-4, _kkt_resid(t_b, coeff, tcomp, mask, bw) * 1.5)
        assert _kkt_resid(t_n, coeff, tcomp, mask, bw) <= 1e-4


def test_newton_iteration_budget_beats_bisection60():
    """The default Newton budget is <= 16 iterations and reaches the
    bisection-60 KKT residual within it (acceptance criterion)."""
    assert bandwidth.default_iters("newton") <= 16
    rng = np.random.default_rng(7)
    worst_n, worst_b = 0.0, 0.0
    for _ in range(50):
        coeff, tcomp, mask, bw = _random_instance(rng,
                                                  int(rng.integers(1, 60)))
        if not mask.any():
            mask[0] = True
        args = (jnp.asarray(coeff, jnp.float32),
                jnp.asarray(tcomp, jnp.float32), jnp.asarray(mask),
                jnp.float32(bw))
        t_n = float(bandwidth.bs_time(*args, method="newton", iters=16))
        t_b = float(bandwidth.bs_time(*args, method="bisect", iters=60))
        worst_n = max(worst_n, _kkt_resid(t_n, coeff, tcomp, mask, bw))
        worst_b = max(worst_b, _kkt_resid(t_b, coeff, tcomp, mask, bw))
    assert worst_n <= max(worst_b * 1.5, 1e-4)


def test_warm_start_lo_hint():
    """Warm-starting with a valid lower bound returns the same root."""
    rng = np.random.default_rng(3)
    coeff, tcomp, mask, bw = _random_instance(rng, 20)
    if not mask.any():
        mask[0] = True
    args = (jnp.asarray(coeff, jnp.float32), jnp.asarray(tcomp, jnp.float32),
            jnp.asarray(mask), jnp.float32(bw))
    cold = float(bandwidth.bs_time(*args))
    # hint below the root, at the root, and numpy-mirror equivalents
    for hint in (0.0, 0.5 * cold, cold):
        warm = float(bandwidth.bs_time(*args, lo_hint=jnp.float32(hint)))
        np.testing.assert_allclose(warm, cold, rtol=1e-5)
        warm_np = dagsa._bs_time_np(coeff, tcomp, mask, bw, lo_hint=hint)
        np.testing.assert_allclose(warm_np, cold, rtol=1e-5)


def test_kernel_newton_matches_oracle():
    """Pallas kernel (interpret) Newton/bisect + warm start vs jnp oracle."""
    from repro.kernels import ref
    rng = np.random.default_rng(5)
    k, u = 13, 40
    coeff = jnp.asarray(rng.uniform(0.05, 2.0, (k, u)), jnp.float32)
    tcomp = jnp.asarray(rng.uniform(0.05, 0.15, (k, u)), jnp.float32)
    mask = jnp.asarray(rng.random((k, u)) < 0.6)
    mask = mask.at[0].set(False)                      # one empty BS row
    bw = jnp.asarray(rng.uniform(0.5, 2.0, (k,)), jnp.float32)
    for method in ("newton", "bisect"):
        got = bandwidth_solve(coeff, tcomp, mask, bw, method=method,
                              interpret=True)
        want = ref.bandwidth_solve(coeff, tcomp, mask, bw, method=method)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-3, atol=1e-5)
        assert float(got[0]) == 0.0
    # warm start with the previous root must reproduce it
    base = bandwidth_solve(coeff, tcomp, mask, bw, interpret=True)
    warm = bandwidth_solve(coeff, tcomp, mask, bw, lo=base, interpret=True)
    np.testing.assert_allclose(np.asarray(warm), np.asarray(base),
                               rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("u", [40, 16384 + 40], ids=["one-block", "ragged"])
def test_kernel_shared_tcomp_row_equals_its_broadcast(u):
    """A [U] tcomp, one row every solve shares, reads as the same row
    broadcast to [K, U]: the same roots, bit for bit."""
    rng = np.random.default_rng(6)
    k = 5
    coeff = jnp.asarray(rng.uniform(0.05, 2.0, (k, u)), jnp.float32)
    tcomp = jnp.asarray(rng.uniform(0.05, 0.15, (u,)), jnp.float32)
    mask = jnp.asarray(rng.random((k, u)) < 0.3)
    bw = jnp.asarray(rng.uniform(0.5, 2.0, (k,)), jnp.float32)
    shared = bandwidth_solve(coeff, tcomp, mask, bw, interpret=True)
    full = bandwidth_solve(coeff, jnp.broadcast_to(tcomp, (k, u)), mask, bw,
                           interpret=True)
    np.testing.assert_array_equal(np.asarray(shared), np.asarray(full))
    assert (np.asarray(shared) > 0).all()


# ------------------------------------------------------- batched DAGSA ----
def test_batch_matches_per_problem_loop():
    """dagsa_schedule_batch == per-problem dagsa_schedule_jit, same keys,
    on >= 20 random problems (assignment masks exactly, t_round to f32)."""
    n_prob = 20
    probs = [make_problem(s) for s in range(n_prob)]
    keys = jax.random.split(jax.random.PRNGKey(99), n_prob)
    batch = dagsa_schedule_batch(probs, keys)
    for i, p in enumerate(probs):
        single = dagsa_schedule_jit(p, keys[i])
        np.testing.assert_array_equal(np.asarray(batch.assign[i]),
                                      np.asarray(single.assign))
        np.testing.assert_allclose(float(batch.t_round[i]),
                                   float(single.t_round), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(batch.bw[i]),
                                   np.asarray(single.bw), rtol=1e-5,
                                   atol=1e-7)


def test_batch_constraints_and_registry():
    probs = [make_problem(s) for s in range(4)]
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    res = schedule_batch("dagsa_jit", probs, keys)
    assign = np.asarray(res.assign)
    assert assign.shape == (4, CFG.n_users, CFG.n_bs)
    assert (assign.sum(axis=2) <= 1).all()                       # Eq. (8d)
    assert (res.selected.sum(axis=1) >=
            np.asarray([p.min_participants for p in probs])).all()  # (8h)
    with pytest.raises(ValueError):
        schedule_batch("dagsa", probs, keys)   # host-numpy: unbatchable


def test_batch_pallas_backend_matches_jax():
    probs = [make_problem(s) for s in range(3)]
    stacked = stack_problems(probs)
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    jx = dagsa_schedule_batch(stacked, keys, backend="jax")
    pl = dagsa_schedule_batch(stacked, keys, backend="pallas",
                              interpret=True)
    np.testing.assert_array_equal(np.asarray(pl.assign),
                                  np.asarray(jx.assign))
    np.testing.assert_allclose(np.asarray(pl.t_round),
                               np.asarray(jx.t_round), rtol=1e-4)


def test_stack_problems_rejects_mixed_min_participants():
    import dataclasses
    p0, p1 = make_problem(0), make_problem(1)
    p1 = dataclasses.replace(p1, min_participants=p0.min_participants + 1)
    with pytest.raises(ValueError):
        stack_problems([p0, p1])


# --------------------------------------------------------- determinism ----
def test_host_dagsa_seed_determinism():
    """One Generator threaded through steps 1-4: seed fixes the schedule."""
    prob = make_problem(0)
    a = dagsa.dagsa_schedule(prob, seed=11)
    b = dagsa.dagsa_schedule(prob, seed=11)
    np.testing.assert_array_equal(np.asarray(a.assign), np.asarray(b.assign))
    np.testing.assert_array_equal(np.asarray(a.bw), np.asarray(b.bw))
    assert float(a.t_round) == float(b.t_round)


def test_host_dagsa_forced_adds_deterministic():
    """Determinism must survive step 4 (the random force-adds): build a
    problem whose threshold pass cannot reach min_participants."""
    rng = np.random.default_rng(0)
    n, m = 16, 3
    snr = jnp.asarray(rng.lognormal(2.0, 2.0, (n, m)), jnp.float32)
    coeff = 0.5 / jnp.log2(1.0 + snr)
    prob = SchedulingProblem(
        snr=snr, tcomp=jnp.asarray(rng.uniform(0.1, 0.11, n), jnp.float32),
        bs_bw=jnp.ones((m,), jnp.float32), coeff=coeff,
        necessary=jnp.zeros(n, dtype=bool), min_participants=n - 2)
    runs = [dagsa.dagsa_schedule(prob, seed=4) for _ in range(3)]
    for r in runs[1:]:
        np.testing.assert_array_equal(np.asarray(runs[0].assign),
                                      np.asarray(r.assign))
    assert int(runs[0].selected.sum()) >= n - 2
