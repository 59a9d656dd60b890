"""Named device spans and work counters of the round step and the greedy.

Every span of the vocabulary must survive jit, vmap, scan, while_loop and
fusion as a path component of the compiled instructions' ``op_name``
metadata, in each program that runs its phase; the counters must count
what they say.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import channel, dagsa_jit, mobility
from repro.core.types import MobilityState, WirelessConfig
from repro.fl import FLConfig, FLSimulation
from repro.launch import sweep

LEARNING_SPANS = {"round.mobility", "round.channel", "round.select",
                  "dagsa.select", "dagsa.eq11", "round.sgd",
                  "round.aggregate", "round.eval"}
WIRELESS_SPANS = {"world.init", "round.mobility", "round.channel",
                  "round.select", "dagsa.select", "dagsa.eq11"}
VOCABULARY = LEARNING_SPANS | WIRELESS_SPANS | {"round.compress"}

SMALL = dict(scheduler="dagsa_jit",
             wireless=WirelessConfig(n_users=8, n_bs=3),
             n_train=160, n_test=40, batch_size=10, local_epochs=1,
             eval_every=1, seed=0)


def spans_in(compiled_text: str) -> set:
    """The vocabulary's names found as path components of op_names; a
    transform wraps the scopes inside it (``vmap(vmap(world.init))``)."""
    found = set()
    for op_name in re.findall(r'op_name="([^"]*)"', compiled_text):
        for part in op_name.split("/"):
            name = re.sub(r"^(?:\w+\()*|\)*$", "", part)
            if name in VOCABULARY:
                found.add(name)
    return found


def scan_spans(sim: FLSimulation, rounds: int = 2) -> set:
    compiled = sim._scan_jit.lower(sim._carry(), sim.round_idx,
                                   n_rounds=rounds).compile()
    return spans_in(compiled.as_text())


@pytest.mark.parametrize("extra, want", [
    ({}, LEARNING_SPANS),
    ({"aggregation": "hierarchical", "tau_global": 2}, LEARNING_SPANS),
    ({"compress": "topk", "topk_frac": 0.25},
     LEARNING_SPANS | {"round.compress"}),
], ids=["sync", "hierarchical", "compressed"])
def test_fused_scan_carries_every_span(extra, want):
    sim = FLSimulation(FLConfig(**SMALL, **extra))
    assert scan_spans(sim) == want


def test_wireless_bucket_carries_every_span():
    cfg = WirelessConfig(n_users=64, n_bs=4, rho1=0.1, rho2=0.2)
    params = sweep._scenario_params(
        [sweep.get_scenario("paper-default")], cfg)
    compiled = sweep._sweep_bucket.lower(
        params, jax.random.PRNGKey(0), cfg=cfg, n_rounds=2, n_seeds=1,
        min_participants=13, backend="jax", user_chunk=16,
        channel_dtype="bf16",
        n_models=len(mobility.MOBILITY_MODELS)).compile()
    assert spans_in(compiled.as_text()) == WIRELESS_SPANS


def _problem(seed: int, n: int = 120, m: int = 5):
    cfg = WirelessConfig(n_users=n, n_bs=m, rho1=0.1, rho2=0.3)
    k_pos, k_bs, k_cnt, k_prob = jax.random.split(jax.random.PRNGKey(seed), 4)
    state = MobilityState(
        user_pos=jax.random.uniform(k_pos, (n, 2)) * cfg.area_m,
        bs_pos=jax.random.uniform(k_bs, (m, 2)) * cfg.area_m)
    # after 6 rounds users with no participation are necessary (Eq. 8g)
    counts = jax.random.bernoulli(k_cnt, 0.6, (n,)).astype(jnp.float32)
    return channel.make_problem(k_prob, state, cfg, counts, 5)


@pytest.mark.parametrize("selection_block", [None, 32],
                         ids=["jax", "chunked"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_greedy_steps_count_the_users_the_loop_added(seed, selection_block):
    p = _problem(seed)
    args = (p.snr, p.coeff, p.tcomp, p.bs_bw, p.necessary,
            p.min_participants, jax.random.PRNGKey(seed + 10))
    *res, steps, dense_steps = dagsa_jit._schedule(
        *args, selection_block=selection_block)
    n_selected = int(np.asarray(res[1]).sum())
    step1 = int(np.asarray(p.necessary).sum())
    assert 0 < step1 < n_selected
    assert steps.dtype == dense_steps.dtype == jnp.int32
    assert int(steps) == n_selected - step1
    # N = 120 is within the compact width: every step is a dense one
    assert int(dense_steps) == int(steps)


def test_sweep_records_carry_greedy_steps():
    cfg = WirelessConfig(n_users=64, n_bs=4, rho1=0.1, rho2=0.2)
    rec = sweep.run_sweep(["paper-default"], n_seeds=1, n_rounds=2, cfg=cfg,
                          user_chunk=16)[0]
    steps = rec["curves"]["greedy_steps"]
    assert len(steps) == 2
    assert rec["curves"]["dense_steps"] == steps     # N = 64: all dense
    assert all(0 <= s <= n for s, n in zip(steps,
                                           rec["curves"]["n_selected"]))


@pytest.mark.parametrize("compute, cap, want", [
    ("full", None, 8), ("selected", 5, 5), ("selected", 50, 8)])
def test_n_trained_is_the_fleet_or_the_cap(compute, cap, want):
    sim = FLSimulation(FLConfig(**SMALL, compute=compute, select_cap=cap))
    recs = sim.run(2, mode="fused")
    assert [r.n_trained for r in recs] == [want, want]


def test_eager_rounds_train_the_whole_fleet():
    sim = FLSimulation(FLConfig(**SMALL))
    assert sim.run(1, mode="eager")[0].n_trained == 8


def test_host_spans_share_the_call_identifier(tmp_path):
    """The engine's and the sweep's host spans land in the profiler trace
    with their annotation arguments."""
    from jax.profiler import ProfileData
    sim = FLSimulation(FLConfig(**SMALL))
    sim.run(2, mode="fused")                  # compile outside the trace
    cfg = WirelessConfig(n_users=32, n_bs=3)
    sweep.run_sweep(["paper-default"], n_seeds=1, n_rounds=1, cfg=cfg,
                    seed=5)
    jax.profiler.start_trace(str(tmp_path))
    try:
        sim.run(2, mode="fused")
        sweep.run_sweep(["paper-default"], n_seeds=1, n_rounds=1, cfg=cfg,
                        seed=5)
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    events = {}
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("fl.", "sweep.")):
                    events.setdefault(e.name, []).append(dict(e.stats))
    assert {k: len(v) for k, v in events.items()} == {
        "fl.call": 1, "fl.dispatch": 1, "fl.fetch": 1, "sweep.call": 1,
        "sweep.params": 1, "sweep.dispatch": 1, "sweep.fetch": 1}
    assert {e[0]["round"] for k, e in events.items()
            if k.startswith("fl.")} == {2}
    assert events["fl.dispatch"][0]["rounds"] == 2
    assert {e[0]["seed"] for k, e in events.items()
            if k.startswith("sweep.")} == {5}
