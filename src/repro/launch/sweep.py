"""Fully-batched multi-scenario, multi-seed wireless + learning sweeps.

One compiled loop runs (mobility step -> channel sample -> DAGSA-X
schedule) as a ``lax.scan`` over rounds, vmapped over seeds x scenarios.
Scenario differences (mobility model, speed, BS layout, bandwidth draw,
shadowing, compute spread) are DATA — per-scenario parameter arrays feeding
a ``lax.switch`` over the mobility registry — so adding a scenario never
re-traces; only a different array *shape* (n_users, n_bs) opens a new
compilation bucket.  Candidate bandwidth solves go through the same
``repro.core.dagsa_jit._schedule`` greedy the fleet engine batches
(``backend="pallas"`` routes them through the Pallas kernel).

``--learning`` extends the compiled loop with the full FL data plane
(fleet local SGD + masked Eq. (2) FedAvg + periodic eval) — the paper's
accuracy-vs-simulated-wall-clock figures (Figs. 2-4) as one compiled call
per shape bucket, seeds x scenarios batched.

CLI (emits per-scenario JSON latency/fairness curves, schema below):

    PYTHONPATH=src python -m repro.launch.sweep \
        --scenarios paper-default,high-mobility --seeds 4 --rounds 10

    # learning curves: test-acc vs simulated wall-clock per scenario x seed
    PYTHONPATH=src python -m repro.launch.sweep --learning \
        --scenarios paper-default,static --seeds 2 --rounds 10

    # same grid sharded over 8 host devices (bit-identical output; see
    # repro.launch.shard_sweep and docs/SCALING.md)
    PYTHONPATH=src XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python -m repro.launch.sweep --shard \
        --scenarios paper-default,high-mobility --seeds 8 --rounds 3

Wireless record schema (one dict per scenario, JSON list on stdout /
``--out``):

    {"scenario": str, "mobility": str, "speed_mps": float,
     "n_seeds": int, "n_rounds": int,
     "t_round_mean_s": float,          # mean Eq. (3) latency, seeds x rounds
     "t_round_p95_s": float,           # 95th pct, pooled seeds x rounds
     "participants_mean": float,       # mean selected users per round
     "min_part_rate": float,           # final-round min_i counts_i / round,
                                       #   the Eq. (8g) fairness monitor
     "curves": {"t_round_s": [R], "n_selected": [R],
                "min_part_rate": [R],  # per-round means across seeds
                "greedy_steps": [R],   # DAGSA while-loop iterations
                "dense_steps": [R]}}   #   of them on the dense [M, N] trials

Learning records add (see :func:`run_learning_sweep`):

    {..., "dataset": str,
     "aggregation": str, "tau_global": int,  # single | hierarchical
     "final_acc_mean": float, "final_acc_std": float,
     "wall_clock_mean_s": float,       # mean final simulated clock
     "acc_at_budget": {"budget_s": float, "acc_mean": float},
     "curves": {"wall_clock_s": [R], "test_acc": [R],  # seed means
                "t_round_s": [R], "n_selected": [R]},
     "seed_curves": {"wall_clock_s": [seeds][R],       # per-seed curves
                     "test_acc": [seeds][R]}}

Hierarchical scenarios (``hfl-*`` or ``aggregation="hierarchical"``)
additionally report ``handover_rate_mean`` and a per-round
``handover_rate`` curve, and are bucketed separately so every bucket
stays one compiled call.

Faulty scenarios (an active ``FaultSpec`` on the spec, or the
``--faults``/``--deadline`` overrides; see docs/ROBUSTNESS.md) carry a
``"scheduler"`` field (``--scheduler dagsa-r`` discounts candidates by
estimated delivery probability), the fault model under ``"faults"``
(strict JSON via ``FaultSpec.to_json``), ``delivered_mean`` /
``delivered_rate_mean`` / ``goodput_mbit_s_mean``, and per-round
``n_delivered`` / ``delivered_rate`` / ``goodput_mbit_s`` curves.
Fault severity is traced data, so faulty scenarios of different
severity share one compiled bucket (keyed only on the static
``faults_on``/``clip_on`` flags).

Seeds are PAIRED across scenarios in the same shape bucket (same geometry/
fading keys, same client data + model init in the learning sweep), a
variance-reduction trick for A-vs-B scenario comparisons.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import channel, dagsa_jit, mobility
from repro.core.scenario import (SCENARIOS, BS_LAYOUTS, COMPRESS_MODES,
                                 PARTITIONS, ScenarioSpec, get_scenario)
from repro.core.types import MobilityState, WirelessConfig
# registers the faulty-* scenarios and supplies the traced fault samplers
from repro.fl import faults as fl_faults
from repro.launch.cache import use_compile_cache

# Learning-sweep scheduler choices: the compiled greedy, its failure-aware
# variant that discounts candidates by estimated delivery probability
# (identical to dagsa_jit when the scenario has no faults), the random
# baseline, and the stateful online policies (per-user running estimates
# riding the scan carry) — the bake-off set.
SWEEP_SCHEDULERS = ("dagsa_jit", "dagsa-r", "rs", "ucb", "biased-adaptive",
                    "rr", "pf")


# -------------------------------------------------------------- lowering ---
def _scenario_params(specs: Sequence[ScenarioSpec],
                     cfg: WirelessConfig) -> dict:
    """Lower specs to per-scenario parameter arrays [S] (all traced)."""
    f32 = jnp.float32

    def arr(fn, dtype=f32):
        return jnp.asarray([fn(s) for s in specs], dtype)

    return {
        "model_id": arr(lambda s: mobility.model_index(s.mobility),
                        jnp.int32),
        "layout_id": arr(lambda s: BS_LAYOUTS.index(s.bs_layout), jnp.int32),
        "speed": arr(lambda s: s.speed_mps),
        "pause_s": arr(lambda s: s.pause_s),
        "gm_memory": arr(lambda s: s.gm_memory),
        "bw_min": arr(lambda s: s.bw_min_mhz if s.bw_min_mhz is not None
                      else cfg.bs_bandwidth_mhz),
        "bw_max": arr(lambda s: s.bw_max_mhz if s.bw_max_mhz is not None
                      else cfg.bs_bandwidth_mhz),
        "shadow_sigma": arr(lambda s: s.shadow_sigma_db if s.shadowing
                            else 0.0),
        "tcomp_min": arr(lambda s: s.tcomp_min_s if s.tcomp_min_s is not None
                         else cfg.tcomp_min_s),
        "tcomp_max": arr(lambda s: s.tcomp_max_s if s.tcomp_max_s is not None
                         else cfg.tcomp_max_s),
        # device heterogeneity spreads (docs/COMPRESSION.md) — DATA: the
        # homogeneous defaults (1.0 / 0.0 dB) are IEEE-exact no-ops inside
        # the round step, so hetero and plain scenarios share a bucket
        "compute_spread": arr(lambda s: s.compute_spread),
        "power_spread_db": arr(lambda s: s.power_spread_db),
        # fault knobs, "f_"-prefixed (NO_FAULTS when the scenario has none);
        # severity is DATA, so scenarios of different severity share a bucket
        **{f"f_{k}": arr(lambda s, k=k: fl_faults.fault_params(
            s.faults if s.faults is not None else fl_faults.NO_FAULTS)[k])
           for k in fl_faults.FAULT_PARAM_KEYS},
    }


def _bs_positions(key: jax.Array, layout_id, cfg: WirelessConfig):
    """[M, 2] BS positions; grid vs uniform selected by traced layout_id."""
    kg, ku = jax.random.split(key)
    grid = mobility.grid_bs_positions(kg, cfg.n_bs, cfg.area_m)
    uniform = jax.random.uniform(ku, (cfg.n_bs, 2), minval=0.0,
                                 maxval=cfg.area_m)
    return jnp.where(layout_id == BS_LAYOUTS.index("grid"), grid, uniform)


# ------------------------------------------------------------ compiled core --
# The chunked distance/shadowing evaluation moved to the channel layer
# (PR 9); the alias keeps this module's long-standing name importable.
_dist_and_shadow = channel.dist_and_shadow


def _check_user_chunk(user_chunk: int | None, n_users: int) -> None:
    if user_chunk is not None and user_chunk < 1:
        raise ValueError(f"user_chunk must be >= 1, got {user_chunk}")


def _one_cell(p: dict, key: jax.Array, cfg: WirelessConfig, n_rounds: int,
              min_participants: int, backend: str,
              user_chunk: int | None = None,
              channel_dtype: str = "f32") -> dict:
    """One (scenario, seed) cell: init world, scan the wireless loop.

    ``channel_dtype="bf16"`` stores the per-round [N, M] SNR (and the
    coefficient matrix derived from it) in bfloat16 — half the bytes/user
    of the channel plane (docs/SCALING.md); selection and the Eq. (11)
    solves upcast per block/row.  ``user_chunk`` additionally routes
    Algorithm 1 steps 1/3 through the streaming chunked selection
    (bit-identical decisions, no [N, M] selection temporaries).

    This body carries the round step's span names by copy
    (:func:`repro.fl.rounds.make_round_step`), plus ``world.init``, and
    returns the greedy's iteration count as ``greedy_steps`` and those
    of them its dense [M, N] trial step took as ``dense_steps``.
    """
    with jax.named_scope("world.init"):
        k_pos, k_bs, k_bw, k_aux, k_shadow, k_run = jax.random.split(key, 6)
        pos0 = jax.random.uniform(k_pos, (cfg.n_users, 2), minval=0.0,
                                  maxval=cfg.area_m)
        bs_pos = _bs_positions(k_bs, p["layout_id"], cfg)
        bs_bw = p["bw_min"] + jax.random.uniform(k_bw, (cfg.n_bs,)) * \
            (p["bw_max"] - p["bw_min"])
        aux0 = mobility.init_aux(k_aux, cfg.n_users, cfg,
                                 speed_mps=p["speed"])
        counts0 = jnp.zeros((cfg.n_users,))

    def round_body(carry, r):
        pos, aux, counts, key = carry
        key, k_mob, k_snr, k_tc, k_sched = jax.random.split(key, 5)
        pos, aux = mobility.step_switch(
            p["model_id"], k_mob, pos, aux, cfg.area_m, cfg.round_duration_s,
            p["speed"], p["pause_s"], p["gm_memory"])
        with jax.named_scope("round.channel"):
            # same k_shadow every round -> the field is consistent over
            # time; sigma 0 (scenario off) makes it a no-op multiplier.
            dist, shadow_db = _dist_and_shadow(pos, bs_pos,
                                               p["shadow_sigma"], k_shadow,
                                               cfg, user_chunk)
            snr_store, snr_scale, snr_lin = channel.encode_channel(
                channel.sample_snr(k_snr, dist, cfg, shadow_db=shadow_db),
                channel_dtype)
            if channel_dtype == "int8":
                # Eq. (11) needs real coefficients — derive from the
                # dequantised plane (the int8 codes carry only ranks + dB
                # values)
                coeff = channel.bandwidth_time_coeff(snr_lin, cfg)
            else:
                coeff = channel.compress_channel(
                    channel.bandwidth_time_coeff(snr_store, cfg),
                    channel_dtype)
            u = jax.random.uniform(k_tc, (cfg.n_users,))
            tcomp = p["tcomp_min"] + u * (p["tcomp_max"] - p["tcomp_min"])
            # Eq. (8g): post-round requirement — participate if sitting out
            # would leave the count below rho1 * (rounds so far INCLUDING
            # this one); matches channel.make_problem.
            necessary = counts < cfg.rho1 * (r + 1.0)
        with jax.named_scope("round.select"):
            decision = dagsa_jit._schedule(
                snr_store, coeff, tcomp, bs_bw, necessary, min_participants,
                k_sched, backend=backend, selection_block=user_chunk,
                snr_scale=snr_scale)
        selected, t_round = decision[1], decision[4]
        *_, greedy_steps, dense_steps = decision
        counts = counts + selected.astype(counts.dtype)
        out = {
            "t_round": t_round,
            "n_selected": jnp.sum(selected).astype(jnp.float32),
            "min_part_rate": jnp.min(counts) / (r + 1.0),
            "greedy_steps": greedy_steps,
            "dense_steps": dense_steps,
        }
        return (pos, aux, counts, key), out

    _, outs = jax.lax.scan(round_body, (pos0, aux0, counts0, k_run),
                           jnp.arange(n_rounds, dtype=jnp.float32))
    return outs


@partial(jax.jit, static_argnames=("cfg", "n_rounds", "n_seeds",
                                   "min_participants", "backend",
                                   "user_chunk", "channel_dtype",
                                   "n_models"))
def _sweep_bucket(params: dict, key: jax.Array, *, cfg: WirelessConfig,
                  n_rounds: int, n_seeds: int, min_participants: int,
                  backend: str, user_chunk: int | None,
                  channel_dtype: str, n_models: int) -> dict:
    """All scenarios of one shape bucket x all seeds, one compiled call.

    Returns a dict of [S, n_seeds, n_rounds] arrays.  ``n_models`` is the
    mobility-registry size at call time: the lax.switch branch table is
    baked in at trace time, so a model registered later must open a fresh
    compilation instead of silently clamping to the last cached branch.
    """
    seed_keys = jax.random.split(key, n_seeds)   # shared: paired comparisons
    run = partial(_one_cell, cfg=cfg, n_rounds=n_rounds,
                  min_participants=min_participants, backend=backend,
                  user_chunk=user_chunk, channel_dtype=channel_dtype)
    return jax.vmap(lambda p: jax.vmap(lambda k: run(p, k))(seed_keys))(
        params)


# ------------------------------------------------------------------- API ---
def _wireless_buckets(specs: Sequence[ScenarioSpec], base: WirelessConfig
                      ) -> dict[tuple[int, int],
                                list[tuple[int, ScenarioSpec]]]:
    """Group (position, spec) pairs by resolved array shape (n_users, n_bs).

    Each bucket compiles once; shared by the single-device sweep and the
    device-sharded one (:mod:`repro.launch.shard_sweep`)."""
    buckets: dict[tuple[int, int], list[tuple[int, ScenarioSpec]]] = {}
    for pos, spec in enumerate(specs):
        w = spec.wireless(base)
        buckets.setdefault((w.n_users, w.n_bs), []).append((pos, spec))
    return buckets


def _wireless_records(group: list[tuple[int, ScenarioSpec]], outs: dict,
                      n_seeds: int, n_rounds: int) -> dict[int, dict]:
    """[S, seeds, R] bucket outputs -> per-scenario record dicts.

    Shared by ``run_sweep`` and ``shard_sweep.run_shard_sweep`` so the two
    paths emit byte-identical JSON (the parity contract CI diffs)."""
    t_round = np.asarray(outs["t_round"])            # [S, seeds, R]
    n_sel = np.asarray(outs["n_selected"])
    min_pr = np.asarray(outs["min_part_rate"])
    steps = np.asarray(outs["greedy_steps"])
    dense = np.asarray(outs["dense_steps"])
    records: dict[int, dict] = {}
    for i, (pos, spec) in enumerate(group):
        records[pos] = {
            "scenario": spec.name,
            "mobility": spec.mobility,
            "speed_mps": spec.speed_mps,
            "n_seeds": n_seeds,
            "n_rounds": n_rounds,
            "t_round_mean_s": float(t_round[i].mean()),
            "t_round_p95_s": float(np.percentile(t_round[i], 95)),
            "participants_mean": float(n_sel[i].mean()),
            "min_part_rate": float(min_pr[i, :, -1].mean()),
            "curves": {
                "t_round_s": t_round[i].mean(axis=0).tolist(),
                "n_selected": n_sel[i].mean(axis=0).tolist(),
                "min_part_rate": min_pr[i].mean(axis=0).tolist(),
                "greedy_steps": steps[i].mean(axis=0).tolist(),
                "dense_steps": dense[i].mean(axis=0).tolist(),
            },
        }
    return records


def run_sweep(scenarios: Sequence[str | ScenarioSpec], n_seeds: int = 4,
              n_rounds: int = 10, cfg: WirelessConfig | None = None,
              backend: str = "jax", seed: int = 0,
              user_chunk: int | None = None,
              channel_dtype: str = "f32") -> list[dict]:
    """Run the batched wireless sweep; one record dict per scenario.

    Scenarios are bucketed by resolved array shape (n_users, n_bs); each
    bucket is ONE jit-compiled call covering all its scenarios x seeds.
    ``user_chunk`` bounds the per-round O(N x M x F) channel intermediates
    (see :func:`_dist_and_shadow`) and streams Algorithm 1's selection in
    blocks of that size (any value works — partial blocks are padded).
    ``channel_dtype="bf16"`` stores the [N, M] channel planes compactly
    (docs/SCALING.md).  See the module docstring for the record schema.
    """
    specs = [get_scenario(s) if isinstance(s, str) else s for s in scenarios]
    base = cfg or WirelessConfig()
    records: dict[int, dict] = {}       # original position -> record
    # host spans on the profiler's clock, each naming the call's seed:
    # the whole call, the scenario arrays, the bucket's enqueue, and the
    # records brought back to the host
    span = jax.profiler.TraceAnnotation
    with span("sweep.call", seed=seed):
        for (n_users, n_bs), group in _wireless_buckets(specs, base).items():
            _check_user_chunk(user_chunk, n_users)
            bcfg = dataclasses.replace(base, n_bs=n_bs)
            minp = int(np.ceil(bcfg.rho2 * n_users))
            with span("sweep.params", seed=seed):
                params = _scenario_params([s for _, s in group], bcfg)
            with span("sweep.dispatch", seed=seed):
                outs = _sweep_bucket(params, jax.random.PRNGKey(seed),
                                     cfg=bcfg, n_rounds=n_rounds,
                                     n_seeds=n_seeds, min_participants=minp,
                                     backend=backend, user_chunk=user_chunk,
                                     channel_dtype=channel_dtype,
                                     n_models=len(mobility.MOBILITY_MODELS))
            with span("sweep.fetch", seed=seed):
                records.update(_wireless_records(group, outs, n_seeds,
                                                 n_rounds))
    # preserve the caller's scenario order
    return [records[i] for i in range(len(specs))]


# ---------------------------------------------------- learning-curve sweep --
def _one_learning_cell(p: dict, key: jax.Array, x_c, y_c, params0,
                       x_test, y_test, *, cfg: WirelessConfig, n_rounds: int,
                       minp: int, epochs: int, batch_size: int, lr: float,
                       eval_every: int, backend: str, fedavg_backend: str,
                       compute: str, select_cap, aggregation: str = "single",
                       tau_global: int = 1, scheduler: str = "dagsa_jit",
                       faults_on: bool = False, clip_on: bool = False,
                       async_on: bool = False, tick_s: float = 1.0,
                       staleness_alpha: float = 0.0, buffer_size: int = 1,
                       user_chunk: int | None = None,
                       channel_dtype: str = "f32",
                       compress: str | None = None,
                       topk_frac: float = 1.0) -> dict:
    """One (scenario, seed) FL cell: init world, scan the full round loop
    (wireless control plane + local SGD + Eq. (2) aggregation — single-tier
    or hierarchical per-BS edges with a tau_global sync — + periodic
    eval).

    ``faults_on`` (static, part of the bucket key) switches in the fault
    layer of :mod:`repro.fl.faults`: outage/straggler/crash/corruption
    realizations from one extra per-round subkey, deadline-truncated round
    latency, and delivery-masked aggregation.  Fault *severity* stays data
    (the ``f_*`` entries of ``p``).  ``clip_on`` statically enables the
    norm-clip defense (the clip value is traced; ``inf`` is an exact
    no-op, so clip and no-clip scenarios may share a bucket).
    ``scheduler="dagsa-r"`` discounts the greedy's candidate score by the
    estimated delivery probability — with faults off it IS dagsa_jit.

    ``async_on`` (static) switches the data plane to the buffered-async
    tick engine (docs/ASYNC.md): each scan step is one ``tick_s`` of
    simulated time, scheduled non-busy clients dispatch with their Eq. (1)
    completion times into an event queue riding the carry, and whatever
    lands within the tick aggregates under the staleness discount
    ``(1+s)^(-staleness_alpha)``.  The control plane (PRNG splits,
    mobility, channel, scheduling, fault realization) is untouched, so
    sync-vs-async curves compare the aggregation discipline alone.

    The round body itself is the canonical
    :func:`repro.fl.rounds.make_round_step` step (``world="sweep"``) —
    the SAME function :class:`repro.fl.rounds.FLSimulation` scans; this
    cell only draws the world (positions, BS layout, bandwidths,
    kinematics) and hands the typed :class:`~repro.core.types.RoundState`
    to the scan.
    """
    from repro.fl.rounds import RoundPlan, make_round_step

    fp = {k: p[f"f_{k}"] for k in fl_faults.FAULT_PARAM_KEYS}
    with jax.named_scope("world.init"):
        k_pos, k_bs, k_bw, k_aux, k_shadow, k_run = jax.random.split(key, 6)
        pos0 = jax.random.uniform(k_pos, (cfg.n_users, 2), minval=0.0,
                                  maxval=cfg.area_m)
        bs_pos = _bs_positions(k_bs, p["layout_id"], cfg)
        bs_bw = p["bw_min"] + jax.random.uniform(k_bw, (cfg.n_bs,)) * \
            (p["bw_max"] - p["bw_min"])
        aux0 = mobility.init_aux(k_aux, cfg.n_users, cfg,
                                 speed_mps=p["speed"])
        counts0 = jnp.zeros((cfg.n_users,))
    data_sizes = jnp.full((cfg.n_users,), x_c.shape[1])

    plan = RoundPlan(
        scheduler=scheduler, epochs=epochs, batch_size=batch_size, lr=lr,
        eval_every=eval_every, compute=compute, select_cap=select_cap,
        fedavg_backend=fedavg_backend, aggregation=aggregation,
        tau_global=tau_global, async_on=async_on, tick_s=tick_s,
        staleness_alpha=staleness_alpha, buffer_size=buffer_size,
        faults_on=faults_on, clip_on=clip_on, backend=backend,
        user_chunk=user_chunk, channel_dtype=channel_dtype, world="sweep",
        compress=compress, topk_frac=topk_frac)
    init_state, step = make_round_step(
        plan, cfg, scenario=p, faults=fp, x_clients=x_c, y_clients=y_c,
        data_sizes=data_sizes, x_test=x_test, y_test=y_test, bs_pos=bs_pos,
        bs_bw=bs_bw, k_shadow=k_shadow, min_participants=minp,
        params0=params0, pos0=pos0, aux0=aux0, counts0=counts0, key0=k_run)
    _, outs = jax.lax.scan(step, init_state, jnp.arange(n_rounds))
    return outs


@partial(jax.jit, static_argnames=("cfg", "n_rounds", "minp", "epochs",
                                   "batch_size", "lr", "eval_every",
                                   "backend", "fedavg_backend", "compute",
                                   "select_cap", "aggregation", "tau_global",
                                   "scheduler", "faults_on", "clip_on",
                                   "async_on", "tick_s", "staleness_alpha",
                                   "buffer_size", "user_chunk",
                                   "channel_dtype", "compress", "topk_frac",
                                   "n_models"))
def _learning_bucket(params: dict, seed_keys: jax.Array, x_c, y_c, w0,
                     x_test, y_test, *, cfg: WirelessConfig, n_rounds: int,
                     minp: int, epochs: int, batch_size: int, lr: float,
                     eval_every: int, backend: str, fedavg_backend: str,
                     compute: str, select_cap, aggregation: str,
                     tau_global: int, scheduler: str, faults_on: bool,
                     clip_on: bool, async_on: bool, tick_s: float,
                     staleness_alpha: float, buffer_size: int,
                     user_chunk: int | None, channel_dtype: str,
                     compress: str | None, topk_frac: float,
                     n_models: int) -> dict:
    """All scenarios of one shape bucket x all seeds, one compiled call.

    ``x_c``/``y_c``/``w0`` carry a leading seed axis (per-seed Non-IID
    partition and model init, shared across scenarios for paired
    comparisons); ``params`` carries the scenario axis.  Returns a dict of
    [S, n_seeds, n_rounds] arrays.
    """
    run = partial(_one_learning_cell, cfg=cfg, n_rounds=n_rounds, minp=minp,
                  epochs=epochs, batch_size=batch_size, lr=lr,
                  eval_every=eval_every, backend=backend,
                  fedavg_backend=fedavg_backend, compute=compute,
                  select_cap=select_cap, aggregation=aggregation,
                  tau_global=tau_global, scheduler=scheduler,
                  faults_on=faults_on, clip_on=clip_on, async_on=async_on,
                  tick_s=tick_s, staleness_alpha=staleness_alpha,
                  buffer_size=buffer_size, user_chunk=user_chunk,
                  channel_dtype=channel_dtype, compress=compress,
                  topk_frac=topk_frac)

    def per_scenario(p):
        return jax.vmap(lambda k, xc, yc, w: run(p, k, xc, yc, w,
                                                 x_test, y_test))(
            seed_keys, x_c, y_c, w0)

    return jax.vmap(per_scenario)(params)


def _finite_or_none(xs) -> list:
    """nan -> None so the emitted JSON stays strictly parseable."""
    return [float(v) if np.isfinite(v) else None for v in np.asarray(xs)]


def _scalar_or_none(x):
    """Scalar counterpart of :func:`_finite_or_none` (e.g. an all-nan
    acc_at_budget when no eval landed inside the budget)."""
    return float(x) if np.isfinite(x) else None


def _resolve_aggregation(spec: ScenarioSpec, aggregation: str | None,
                         tau_global: int | None) -> tuple[str, int]:
    """Effective (aggregation, tau) for one scenario: explicit args win."""
    from repro.fl.rounds import DEFAULT_TAU_GLOBAL

    agg = aggregation or spec.aggregation
    if agg != "hierarchical":
        return agg, 1
    if tau_global is not None:
        return agg, tau_global
    if spec.aggregation == "hierarchical":
        return agg, spec.tau_global
    return agg, DEFAULT_TAU_GLOBAL


def _fault_flags(spec: ScenarioSpec) -> tuple[bool, bool]:
    """(faults_on, clip_on) — the STATIC part of a scenario's fault model.

    ``faults_on`` keys the bucket: a faulty scenario compiles an extra
    PRNG split + the fault/deadline graph, so it must never share a trace
    with a fault-free one (whose trajectories must stay bit-identical to
    the pre-fault sweep).  ``clip_on`` statically enables the norm-clip
    defense graph; the traced clip value lowers ``None`` to ``inf`` (an
    exact no-op), so clip and no-clip scenarios can share a faulty bucket.
    """
    fs = spec.faults
    on = fs is not None and fs.active
    return on, bool(on and fs.clip_norm is not None)


def _resolve_compress(spec: ScenarioSpec, compress: str | None,
                      topk_frac: float | None) -> tuple[str | None, float]:
    """Effective (compress, topk_frac) for one scenario: explicit args win.

    ``topk_frac`` without a resolved compress mode raises — the knob would
    silently do nothing."""
    comp = compress if compress is not None else spec.compress
    if topk_frac is not None:
        if comp is None:
            raise ValueError(
                f"topk_frac={topk_frac} only applies with a compress mode; "
                f"scenario {spec.name!r} resolves to compression off — it "
                f"would silently do nothing")
        return comp, float(topk_frac)
    return comp, (spec.topk_frac if comp is not None else 1.0)


def _resolve_partition(spec: ScenarioSpec, partition: str | None,
                       dirichlet_alpha: float | None
                       ) -> tuple[str, float | None]:
    """Effective (partition, alpha) for one scenario: explicit args win."""
    part = partition or spec.partition
    alpha = (float(dirichlet_alpha) if dirichlet_alpha is not None
             else spec.dirichlet_alpha)
    if part == "dirichlet":
        if alpha is None:
            raise ValueError(
                f"partition='dirichlet' needs dirichlet_alpha > 0 "
                f"(scenario {spec.name!r} sets none)")
        return part, alpha
    if dirichlet_alpha is not None:
        raise ValueError(
            f"dirichlet_alpha={dirichlet_alpha} only applies with "
            f"partition='dirichlet' (scenario {spec.name!r} resolves to "
            f"{part!r}); it would silently do nothing")
    return part, None


def _learning_buckets(specs: Sequence[ScenarioSpec], base: WirelessConfig,
                      aggregation: str | None, tau_global: int | None,
                      compress: str | None = None,
                      topk_frac: float | None = None,
                      partition: str | None = None,
                      dirichlet_alpha: float | None = None
                      ) -> dict[tuple, list[tuple[int, ScenarioSpec]]]:
    """Group (position, spec) by (n_users, n_bs, aggregation, tau,
    faults_on, clip_on, compress, topk_frac, partition, alpha) — the
    learning sweep's compile-bucket key (hierarchical, faulty and
    compressed buckets carry extra scan state / graph, and the partition
    shapes the shared per-seed client data, so none may share a trace
    with plain ones)."""
    buckets: dict[tuple, list[tuple[int, ScenarioSpec]]] = {}
    for pos, spec in enumerate(specs):
        w = spec.wireless(base)
        agg, tau = _resolve_aggregation(spec, aggregation, tau_global)
        faults_on, clip_on = _fault_flags(spec)
        comp, frac = _resolve_compress(spec, compress, topk_frac)
        part, alpha = _resolve_partition(spec, partition, dirichlet_alpha)
        buckets.setdefault((w.n_users, w.n_bs, agg, tau, faults_on,
                            clip_on, comp, frac, part, alpha),
                           []).append((pos, spec))
    return buckets


def _learning_seed_inputs(data, cnn_cfg, k_part, k_init, n_seeds: int,
                          n_users: int, shards_per_user: int,
                          partition: str = "shard",
                          dirichlet_alpha: float | None = None):
    """Per-seed Non-IID partitions + model inits, [seeds, ...] stacked.

    Shared across scenarios within a bucket (paired seeds) and across the
    single-device / device-sharded sweep paths.  ``partition="dirichlet"``
    swaps the paper's label-shard split for the per-user Dirichlet label
    mixture (same per-user sample count)."""
    from repro.fl.partition import dirichlet_partition, shard_partition
    from repro.models import cnn

    pkeys = jax.random.split(k_part, n_seeds)
    ikeys = jax.random.split(k_init, n_seeds)
    if partition == "dirichlet":
        idx = jax.vmap(partial(
            dirichlet_partition, labels=data.y_train, n_users=n_users,
            samples_per_user=int(data.y_train.shape[0]) // n_users,
            alpha=float(dirichlet_alpha),
            n_classes=int(np.max(np.asarray(data.y_train))) + 1))(pkeys)
    else:
        idx = jax.vmap(partial(shard_partition, labels=data.y_train,
                               n_users=n_users,
                               shards_per_user=shards_per_user))(pkeys)
    x_c, y_c = data.x_train[idx], data.y_train[idx]  # [seeds, N, n_i, ...]
    w0 = jax.vmap(lambda k: cnn.init(k, cnn_cfg))(ikeys)
    return x_c, y_c, w0


def _learning_records(group: list[tuple[int, ScenarioSpec]], outs: dict,
                      n_seeds: int, n_rounds: int, dataset: str, agg: str,
                      tau: int, scheduler: str = "dagsa_jit",
                      async_info: dict | None = None
                      ) -> dict[int, dict]:
    """[S, seeds, R] learning-bucket outputs -> per-scenario record dicts.

    Shared by ``run_learning_sweep`` and
    ``shard_sweep.run_shard_learning_sweep`` (byte-identical JSON)."""
    import warnings

    t_round = np.asarray(outs["t_round"])            # [S, seeds, R]
    n_sel = np.asarray(outs["n_selected"])
    acc = np.asarray(outs["test_acc"])
    hand = (np.asarray(outs["handover_rate"])
            if "handover_rate" in outs else None)
    n_del = (np.asarray(outs["n_delivered"])
             if "n_delivered" in outs else None)
    del_rate = (np.asarray(outs["delivered_rate"])
                if n_del is not None else None)
    goodput = (np.asarray(outs["goodput_mbit_s"])
               if n_del is not None else None)
    n_inf = (np.asarray(outs["n_inflight"])
             if "n_inflight" in outs else None)
    n_drp = (np.asarray(outs["n_dropped"])
             if "n_dropped" in outs else None)
    wall = np.cumsum(t_round, axis=-1)
    records: dict[int, dict] = {}
    for i, (pos, spec) in enumerate(group):
        finals = []                      # last evaluated acc per seed
        at_budget = []                   # paper metric per seed
        budget = float(wall[i, :, -1].mean()) / 2.0
        for s in range(n_seeds):
            finite = np.isfinite(acc[i, s])
            finals.append(acc[i, s][finite][-1] if finite.any()
                          else np.nan)
            in_budget = finite & (wall[i, s] <= budget)
            at_budget.append(acc[i, s][in_budget].max()
                             if in_budget.any() else np.nan)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            acc_curve = np.nanmean(acc[i], axis=0)
            at_budget_mean = float(np.nanmean(at_budget))
            final_mean = float(np.nanmean(finals))
            final_std = float(np.nanstd(finals))
        records[pos] = {
            "scenario": spec.name,
            "mobility": spec.mobility,
            "speed_mps": spec.speed_mps,
            "dataset": dataset,
            "aggregation": agg,
            "tau_global": tau,
            "scheduler": scheduler,
            "faults": (spec.faults.to_json()
                       if _fault_flags(spec)[0] else None),
            "n_seeds": n_seeds,
            "n_rounds": n_rounds,
            "final_acc_mean": _scalar_or_none(final_mean),
            "final_acc_std": _scalar_or_none(final_std),
            "wall_clock_mean_s": float(wall[i, :, -1].mean()),
            "acc_at_budget": {"budget_s": budget,
                              "acc_mean": _scalar_or_none(
                                  at_budget_mean)},
            "curves": {
                "wall_clock_s": wall[i].mean(axis=0).tolist(),
                "test_acc": _finite_or_none(acc_curve),
                "t_round_s": t_round[i].mean(axis=0).tolist(),
                "n_selected": n_sel[i].mean(axis=0).tolist(),
            },
            "seed_curves": {
                "wall_clock_s": wall[i].tolist(),
                "test_acc": [_finite_or_none(acc[i, s])
                             for s in range(n_seeds)],
            },
        }
        if hand is not None:
            records[pos]["handover_rate_mean"] = float(hand[i].mean())
            records[pos]["curves"]["handover_rate"] = \
                hand[i].mean(axis=0).tolist()
        if n_del is not None:
            records[pos]["delivered_mean"] = float(n_del[i].mean())
            records[pos]["delivered_rate_mean"] = float(del_rate[i].mean())
            records[pos]["goodput_mbit_s_mean"] = float(goodput[i].mean())
            records[pos]["curves"]["n_delivered"] = \
                n_del[i].mean(axis=0).tolist()
            records[pos]["curves"]["delivered_rate"] = \
                del_rate[i].mean(axis=0).tolist()
            records[pos]["curves"]["goodput_mbit_s"] = \
                goodput[i].mean(axis=0).tolist()
        if async_info is not None:
            records[pos].update(async_info)
            records[pos]["n_inflight_mean"] = float(n_inf[i].mean())
            records[pos]["n_dropped_mean"] = float(n_drp[i].mean())
            records[pos]["curves"]["n_inflight"] = \
                n_inf[i].mean(axis=0).tolist()
            records[pos]["curves"]["n_dropped"] = \
                n_drp[i].mean(axis=0).tolist()
    return records


def _check_async_args(aggregation_async: bool, tick_s, staleness_alpha,
                      buffer_size, compute: str,
                      aggregation: str | None) -> None:
    """Shared buffered-async argument validation (sweep + shard_sweep)."""
    if aggregation_async:
        if tick_s is None:
            raise ValueError("aggregation_async=True needs tick_s")
        if aggregation == "hierarchical":
            raise ValueError("aggregation_async composes with single-tier "
                             "aggregation only")
    elif (tick_s is not None or staleness_alpha != 0.0
          or buffer_size is not None):
        raise ValueError("tick_s/staleness_alpha/buffer_size only apply "
                         "with aggregation_async=True; they would silently "
                         "do nothing")


def run_learning_sweep(scenarios: Sequence[str | ScenarioSpec],
                       n_seeds: int = 2, n_rounds: int = 10,
                       cfg: WirelessConfig | None = None,
                       dataset: str = "mnist", n_train: int = 600,
                       n_test: int = 200, local_epochs: int = 2,
                       batch_size: int = 10, lr: float = 0.01,
                       eval_every: int = 1, shards_per_user: int = 2,
                       backend: str = "jax", fedavg_backend: str = "jax",
                       compute: str = "full", select_cap: int | None = None,
                       aggregation: str | None = None,
                       tau_global: int | None = None,
                       scheduler: str = "dagsa_jit",
                       faults=None, deadline_s: float | None = None,
                       aggregation_async: bool = False,
                       tick_s: float | None = None,
                       staleness_alpha: float = 0.0,
                       buffer_size: int | None = None,
                       user_chunk: int | None = None,
                       channel_dtype: str = "f32",
                       compress: str | None = None,
                       topk_frac: float | None = None,
                       partition: str | None = None,
                       dirichlet_alpha: float | None = None,
                       seed: int = 0) -> list[dict]:
    """Accuracy-vs-simulated-wall-clock curves, one record per scenario.

    Scenarios are bucketed by resolved array shape (n_users, n_bs),
    aggregation architecture and fault-graph flags; each bucket is ONE
    jit-compiled call covering all its scenarios x seeds — the fused round
    engine of :mod:`repro.fl.rounds` vmapped over the scenario parameter
    arrays.  ``aggregation``/``tau_global`` override every scenario's own
    choice (``hfl-*`` scenarios default to hierarchical with their
    registered tau).  ``faults`` (a preset name or
    :class:`~repro.fl.faults.FaultSpec`) overrides every scenario's fault
    model; ``deadline_s`` overrides just the round deadline;
    ``scheduler="dagsa-r"`` switches the greedy to the failure-aware
    delivery-discounted variant.  Dataset and per-seed partitions/inits
    are shared across scenarios (paired seeds).  See the module docstring
    for the record schema; hierarchical records additionally carry
    ``tau_global``, ``handover_rate_mean`` and a ``handover_rate`` curve;
    faulty records carry ``delivered_rate_mean`` / ``goodput_mbit_s_mean``
    and per-round delivered/goodput curves.

    ``aggregation_async=True`` switches every bucket's data plane to the
    buffered-async tick engine (``tick_s`` required; ``staleness_alpha`` /
    ``buffer_size`` as in :class:`repro.fl.FLConfig`) — the scan axis
    becomes aggregation ticks of ``tick_s`` simulated seconds, and records
    gain ``n_inflight_mean`` / ``n_dropped_mean`` plus per-tick
    ``n_inflight`` / ``n_dropped`` / delivery curves, so sync and async
    runs of the same scenarios yield directly comparable
    accuracy-vs-wall-clock curves.

    ``user_chunk`` streams the per-user channel tensors AND Algorithm 1's
    selection in blocks (any value; partial blocks are padded);
    ``channel_dtype="bf16"`` stores the [N, M] channel planes compactly;
    ``compute="selected"`` + ``select_cap`` keeps per-round learning state
    [cap]-shaped in both the sync and buffered-async engines
    (docs/SCALING.md).

    ``compress`` / ``topk_frac`` override every scenario's uplink
    compression mode (docs/COMPRESSION.md); compressed records carry
    ``compress`` / ``topk_frac`` / ``uplink_mbit_per_client`` /
    ``uplink_compression_ratio``.  ``partition="dirichlet"`` +
    ``dirichlet_alpha`` swap the label-shard split for the per-user
    Dirichlet label mixture.
    """
    from repro.data import make_dataset
    from repro.models import cnn

    if scheduler not in SWEEP_SCHEDULERS:
        raise ValueError(f"unknown sweep scheduler {scheduler!r}; "
                         f"choose from {SWEEP_SCHEDULERS}")
    _check_async_args(aggregation_async, tick_s, staleness_alpha,
                      buffer_size, compute, aggregation)
    specs = [get_scenario(s) if isinstance(s, str) else s for s in scenarios]
    if faults is not None:
        fs = fl_faults.get_faults(faults) if isinstance(faults, str) \
            else faults
        specs = [dataclasses.replace(s, faults=fs) for s in specs]
    if deadline_s is not None:
        specs = [dataclasses.replace(
            s, faults=dataclasses.replace(
                s.faults if s.faults is not None else fl_faults.NO_FAULTS,
                deadline_s=float(deadline_s))) for s in specs]
    base = cfg or WirelessConfig()
    data = make_dataset(dataset, seed=seed, n_train=n_train, n_test=n_test)
    h, wd, c = data.x_train.shape[1:]
    cnn_cfg = cnn.CNNConfig(height=h, width=wd, channels=c)

    k_cells, k_part, k_init = jax.random.split(jax.random.PRNGKey(seed), 3)
    seed_keys = jax.random.split(k_cells, n_seeds)   # paired across scenarios
    records: dict[int, dict] = {}
    buckets = _learning_buckets(specs, base, aggregation, tau_global,
                                compress, topk_frac, partition,
                                dirichlet_alpha)
    for (n_users, n_bs, agg, tau, faults_on, clip_on, comp, frac, part,
            alpha), group in buckets.items():
        if aggregation_async and agg == "hierarchical":
            raise ValueError(
                f"aggregation_async composes with single-tier aggregation "
                f"only; scenario(s) "
                f"{[s.name for _, s in group]} resolve to 'hierarchical'")
        _check_user_chunk(user_chunk, n_users)
        bcfg = dataclasses.replace(base, n_bs=n_bs)
        minp = int(np.ceil(bcfg.rho2 * n_users))
        buf = (int(buffer_size) if buffer_size is not None else n_users)
        x_c, y_c, w0 = _learning_seed_inputs(
            data, cnn_cfg, k_part, k_init, n_seeds, n_users, shards_per_user,
            partition=part, dirichlet_alpha=alpha)
        params = _scenario_params([s for _, s in group], bcfg)
        outs = _learning_bucket(
            params, seed_keys, x_c, y_c, w0, data.x_test, data.y_test,
            cfg=bcfg, n_rounds=n_rounds, minp=minp, epochs=local_epochs,
            batch_size=batch_size, lr=float(lr), eval_every=eval_every,
            backend=backend, fedavg_backend=fedavg_backend, compute=compute,
            select_cap=select_cap, aggregation=agg, tau_global=tau,
            scheduler=scheduler, faults_on=faults_on, clip_on=clip_on,
            async_on=aggregation_async,
            tick_s=(float(tick_s) if aggregation_async else 1.0),
            staleness_alpha=float(staleness_alpha),
            buffer_size=(buf if aggregation_async else 1),
            user_chunk=user_chunk, channel_dtype=channel_dtype,
            compress=comp, topk_frac=frac,
            n_models=len(mobility.MOBILITY_MODELS))
        async_info = ({"aggregation_async": True, "tick_s": float(tick_s),
                       "staleness_alpha": float(staleness_alpha),
                       "buffer_size": buf}
                      if aggregation_async else None)
        recs = _learning_records(group, outs, n_seeds, n_rounds,
                                 dataset, agg, tau, scheduler, async_info)
        if comp is not None:
            from repro.kernels import compress_topk as ct
            ratio = ct.compression_ratio(
                jax.tree.map(lambda a: a[0], w0), frac,
                comp == "topk-int8")
            for pos, _ in group:
                recs[pos].update(
                    compress=comp, topk_frac=frac,
                    uplink_compression_ratio=float(ratio),
                    uplink_mbit_per_client=float(bcfg.model_mbit * ratio))
        if part != "shard":
            for pos, _ in group:
                recs[pos].update(partition=part, dirichlet_alpha=alpha)
        records.update(recs)
    return [records[i] for i in range(len(specs))]


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser(
        description="Batched multi-scenario wireless/learning sweep "
                    "(JSON records).")
    ap.add_argument("--scenarios", default="all",
                    help="comma-separated registry names, or 'all' "
                         f"(registered: {','.join(SCENARIOS)})")
    ap.add_argument("--seeds", type=int, default=4)
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--backend", default="jax", choices=("jax", "pallas"))
    ap.add_argument("--seed", type=int, default=0, help="PRNG root seed")
    ap.add_argument("--shard", action="store_true",
                    help="shard the seeds x scenarios grid over a (data,) "
                         "device mesh (repro.launch.shard_sweep); output is "
                         "bit-identical to the single-device sweep")
    ap.add_argument("--mesh", type=int, default=None, metavar="D",
                    help="data-mesh size for --shard (default: every "
                         "visible device; force host devices with "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=D)")
    ap.add_argument("--user-chunk", type=int, default=None, metavar="B",
                    help="compute per-user channel tensors AND Algorithm 1 "
                         "selection in blocks of B users (bounds the "
                         "O(N*M*F) shadowing and [N, M] selection "
                         "intermediates; partial final blocks are padded)")
    ap.add_argument("--n-users", type=int, default=None, metavar="N",
                    help="override WirelessConfig.n_users (fleet size) for "
                         "every scenario")
    ap.add_argument("--rho1", type=float, default=None,
                    help="override WirelessConfig.rho1 (per-user "
                         "participation floor, Eq. (8g))")
    ap.add_argument("--rho2", type=float, default=None,
                    help="override WirelessConfig.rho2 (per-round "
                         "participation fraction floor)")
    ap.add_argument("--channel-dtype", default="f32",
                    choices=channel.CHANNEL_DTYPES,
                    help="storage dtype of the per-round [N, M] channel "
                         "planes (bf16 halves channel bytes/user; "
                         "docs/SCALING.md)")
    ap.add_argument("--out", default="-",
                    help="output path for the JSON list ('-' = stdout)")
    ap.add_argument("--learning", action="store_true",
                    help="run the full FL data plane and emit "
                         "accuracy-vs-wall-clock curves per scenario x seed")
    ap.add_argument("--dataset", default="mnist")
    ap.add_argument("--n-train", type=int, default=600)
    ap.add_argument("--n-test", type=int, default=200)
    ap.add_argument("--local-epochs", type=int, default=2)
    ap.add_argument("--batch-size", type=int, default=10)
    ap.add_argument("--lr", type=float, default=0.01)
    ap.add_argument("--eval-every", type=int, default=1)
    ap.add_argument("--fedavg-backend", default="jax",
                    choices=("jax", "pallas"))
    ap.add_argument("--compute", default="full", choices=("full", "selected"))
    ap.add_argument("--select-cap", type=int, default=None)
    ap.add_argument("--aggregation", default=None,
                    choices=("single", "hierarchical"),
                    help="override every scenario's aggregation "
                         "architecture (--learning only)")
    ap.add_argument("--tau-global", type=int, default=None,
                    help="global sync period for hierarchical aggregation "
                         "(--learning only)")
    ap.add_argument("--scheduler", default="dagsa_jit",
                    choices=SWEEP_SCHEDULERS,
                    help="round scheduler; 'dagsa-r' discounts candidates "
                         "by estimated delivery probability "
                         "(--learning only)")
    ap.add_argument("--faults", default=None,
                    choices=tuple(fl_faults.FAULT_PRESETS),
                    help="override every scenario's fault model with this "
                         "preset (--learning only)")
    ap.add_argument("--deadline", type=float, default=None, metavar="T",
                    help="round deadline in simulated seconds: the server "
                         "stops waiting and drops late updates "
                         "(--learning only)")
    ap.add_argument("--async", dest="async_agg", action="store_true",
                    help="buffered-async aggregation: tick the server every "
                         "--tick simulated seconds and aggregate whatever "
                         "landed, staleness-discounted (--learning only; "
                         "docs/ASYNC.md)")
    ap.add_argument("--tick", type=float, default=None, metavar="S",
                    help="async aggregation period in simulated seconds "
                         "(required with --async)")
    ap.add_argument("--staleness-alpha", type=float, default=0.0,
                    metavar="A",
                    help="staleness discount exponent in (1+s)^(-A) "
                         "(--async only; 0 disables)")
    ap.add_argument("--buffer-size", type=int, default=None, metavar="B",
                    help="async event-queue capacity (default n_users, "
                         "which never overflows)")
    ap.add_argument("--compress", default=None, choices=COMPRESS_MODES,
                    help="override every scenario's uplink compression "
                         "mode: top-k sparsification, optionally + int8 "
                         "stochastic rounding (--learning only; "
                         "docs/COMPRESSION.md)")
    ap.add_argument("--topk-frac", type=float, default=None, metavar="F",
                    help="fraction of each leaf's entries a client uploads "
                         "(requires a compress mode)")
    ap.add_argument("--partition", default=None, choices=PARTITIONS,
                    help="override every scenario's Non-IID data split "
                         "(--learning only)")
    ap.add_argument("--dirichlet-alpha", type=float, default=None,
                    metavar="A",
                    help="Dirichlet concentration for --partition dirichlet "
                         "(lower = more pathological)")
    args = ap.parse_args()

    names = list(SCENARIOS) if args.scenarios == "all" \
        else args.scenarios.split(",")
    overrides = {k: v for k, v in (("n_users", args.n_users),
                                   ("rho1", args.rho1),
                                   ("rho2", args.rho2)) if v is not None}
    cfg = dataclasses.replace(WirelessConfig(), **overrides) \
        if overrides else None
    if args.mesh is not None and not args.shard:
        ap.error("--mesh only applies with --shard; it would silently "
                 "do nothing")
    if not args.learning and (args.faults is not None
                              or args.deadline is not None
                              or args.scheduler != "dagsa_jit"):
        ap.error("--faults/--deadline/--scheduler shape the FL round loop; "
                 "they only apply with --learning")
    if not args.learning and (args.async_agg or args.tick is not None
                              or args.staleness_alpha != 0.0
                              or args.buffer_size is not None):
        ap.error("--async/--tick/--staleness-alpha/--buffer-size shape the "
                 "FL round loop; they only apply with --learning")
    if args.async_agg and args.tick is None:
        ap.error("--async needs --tick (the aggregation period in "
                 "simulated seconds)")
    if not args.learning and (args.compress is not None
                              or args.topk_frac is not None
                              or args.partition is not None
                              or args.dirichlet_alpha is not None):
        ap.error("--compress/--topk-frac/--partition/--dirichlet-alpha "
                 "shape the FL round loop; they only apply with --learning")
    # --topk-frac without --compress and --dirichlet-alpha without
    # --partition dirichlet stay legal here: a scenario may resolve the
    # mode itself (e.g. compressed-uplink / non-iid-pathological); the
    # per-scenario resolution raises when the knob would truly do nothing.
    if args.shard:
        # local import: shard_sweep imports this module's cell functions
        from repro.launch import shard_sweep
        learning_fn = partial(shard_sweep.run_shard_learning_sweep,
                              n_devices=args.mesh)
        wireless_fn = partial(shard_sweep.run_shard_sweep,
                              n_devices=args.mesh)
    else:
        learning_fn, wireless_fn = run_learning_sweep, run_sweep
    if args.learning:
        records = learning_fn(
            names, n_seeds=args.seeds, n_rounds=args.rounds, cfg=cfg,
            dataset=args.dataset, n_train=args.n_train, n_test=args.n_test,
            local_epochs=args.local_epochs, batch_size=args.batch_size,
            lr=args.lr, eval_every=args.eval_every, backend=args.backend,
            fedavg_backend=args.fedavg_backend, compute=args.compute,
            select_cap=args.select_cap, aggregation=args.aggregation,
            tau_global=args.tau_global, scheduler=args.scheduler,
            faults=args.faults, deadline_s=args.deadline,
            aggregation_async=args.async_agg, tick_s=args.tick,
            staleness_alpha=args.staleness_alpha,
            buffer_size=args.buffer_size,
            user_chunk=args.user_chunk,
            channel_dtype=args.channel_dtype, compress=args.compress,
            topk_frac=args.topk_frac, partition=args.partition,
            dirichlet_alpha=args.dirichlet_alpha, seed=args.seed)
        summary = " ".join(
            f"{r['scenario']}="
            f"{r['final_acc_mean']:.3f}" if r["final_acc_mean"] is not None
            else f"{r['scenario']}=n/a" for r in records)
    else:
        records = wireless_fn(names, n_seeds=args.seeds,
                              n_rounds=args.rounds, cfg=cfg,
                              backend=args.backend,
                              user_chunk=args.user_chunk,
                              channel_dtype=args.channel_dtype,
                              seed=args.seed)
        summary = " ".join(f"{r['scenario']}={r['t_round_mean_s']:.3f}s"
                           for r in records)
    payload = json.dumps(records, indent=2)
    if args.out == "-":
        print(payload)
    else:
        with open(args.out, "w") as f:
            f.write(payload + "\n")
        print(f"wrote {args.out}: {summary}")


if __name__ == "__main__":
    main()
