"""Device-sharded fleet sweeps: the seeds x scenarios grid over a mesh.

:mod:`repro.launch.sweep` compiles one call per shape bucket and vmaps the
whole seeds x scenarios grid onto ONE device.  This layer scales that
horizontally: the grid is flattened to independent (scenario, seed) cells,
padded to a multiple of the mesh size (cyclic repetition, sliced off after
the gather), and ``shard_map``'ed over a 1-D ``("data",)`` mesh
(:func:`repro.launch.mesh.make_data_mesh`) — every device runs the SAME
per-cell scan :mod:`repro.launch.sweep` uses, just on its slice of cells.
Cells are independent, so no collectives cross the wire and the output is
**bit-identical** to the single-device sweep (asserted by
``tests/test_shard_sweep.py``; CI diffs the emitted JSON byte-for-byte).

Memory at fleet scale is governed by two independent knobs:

* ``n_devices`` — how many grid cells live on one device at a time;
* ``user_chunk`` — inside one cell, the per-user channel tensors (the
  O(N x M x F) shadowing features) are computed in blocks of ``user_chunk``
  users (:func:`repro.launch.sweep._dist_and_shadow`), so an N >= 100k-user
  world fits per-device memory while the greedy still sees the full
  [N, M] problem.

CLI: ``python -m repro.launch.sweep --shard [--mesh D] [--user-chunk B]``
(records and JSON identical to the unsharded CLI).  On CPU, force host
devices with ``XLA_FLAGS=--xla_force_host_platform_device_count=D``.

:func:`shard_schedule_batch` applies the same recipe to the fleet axis of
:func:`repro.core.dagsa_jit.dagsa_schedule_batch` — F same-shape cells'
schedules, scattered over the mesh, decisions identical to the
single-device batch.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import dagsa_jit, mobility
from repro.core.scenario import ScenarioSpec, get_scenario
from repro.core.types import ScheduleResult, SchedulingProblem, WirelessConfig
from repro.launch import sweep
from repro.launch.mesh import make_data_mesh
from repro.launch.sharding import pad_leading, padded_count, unpad_leading


# ---------------------------------------------------------- grid plumbing --
def _grid_cells(params: dict, seed_keys: jax.Array) -> tuple[dict, jax.Array]:
    """Flatten [S] scenario params x [seeds] keys to per-cell arrays [G].

    Cell ``g`` is (scenario ``g // n_seeds``, seed ``g % n_seeds``) — the
    row-major order the bucket output reshapes back to [S, seeds, ...].
    """
    n_seeds = seed_keys.shape[0]
    cell_params = jax.tree.map(lambda a: jnp.repeat(a, n_seeds, axis=0),
                               params)
    n_scen = jax.tree.leaves(params)[0].shape[0]
    cell_keys = jnp.tile(seed_keys, (n_scen, 1))
    return cell_params, cell_keys


def _grid_shape(outs: dict, n_cells: int, n_scen: int, n_seeds: int) -> dict:
    """Unpad [G_pad, ...] bucket outputs and restore the [S, seeds, ...]
    layout the record builders expect."""
    outs = unpad_leading(outs, n_cells)
    return jax.tree.map(
        lambda a: a.reshape(n_scen, n_seeds, *a.shape[1:]), outs)


# ---------------------------------------------------------- wireless sweep --
@partial(jax.jit, static_argnames=("mesh", "cfg", "n_rounds",
                                   "min_participants", "backend",
                                   "user_chunk", "channel_dtype",
                                   "n_models"))
def _shard_sweep_bucket(cell_params: dict, cell_keys: jax.Array, *, mesh,
                        cfg: WirelessConfig, n_rounds: int,
                        min_participants: int, backend: str,
                        user_chunk: int | None, channel_dtype: str,
                        n_models: int) -> dict:
    """One shape bucket's padded cell grid, shard_map'ed over the mesh.

    ``n_models`` pins the mobility-registry size into the compilation key
    (same contract as ``sweep._sweep_bucket``).
    """
    run = partial(sweep._one_cell, cfg=cfg, n_rounds=n_rounds,
                  min_participants=min_participants, backend=backend,
                  user_chunk=user_chunk, channel_dtype=channel_dtype)
    mapped = jax.shard_map(
        jax.vmap(lambda p, k: run(p, k)), mesh=mesh,
        in_specs=(P("data"), P("data")), out_specs=P("data"),
        check_vma=False)
    return mapped(cell_params, cell_keys)


def run_shard_sweep(scenarios: Sequence[str | ScenarioSpec],
                    n_seeds: int = 4, n_rounds: int = 10,
                    cfg: WirelessConfig | None = None, backend: str = "jax",
                    user_chunk: int | None = None,
                    channel_dtype: str = "f32", seed: int = 0,
                    mesh=None, n_devices: int | None = None) -> list[dict]:
    """Device-sharded :func:`repro.launch.sweep.run_sweep`.

    Same arguments, same record schema, bit-identical values — plus
    ``mesh`` (a ready ``("data",)`` mesh) or ``n_devices`` (build one over
    the first N visible devices; default all).  Uneven grids (cells not a
    multiple of the mesh size) are padded cyclically and sliced.
    """
    if mesh is None:
        mesh = make_data_mesh(n_devices)
    n_shards = mesh.devices.size
    specs = [get_scenario(s) if isinstance(s, str) else s for s in scenarios]
    base = cfg or WirelessConfig()
    records: dict[int, dict] = {}
    for (n_users, n_bs), group in sweep._wireless_buckets(specs,
                                                          base).items():
        sweep._check_user_chunk(user_chunk, n_users)
        bcfg = dataclasses.replace(base, n_bs=n_bs)
        minp = int(np.ceil(bcfg.rho2 * n_users))
        params = sweep._scenario_params([s for _, s in group], bcfg)
        seed_keys = jax.random.split(jax.random.PRNGKey(seed), n_seeds)
        cell_params, cell_keys = _grid_cells(params, seed_keys)
        n_cells = len(group) * n_seeds
        n_pad = padded_count(n_cells, n_shards)
        outs = _shard_sweep_bucket(
            pad_leading(cell_params, n_pad), pad_leading(cell_keys, n_pad),
            mesh=mesh, cfg=bcfg, n_rounds=n_rounds, min_participants=minp,
            backend=backend, user_chunk=user_chunk,
            channel_dtype=channel_dtype,
            n_models=len(mobility.MOBILITY_MODELS))
        outs = _grid_shape(outs, n_cells, len(group), n_seeds)
        records.update(sweep._wireless_records(group, outs, n_seeds,
                                               n_rounds))
    return [records[i] for i in range(len(specs))]


# ---------------------------------------------------------- learning sweep --
@partial(jax.jit, static_argnames=("mesh", "cfg", "n_rounds", "minp",
                                   "epochs", "batch_size", "lr",
                                   "eval_every", "backend", "fedavg_backend",
                                   "compute", "select_cap", "aggregation",
                                   "tau_global", "scheduler", "faults_on",
                                   "clip_on", "async_on", "tick_s",
                                   "staleness_alpha", "buffer_size",
                                   "user_chunk", "channel_dtype",
                                   "compress", "topk_frac", "n_models"))
def _shard_learning_bucket(cell_params: dict, cell_keys: jax.Array,
                           cell_seed: jax.Array, x_c, y_c, w0, x_test,
                           y_test, *, mesh, cfg: WirelessConfig,
                           n_rounds: int, minp: int, epochs: int,
                           batch_size: int, lr: float, eval_every: int,
                           backend: str, fedavg_backend: str, compute: str,
                           select_cap, aggregation: str, tau_global: int,
                           scheduler: str, faults_on: bool, clip_on: bool,
                           async_on: bool, tick_s: float,
                           staleness_alpha: float, buffer_size: int,
                           user_chunk: int | None, channel_dtype: str,
                           compress: str | None, topk_frac: float,
                           n_models: int) -> dict:
    """Learning-sweep bucket over the mesh.

    The per-seed client data / model inits stay replicated ([seeds, ...]
    leaves, ``P()`` specs) and each cell gathers its seed's slice inside the
    shard — cells on one device only materialise their own [N, ...] views.
    The buffered-async engine (``async_on``) shards the same way: the event
    queue is per-cell scan state, so no collectives cross the wire and the
    async curves stay bit-identical to the single-device sweep.
    """
    run = partial(sweep._one_learning_cell, cfg=cfg, n_rounds=n_rounds,
                  minp=minp, epochs=epochs, batch_size=batch_size, lr=lr,
                  eval_every=eval_every, backend=backend,
                  fedavg_backend=fedavg_backend, compute=compute,
                  select_cap=select_cap, aggregation=aggregation,
                  tau_global=tau_global, scheduler=scheduler,
                  faults_on=faults_on, clip_on=clip_on, async_on=async_on,
                  tick_s=tick_s, staleness_alpha=staleness_alpha,
                  buffer_size=buffer_size, user_chunk=user_chunk,
                  channel_dtype=channel_dtype, compress=compress,
                  topk_frac=topk_frac)

    def local(cp, ck, cs, xc, yc, w, xt, yt):
        def cell(p, k, j):
            return run(p, k, xc[j], yc[j],
                       jax.tree.map(lambda a: a[j], w), xt, yt)

        return jax.vmap(cell)(cp, ck, cs)

    mapped = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P("data"), P("data"), P("data"), P(), P(), P(), P(), P()),
        out_specs=P("data"), check_vma=False)
    return mapped(cell_params, cell_keys, cell_seed, x_c, y_c, w0, x_test,
                  y_test)


def run_shard_learning_sweep(scenarios: Sequence[str | ScenarioSpec],
                             n_seeds: int = 2, n_rounds: int = 10,
                             cfg: WirelessConfig | None = None,
                             dataset: str = "mnist", n_train: int = 600,
                             n_test: int = 200, local_epochs: int = 2,
                             batch_size: int = 10, lr: float = 0.01,
                             eval_every: int = 1, shards_per_user: int = 2,
                             backend: str = "jax",
                             fedavg_backend: str = "jax",
                             compute: str = "full",
                             select_cap: int | None = None,
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
                             seed: int = 0, mesh=None,
                             n_devices: int | None = None) -> list[dict]:
    """Device-sharded :func:`repro.launch.sweep.run_learning_sweep`.

    Same arguments, record schema and values (bit-identical curves); cells
    scatter over ``mesh`` / the first ``n_devices`` visible devices.  The
    buffered-async knobs (``aggregation_async``/``tick_s``/...) follow the
    same contract: per-cell event queues are scan state, so async curves
    are byte-identical to the single-device sweep too.
    """
    from repro.data import make_dataset
    from repro.fl import faults as fl_faults
    from repro.models import cnn

    if scheduler not in sweep.SWEEP_SCHEDULERS:
        raise ValueError(f"unknown sweep scheduler {scheduler!r}; "
                         f"choose from {sweep.SWEEP_SCHEDULERS}")
    sweep._check_async_args(aggregation_async, tick_s, staleness_alpha,
                            buffer_size, compute, aggregation)
    if mesh is None:
        mesh = make_data_mesh(n_devices)
    n_shards = mesh.devices.size
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
    buckets = sweep._learning_buckets(specs, base, aggregation, tau_global,
                                      compress, topk_frac, partition,
                                      dirichlet_alpha)
    for (n_users, n_bs, agg, tau, faults_on, clip_on, comp, frac, part,
            alpha), group in buckets.items():
        if aggregation_async and agg == "hierarchical":
            raise ValueError(
                f"aggregation_async composes with single-tier aggregation "
                f"only; scenario(s) "
                f"{[s.name for _, s in group]} resolve to 'hierarchical'")
        sweep._check_user_chunk(user_chunk, n_users)
        bcfg = dataclasses.replace(base, n_bs=n_bs)
        minp = int(np.ceil(bcfg.rho2 * n_users))
        buf = (int(buffer_size) if buffer_size is not None else n_users)
        x_c, y_c, w0 = sweep._learning_seed_inputs(
            data, cnn_cfg, k_part, k_init, n_seeds, n_users, shards_per_user,
            partition=part, dirichlet_alpha=alpha)
        params = sweep._scenario_params([s for _, s in group], bcfg)
        cell_params, cell_keys = _grid_cells(params, seed_keys)
        cell_seed = jnp.tile(jnp.arange(n_seeds, dtype=jnp.int32),
                             len(group))
        n_cells = len(group) * n_seeds
        n_pad = padded_count(n_cells, n_shards)
        outs = _shard_learning_bucket(
            pad_leading(cell_params, n_pad), pad_leading(cell_keys, n_pad),
            pad_leading(cell_seed, n_pad), x_c, y_c, w0, data.x_test,
            data.y_test, mesh=mesh, cfg=bcfg, n_rounds=n_rounds, minp=minp,
            epochs=local_epochs, batch_size=batch_size, lr=float(lr),
            eval_every=eval_every, backend=backend,
            fedavg_backend=fedavg_backend, compute=compute,
            select_cap=select_cap, aggregation=agg, tau_global=tau,
            scheduler=scheduler, faults_on=faults_on, clip_on=clip_on,
            async_on=aggregation_async,
            tick_s=(float(tick_s) if aggregation_async else 1.0),
            staleness_alpha=float(staleness_alpha),
            buffer_size=(buf if aggregation_async else 1),
            user_chunk=user_chunk, channel_dtype=channel_dtype,
            compress=comp, topk_frac=frac,
            n_models=len(mobility.MOBILITY_MODELS))
        outs = _grid_shape(outs, n_cells, len(group), n_seeds)
        async_info = ({"aggregation_async": True, "tick_s": float(tick_s),
                       "staleness_alpha": float(staleness_alpha),
                       "buffer_size": buf}
                      if aggregation_async else None)
        recs = sweep._learning_records(group, outs, n_seeds, n_rounds,
                                       dataset, agg, tau, scheduler,
                                       async_info)
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


# ------------------------------------------------------- fleet scheduler ---
@partial(jax.jit, static_argnames=("mesh", "min_participants", "method",
                                   "iters", "backend", "interpret",
                                   "selection_block"))
def _shard_schedule(snr, coeff, tcomp, bs_bw, necessary, keys, *, mesh,
                    min_participants: int, method: str, iters, backend: str,
                    interpret, selection_block=None):
    """Padded fleet arrays, shard_map'ed over the mesh.

    Module-level jit (mesh and greedy knobs static) so repeated
    :func:`shard_schedule_batch` calls at the same shapes reuse one
    compilation instead of retracing per call.
    """
    fn = partial(dagsa_jit._schedule_batch,
                 min_participants=min_participants, method=method,
                 iters=iters, backend=backend, interpret=interpret,
                 selection_block=selection_block)
    mapped = jax.shard_map(
        lambda s, c, t, b, ne, k: fn(s, c, t, b, ne, keys=k), mesh=mesh,
        in_specs=(P("data"),) * 6, out_specs=P("data"), check_vma=False)
    return mapped(snr, coeff, tcomp, bs_bw, necessary, keys)


def shard_schedule_batch(problems, keys: jax.Array, method: str = "newton",
                         iters: int | None = None, backend: str = "jax",
                         interpret: bool | None = None,
                         selection_block: int | None = None, mesh=None,
                         n_devices: int | None = None) -> ScheduleResult:
    """:func:`repro.core.dagsa_jit.dagsa_schedule_batch` over a device mesh.

    The fleet axis is padded to a multiple of the mesh size and scattered;
    every device runs the identical vmapped greedy on its slice, so the
    decisions match the single-device batch exactly (parity-tested).  The
    [F, N, M] problem tensors arrive sharded, so per-device memory is
    F/D cells' worth — the fleet-size scale-out knob to pair with the
    per-cell ``user_chunk``.
    """
    if not isinstance(problems, SchedulingProblem):
        problems = dagsa_jit.stack_problems(problems)
    if mesh is None:
        mesh = make_data_mesh(n_devices)
    n_shards = mesh.devices.size
    fleet = problems.snr.shape[0]
    n_pad = padded_count(fleet, n_shards)
    arrs = (problems.snr, problems.coeff, problems.tcomp, problems.bs_bw,
            problems.necessary, keys)
    arrs = pad_leading(arrs, n_pad)
    out = _shard_schedule(*arrs, mesh=mesh,
                          min_participants=int(problems.min_participants),
                          method=method, iters=iters, backend=backend,
                          interpret=interpret,
                          selection_block=selection_block)
    assign, selected, bw, t_k, t_round, *_ = unpad_leading(out, fleet)
    return ScheduleResult(assign=assign, selected=selected, bw=bw,
                          bs_time=t_k, t_round=t_round)
