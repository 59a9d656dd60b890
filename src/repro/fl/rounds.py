"""The mobility-aware FL round engine (paper §II + §IV simulation loop).

Per communication round:
  1. users move (mobility model chosen by the scenario),
  2. BSs observe positions/channels -> SchedulingProblem,
  3. the chosen scheduler (DAGSA or a baseline) picks users/BSs/bandwidth,
  4. clients run E local epochs in one compiled vmap step — either the whole
     fleet (the mask only enters the FedAvg reduction, Eq. 2 — constant
     compiled graph) or a static-size padded subset of scheduled clients
     (``compute="selected"``),
  5. participation state and simulated wall-clock (Eq. 3) advance,
  6. periodic global-model evaluation on the test split.

The simulated wall-clock, not the number of rounds, is the x-axis of every
paper figure — the whole point is latency-aware scheduling.

Execution modes (all share ONE traced round step, so they agree bit-for-bit
on the training trajectory):

  * ``fused``  — the whole run is a single ``lax.scan`` over rounds inside
    one jit: zero per-round Python dispatches, zero per-round host syncs;
    per-round records come back as stacked device arrays and cross to the
    host once at the end.  Requires a jit-able scheduler (everything except
    the host-numpy ``dagsa``).  This is what :func:`FLSimulation.run` uses
    by default and what the learning-curve sweep
    (:mod:`repro.launch.sweep`) vmaps over seeds x scenarios.
  * ``step``   — one jitted dispatch per round (the fused step without the
    scan); :func:`FLSimulation.run_round` is this thin legacy wrapper.
  * ``eager``  — the seed's original per-round path: eager control plane,
    separate fleet/aggregation dispatches, per-round host syncs.  Kept for
    the host ``dagsa`` scheduler and as the benchmark baseline
    (``benchmarks/bench_fl_rounds.py``).

Aggregation architectures (``FLConfig.aggregation``):

  * ``single``       — the paper's one-tier Eq. (2): every scheduled user
    uploads to the global server each round.
  * ``hierarchical`` — the multi-BS architecture of *Mobility-Aware Cluster
    Federated Learning in Hierarchical Wireless Networks* (arXiv
    2108.09103): each BS edge-aggregates its users' updates every round
    (per-BS segmented Eq. (2), :func:`repro.fl.server.fedavg_segmented` /
    the Pallas ``fedavg_segment_reduce`` kernel), edge models sync into the
    global model every ``tau_global`` rounds, and a user that hands over
    between cells mid-interval pulls the new cell's (diverged) edge model —
    the convergence effect that paper studies.  Lives entirely inside the
    traced round step (edge states ride the ``lax.scan`` carry), so fused
    runs stay one compiled call.

Buffered-async aggregation (``FLConfig.aggregation_async``, docs/ASYNC.md):
the synchronous Eq. (3) round blocks on the slowest scheduled uplink; the
async engine instead advances simulated time in fixed ``tick_s`` steps,
dispatches clients whose updates complete at Eq. (1) completion times
(:func:`repro.core.latency.completion_times`), parks in-flight updates in a
fixed-capacity event queue carried as sorted arrays in the ``lax.scan``
carry (no host callbacks), and aggregates everything that lands within the
tick under the staleness discount ``w(s) = (1 + s)^(-alpha)``
(:func:`repro.fl.server.staleness_weights`, after Online-FEEL, arXiv
2410.10833) folded into the same masked Eq. (2) reduction.  With ``tick_s``
covering the slowest client and ``alpha = 0`` the engine degenerates
BIT-IDENTICALLY to the synchronous fused path — the correctness anchor
``tests/test_async.py`` locks down.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (MobilityState, ParticipationState, WirelessConfig,
                        channel, dagsa_jit, latency, mobility,
                        scheduler as sched)
from repro.core.scenario import (AGGREGATIONS, COMPRESS_MODES, PARTITIONS,
                                 get_scenario)
from repro.core.types import (ClientState, RoundState, ScheduleResult,
                              SchedulingProblem, ServerState, WorldState)
from repro.data import make_dataset
from repro.fl import client as fl_client
from repro.fl import faults as fl_faults
from repro.fl import server as fl_server
from repro.fl.partition import dirichlet_partition, shard_partition
from repro.models import cnn

PyTree = Any

# Schedulers whose round step traces (everything but the host-numpy
# greedies; "dagsa-r-host" is the host-side parity twin of "dagsa-r").
# The stateful online policies trace too — their per-user estimates ride
# the RoundState.sched carry slot as pure transforms.
FUSED_SCHEDULERS = ("dagsa_jit", "dagsa-r", "rs", "ub", "fedcs_low",
                    "fedcs_high", "sa") + sched.STATEFUL_SCHEDULERS

COMPUTE_MODES = ("full", "selected")
FEDAVG_BACKENDS = ("jax", "pallas")

# Global sync period when a config asks for hierarchical aggregation but
# neither it nor its scenario names a tau.
DEFAULT_TAU_GLOBAL = 5


@dataclasses.dataclass(frozen=True)
class FLConfig:
    """End-to-end FL simulation config.

    Precedence of the world-defining knobs (most specific wins):

      1. ``speed_mps`` / ``hetero_bw`` — explicit per-field overrides; they
         beat everything, including a named ``scenario``.
      2. ``scenario`` — a registry name (:mod:`repro.core.scenario`) that
         sets mobility model, BS layout, bandwidth draw and shadowing in
         one word; its static overrides are baked into ``wireless``.
      3. ``wireless`` — the base :class:`WirelessConfig`.

    Setting ``speed_mps > 0`` on a scenario whose mobility model is
    ``static`` raises (the override would silently do nothing).
    """

    dataset: str = "mnist"
    scheduler: str = "dagsa"
    wireless: WirelessConfig = dataclasses.field(default_factory=WirelessConfig)
    local_epochs: int = 10          # paper §IV
    batch_size: int = 16
    lr: float = 0.01                # paper §IV
    shards_per_user: int = 2        # paper §IV Non-IID split
    eval_every: int = 1
    seed: int = 0
    n_train: Optional[int] = None   # defaults per dataset
    n_test: Optional[int] = None
    cnn: cnn.CNNConfig | None = None
    hetero_bw: bool = False         # Fig. 3: B_k ~ U[0.5, 1.5] MHz
    speed_mps: Optional[float] = None  # override wireless.speed_mps (Fig. 4)
    bs_layout: str = "grid"         # grid | uniform (uniform = paper's
                                    # literal reading; grid avoids the
                                    # degenerate all-in-one-corner draw)
    scenario: Optional[str] = None  # registry name (core.scenario); see the
                                    # precedence rules in the class docstring
    compute: str = "full"           # full: every client trains, mask at
                                    # aggregation; selected: static-size
                                    # padded top-K gather of scheduled
                                    # clients (see client.topk_selected_indices)
    select_cap: Optional[int] = None   # K for compute="selected"; default
                                       # ceil(rho2 * N), the Eq. (8h) floor
    fedavg_backend: str = "jax"     # jax oracle | pallas fused reduction
                                    # (interpret mode auto-enabled off-TPU)
    aggregation: Optional[str] = None  # single | hierarchical; None inherits
                                       # the scenario's choice (default
                                       # single).  hierarchical: per-BS edge
                                       # Eq. (2) every round, global sync
                                       # every tau_global rounds, handover
                                       # users pull the new cell's edge model
    tau_global: Optional[int] = None   # global sync period (rounds); only
                                       # meaningful with hierarchical
    shard: bool = False             # place the client-batched tensors on a
                                    # ("data",) device mesh so the fleet's
                                    # local SGD data-parallelises over
                                    # devices (GSPMD; see docs/SCALING.md).
                                    # Numerically equivalent, not bit-equal:
                                    # the FedAvg reduction order changes.
    mesh_devices: Optional[int] = None  # mesh size for shard (default: all
                                        # visible devices)
    faults: Any = None              # fault model: a repro.fl.faults.FaultSpec,
                                    # a FAULT_PRESETS name, or None to inherit
                                    # the scenario's fault model (default: the
                                    # perfect world).  docs/ROBUSTNESS.md
    deadline_s: Optional[float] = None  # round deadline T_dl override (s);
                                        # late clients are dropped, not
                                        # waited for (deadline-truncated
                                        # Eq. (3))
    aggregation_async: bool = False  # buffered-async engine: aggregate every
                                     # tick_s of simulated time from the
                                     # in-flight event queue instead of
                                     # blocking on the slowest uplink
                                     # (docs/ASYNC.md)
    tick_s: Optional[float] = None   # async aggregation period (simulated
                                     # seconds); REQUIRED when
                                     # aggregation_async
    staleness_alpha: float = 0.0     # staleness discount exponent alpha in
                                     # w(s) = (1+s)^(-alpha); 0 disables
    buffer_size: Optional[int] = None   # event-queue capacity (in-flight
                                        # updates); default n_users, which
                                        # can never overflow (each client
                                        # has at most one update in flight)
    compress: Optional[str] = None   # uplink update compression mode
                                     # ("topk" | "topk-int8"); None inherits
                                     # the scenario's choice (default off).
                                     # docs/COMPRESSION.md
    topk_frac: Optional[float] = None   # fraction of each leaf's entries a
                                        # client uploads; None inherits the
                                        # scenario's (default 1.0 = dense)
    partition: Optional[str] = None  # data split: "shard" (paper §IV label
                                     # shards) | "dirichlet" (per-user label
                                     # mixture ~ Dir(alpha)); None inherits
                                     # the scenario's choice (default shard)
    dirichlet_alpha: Optional[float] = None   # Dirichlet concentration;
                                              # REQUIRED when the resolved
                                              # partition is "dirichlet"

    def __post_init__(self):
        if self.compute not in COMPUTE_MODES:
            raise ValueError(f"unknown compute mode {self.compute!r}; "
                             f"choose from {COMPUTE_MODES}")
        if self.fedavg_backend not in FEDAVG_BACKENDS:
            raise ValueError(f"unknown fedavg backend "
                             f"{self.fedavg_backend!r}; "
                             f"choose from {FEDAVG_BACKENDS}")
        if self.aggregation is not None and self.aggregation not in AGGREGATIONS:
            raise ValueError(f"unknown aggregation {self.aggregation!r}; "
                             f"choose from {AGGREGATIONS}")
        if self.tau_global is not None and self.tau_global < 1:
            raise ValueError("tau_global must be >= 1")
        if self.mesh_devices is not None and not self.shard:
            raise ValueError("mesh_devices only applies with shard=True; "
                             "it would silently do nothing")
        if self.deadline_s is not None and not self.deadline_s > 0.0:
            raise ValueError("deadline_s must be > 0")
        if (self.faults is not None and not isinstance(self.faults, str)
                and not hasattr(self.faults, "active")):
            raise ValueError(
                "faults must be a repro.fl.faults.FaultSpec, a preset name, "
                f"or None; got {type(self.faults).__name__}")
        if self.aggregation_async:
            if self.tick_s is None:
                raise ValueError(
                    "aggregation_async=True needs tick_s (the simulated "
                    "aggregation period in seconds)")
            if self.aggregation == "hierarchical":
                raise ValueError(
                    "aggregation_async composes with the single-tier "
                    "Eq. (2) only; hierarchical edge aggregation is "
                    "synchronous by construction")
        else:
            for name, val, default in (("tick_s", self.tick_s, None),
                                       ("staleness_alpha",
                                        self.staleness_alpha, 0.0),
                                       ("buffer_size", self.buffer_size,
                                        None)):
                if val != default:
                    raise ValueError(
                        f"{name}={val!r} only applies with "
                        f"aggregation_async=True; it would silently do "
                        f"nothing")
        if self.tick_s is not None and not self.tick_s > 0.0:
            raise ValueError("tick_s must be > 0")
        if self.staleness_alpha < 0.0:
            raise ValueError("staleness_alpha must be >= 0")
        if self.buffer_size is not None and self.buffer_size < 1:
            raise ValueError("buffer_size must be >= 1")
        if self.compress is not None and self.compress not in COMPRESS_MODES:
            raise ValueError(f"unknown compress mode {self.compress!r}; "
                             f"choose from {COMPRESS_MODES}")
        if self.topk_frac is not None:
            if not 0.0 < self.topk_frac <= 1.0:
                raise ValueError("topk_frac must be in (0, 1]")
            if self.compress is None and self.scenario is None:
                raise ValueError(
                    f"topk_frac={self.topk_frac} only applies with a "
                    f"compress mode (or a scenario that sets one); it "
                    f"would silently do nothing")
        if self.partition is not None and self.partition not in PARTITIONS:
            raise ValueError(f"unknown partition {self.partition!r}; "
                             f"choose from {PARTITIONS}")
        if self.dirichlet_alpha is not None:
            if not self.dirichlet_alpha > 0.0:
                raise ValueError("dirichlet_alpha must be > 0")
            if self.partition == "shard":
                raise ValueError(
                    f"dirichlet_alpha={self.dirichlet_alpha} only applies "
                    f"with partition='dirichlet'; it would silently do "
                    f"nothing")


@dataclasses.dataclass
class RoundRecord:
    round_idx: int
    t_round: float        # simulated round latency (s), Eq. (3)
    wall_clock: float     # cumulative simulated time (s)
    n_selected: int
    test_acc: float       # nan when not evaluated this round
    min_part_rate: float  # min_i counts_i / n — fairness monitor (Eq. 8g)
    n_trained: int = -1   # clients whose local SGD the round ran (all N
                          # under compute="full", the gather's cap under
                          # "selected"); n_selected / n_trained is the
                          # share of that SGD Eq. (2) kept
    handover_rate: float = float("nan")  # fraction of users whose serving
                                         # BS changed this round
                                         # (hierarchical runs only)
    n_delivered: int = -1     # scheduled clients whose update arrived
                              # (-1 when the fault layer is off)
    delivered_rate: float = float("nan")   # n_delivered / n_selected
                                           # (async: n_delivered / n_users)
    goodput_mbit_s: float = float("nan")   # delivered uplink Mbit per
                                           # simulated second this round
    n_inflight: int = -1      # async: updates still in the event queue at
                              # tick end (-1 on synchronous runs)
    n_dropped: int = -1       # async: updates evicted by a full buffer
                              # this tick (-1 on synchronous runs)


def _compress_updates(ref_params: PyTree, client_params: PyTree,
                      compress: str, topk_frac: float, key,
                      fedavg_backend: str):
    """Client side of the compressed uplink (docs/COMPRESSION.md): deltas
    w.r.t. the reference model -> top-k (+ optional int8 stochastic
    rounding) codes.  Returns ``(codes, scales, finite)`` where ``finite``
    [N] marks clients whose RAW update was all-finite — the compressor
    screens non-finite entries to 0, so the caller must drop the screened
    clients' Eq. (2) weight to keep the uncompressed exclusion semantics.

    ``ref_params`` leaves may be the shared global model ([d...]) or
    per-client references ([N, d...], the hierarchical serving-edge init).
    """
    from repro.kernels import compress_topk as ct
    with jax.named_scope("round.compress"):
        delta = jax.tree.map(
            lambda c, g: c - (g if g.ndim == c.ndim
                              else g[None]).astype(c.dtype),
            client_params, ref_params)
        finite = fl_server.finite_update_mask(delta)
        codes, scales = ct.compress_delta_tree(
            delta, topk_frac, quantize=(compress == "topk-int8"), key=key,
            backend="pallas" if fedavg_backend == "pallas" else "jax")
    return codes, scales, finite


def train_and_aggregate(loss_fn, params: PyTree, x_clients, y_clients, keys,
                        selected, data_sizes, *, epochs: int, batch_size: int,
                        lr: float, compute: str = "full",
                        select_cap: int | None = None,
                        fedavg_backend: str = "jax",
                        delivered=None, corrupt=None, corrupt_mode_id=0,
                        corrupt_scale=1.0, clip_norm=None, compress=None,
                        topk_frac: float = 1.0, compress_key=None) -> PyTree:
    """One round of the data plane: local SGD + masked FedAvg (Eq. 2).

    ``compute="full"`` trains every client and masks at aggregation (the
    constant-graph default); ``compute="selected"`` gathers the scheduled
    clients into a static ``select_cap``-sized subset first (per-client PRNG
    keys travel with their original index, so a covering cap reproduces the
    full-fleet result exactly).  Shared by the round engine and the batched
    learning-curve sweep.

    Fault layer: ``delivered`` ([N] bool) replaces ``selected`` as the
    aggregation mask (scheduling decides who *trains*, delivery decides who
    *aggregates*); ``corrupt`` ([N] bool) poisons those clients' updates
    post-SGD (see :func:`repro.fl.faults.corrupt_updates`); ``clip_norm``
    enables the server's norm-clip defense.  All default to the perfect
    world.

    Compressed uplink (``compress`` in :data:`~repro.core.scenario.
    COMPRESS_MODES`): clients upload top-k (+ optional int8) codes of their
    update DELTA and the server folds decompression into the streaming
    Eq. (2) reduction (:mod:`repro.kernels.compress_topk`) — the dense
    [N, model] f32 update tensor never re-materialises on the pallas
    backend.  ``compress=None`` compiles the exact uncompressed graph.
    """
    if compute == "selected":
        n = x_clients.shape[0]
        cap = n if select_cap is None else min(int(select_cap), n)
        idx = fl_client.topk_selected_indices(selected, cap)
        client_params = fl_client.fleet_local_sgd(
            loss_fn, params, x_clients[idx], y_clients[idx], keys[idx],
            epochs=epochs, batch_size=batch_size, lr=lr)
        mask = selected if delivered is None else delivered
        sel, sizes = mask[idx], data_sizes[idx]
        corr = None if corrupt is None else corrupt[idx]
    elif compute == "full":
        client_params = fl_client.fleet_local_sgd(
            loss_fn, params, x_clients, y_clients, keys,
            epochs=epochs, batch_size=batch_size, lr=lr)
        sel = selected if delivered is None else delivered
        sizes, corr = data_sizes, corrupt
    else:
        raise ValueError(f"unknown compute mode {compute!r}; "
                         f"choose from {COMPUTE_MODES}")
    if corr is not None:
        client_params = fl_faults.corrupt_updates(
            client_params, corr, corrupt_mode_id, corrupt_scale)
    with jax.named_scope("round.aggregate"):
        if compress is not None:
            codes, scales, finite = _compress_updates(
                params, client_params, compress, topk_frac, compress_key,
                fedavg_backend)
            sel = sel & finite
            if fedavg_backend == "pallas":
                from repro.kernels.compress_topk import \
                    fedavg_decompress_reduce
                return fedavg_decompress_reduce(params, codes, scales, sel,
                                                sizes, clip_norm=clip_norm)
            from repro.kernels.ref import fedavg_decompress_reduce
            return fedavg_decompress_reduce(params, codes, scales, sel,
                                            sizes, clip_norm=clip_norm)
        if fedavg_backend == "pallas":
            from repro.kernels.fedavg_reduce import fedavg_reduce
            return fedavg_reduce(params, client_params, sel, sizes,
                                 clip_norm=clip_norm)
        return fl_server.fedavg(params, client_params, sel, sizes,
                                clip_norm=clip_norm)


# ---------------------------------------------------- buffered-async engine --
# The in-flight event queue is a tuple of fixed-shape arrays riding the
# lax.scan carry (docs/ASYNC.md):
#
#     comp  [B] f32   absolute Eq. (1) completion time; inf = empty slot
#     tick  [B] i32   the tick the update was dispatched on (staleness base)
#     idx   [B] i32   owning client; N is the out-of-bounds empty sentinel
#     size  [B] f32   the client's Eq. (2) data weight |D_i|
#     upd   pytree    the update itself, leaves [B, ...]
#
# Invariant: `comp` is sorted ascending, so live entries form a prefix and
# capacity eviction is a slice.  Clients with an update in flight are
# "busy" and not re-dispatched, so at most one queue entry per client exists
# — delivery can scatter by client index into [N]-shaped masks/weights and
# feed the SAME masked Eq. (2) reduction as the synchronous path, keeping
# the float accumulation in client-index order (the bit-identity anchor).


def async_queue_init(params: PyTree, n_users: int, buffer_size: int) -> tuple:
    """An empty event queue shaped for ``params`` updates."""
    upd = jax.tree.map(
        lambda p: jnp.zeros((buffer_size,) + p.shape, p.dtype), params)
    return (jnp.full((buffer_size,), jnp.inf, jnp.float32),
            jnp.zeros((buffer_size,), jnp.int32),
            jnp.full((buffer_size,), n_users, jnp.int32),
            jnp.zeros((buffer_size,), jnp.float32),
            upd)


def async_busy(queue: tuple, n_users: int) -> jnp.ndarray:
    """[N] bool: client has an update in flight (empty slots scatter to the
    out-of-bounds sentinel and are dropped)."""
    _, _, idx, _, _ = queue
    return jnp.zeros((n_users,), bool).at[idx].set(True, mode="drop")


def async_queue_step(queue: tuple, client_params: PyTree,
                     dispatch: jnp.ndarray, comp_time: jnp.ndarray,
                     data_sizes: jnp.ndarray, r, tick_end,
                     staleness_alpha, admit_idx=None) -> tuple:
    """Advance the event queue by one tick: admit, deliver, evict.

    Merges this tick's dispatches (``dispatch`` [N] bool, ``comp_time`` [N]
    absolute completion times) into the queue, delivers every live entry
    completing by ``tick_end``, then stable-sorts the survivors by
    completion time and truncates to capacity (latest completions evicted —
    they are the stalest-to-be).  Same-tick deliveries have staleness 0 and
    weight exactly 1.0 for any alpha.

    ``admit_idx`` ([cap] int32, optional) admits a COMPRESSED update batch:
    ``client_params`` leaves are [cap, ...] rows owned by clients
    ``admit_idx`` (the sparse ``compute="selected"`` path).  Because
    :func:`repro.fl.client.topk_selected_indices` lists dispatched clients
    in ascending client index — the same relative order as the dense [N]
    admit — the stable completion-time sort sees an identical live-entry
    order and the queue evolves identically when the cap covers the
    dispatch set (dead padding rows admit as empty slots).

    Returns ``(queue', delivered, wstale, delivered_updates, diag)``:
    ``delivered`` [N] bool / ``wstale`` [N] f32 / ``delivered_updates``
    (leaves [N, ...], zeros off-delivery) feed the weighted Eq. (2)
    reduction; ``diag`` holds n_delivered / n_inflight / n_dropped /
    w_delivered (staleness-weighted delivered Eq. (2) mass).
    """
    comp_q, tick_q, idx_q, size_q, upd_q = queue
    n = dispatch.shape[0]
    b = comp_q.shape[0]
    r = jnp.asarray(r, jnp.int32)
    if admit_idx is None:
        row_disp = dispatch
        row_comp, row_size = comp_time, data_sizes
        row_idx = jnp.arange(n, dtype=jnp.int32)
    else:
        row_disp = dispatch[admit_idx]
        row_comp, row_size = comp_time[admit_idx], data_sizes[admit_idx]
        row_idx = admit_idx.astype(jnp.int32)
    a = row_disp.shape[0]
    comp = jnp.concatenate([comp_q, jnp.where(row_disp, row_comp, jnp.inf)])
    tick = jnp.concatenate([tick_q, jnp.full((a,), r, jnp.int32)])
    idx = jnp.concatenate([idx_q, jnp.where(row_disp, row_idx, n)])
    size = jnp.concatenate(
        [size_q,
         jnp.where(row_disp, row_size.astype(jnp.float32), 0.0)])
    upd = jax.tree.map(
        lambda q, c: jnp.concatenate([q, c.astype(q.dtype)]),
        upd_q, client_params)

    deliver = jnp.isfinite(comp) & (comp <= tick_end)       # [B+A]
    wst = fl_server.staleness_weights(r - tick, staleness_alpha)
    # scatter delivered entries to their client's row; busy-masking makes
    # the delivered indices unique, non-delivered rows go to the sentinel
    scat = jnp.where(deliver, idx, n)
    delivered = jnp.zeros((n,), bool).at[scat].set(True, mode="drop")
    wstale = jnp.zeros((n,), jnp.float32).at[scat].set(wst, mode="drop")
    delivered_upd = fl_client.scatter_client_tree(n, scat, upd)

    # survivors: delivered slots become empty (inf) and the stable sort
    # sinks them past the live prefix; entries beyond capacity are evicted
    comp_left = jnp.where(deliver, jnp.inf, comp)
    order = jnp.argsort(comp_left)                          # stable
    keep = order[:b]
    kept_live = jnp.isfinite(comp_left[keep])
    new_queue = (comp_left[keep],
                 jnp.where(kept_live, tick[keep], 0),
                 jnp.where(kept_live, idx[keep], n),
                 jnp.where(kept_live, size[keep], 0.0),
                 jax.tree.map(lambda u: u[keep], upd))
    evicted = order[b:]
    dropped = jnp.isfinite(comp_left[evicted])
    diag = {
        "n_delivered": jnp.sum(deliver).astype(jnp.int32),
        "n_inflight": jnp.sum(kept_live).astype(jnp.int32),
        "n_dropped": jnp.sum(dropped).astype(jnp.int32),
        "w_delivered": jnp.sum(jnp.where(deliver, size * wst, 0.0)),
    }
    return new_queue, delivered, wstale, delivered_upd, diag


def aggregate_weighted(params: PyTree, delivered_updates: PyTree,
                       delivered: jnp.ndarray, data_sizes: jnp.ndarray,
                       weights: jnp.ndarray, *, fedavg_backend: str = "jax",
                       clip_norm=None) -> PyTree:
    """Staleness-weighted masked Eq. (2) on either aggregation backend."""
    if fedavg_backend == "pallas":
        from repro.kernels.fedavg_reduce import fedavg_reduce
        return fedavg_reduce(params, delivered_updates, delivered,
                             data_sizes, clip_norm=clip_norm,
                             weights=weights)
    return fl_server.fedavg(params, delivered_updates, delivered, data_sizes,
                            clip_norm=clip_norm, weights=weights)


def async_round_tick(loss_fn, params: PyTree, queue: tuple, x_clients,
                     y_clients, keys, dispatch, t_user, data_sizes, r, *,
                     tick_s: float, staleness_alpha, epochs: int,
                     batch_size: int, lr: float, fedavg_backend: str = "jax",
                     compute: str = "full", select_cap: int | None = None,
                     corrupt=None, corrupt_mode_id=0, corrupt_scale=1.0,
                     clip_norm=None, compress=None, topk_frac: float = 1.0,
                     compress_key=None) -> tuple:
    """One buffered-async tick of the data plane (shared by the engine and
    the batched learning-curve sweep).

    Trains the fleet — all of it (the constant-graph ``compute="full"``
    path) or only a static ``select_cap``-sized gather of this tick's
    dispatch set (``compute="selected"``: training AND the queue admit are
    [cap]-shaped, so per-tick learning state scales with the dispatch cap,
    not the population) — stamps each dispatched client's Eq. (1)
    completion time relative to the tick clock ``now = r * tick_s``,
    advances the event queue, and applies the staleness-weighted Eq. (2)
    over whatever landed this tick.  Fully traced; ``r`` may be a host int
    or the fused scan's counter.

    Compressed uplink: the lossy compress->decompress round-trip happens AT
    DISPATCH (clients upload codes; the queue parks exactly what the server
    will decode), so delivery reuses the uncompressed staleness-weighted
    reduction unchanged.  Clients whose raw update went non-finite are not
    dispatched (the compressor would silently zero them while keeping their
    Eq. (2) weight — matching the synchronous exclusion semantics instead).

    Returns ``(params, queue, delivered, diag)``.
    """
    if compute == "selected":
        n = dispatch.shape[0]
        cap = n if select_cap is None else min(int(select_cap), n)
        idx = fl_client.topk_selected_indices(dispatch, cap)
        client_params = fl_client.fleet_local_sgd(
            loss_fn, params, x_clients[idx], y_clients[idx], keys[idx],
            epochs=epochs, batch_size=batch_size, lr=lr)
        if corrupt is not None:
            client_params = fl_faults.corrupt_updates(
                client_params, corrupt[idx], corrupt_mode_id, corrupt_scale)
        admit_idx = idx
    elif compute == "full":
        client_params = fl_client.fleet_local_sgd(
            loss_fn, params, x_clients, y_clients, keys,
            epochs=epochs, batch_size=batch_size, lr=lr)
        if corrupt is not None:
            client_params = fl_faults.corrupt_updates(
                client_params, corrupt, corrupt_mode_id, corrupt_scale)
        admit_idx = None
    else:
        raise ValueError(f"unknown compute mode {compute!r}; "
                         f"choose from {COMPUTE_MODES}")
    if compress is not None:
        codes, scales, finite = _compress_updates(
            params, client_params, compress, topk_frac, compress_key,
            fedavg_backend)
        from repro.kernels.compress_topk import decompress_tree
        with jax.named_scope("round.compress"):
            client_params = jax.tree.map(
                lambda g, d: g[None] + d.astype(g.dtype), params,
                decompress_tree(codes, scales))
            if admit_idx is None:
                dispatch = dispatch & finite
            else:
                # scatter the [cap] finite rows back to the [N] dispatch
                # mask (padding duplicates carry identical rows, so
                # last-write-wins scatters the same value)
                dispatch = dispatch & jnp.ones_like(dispatch).at[
                    admit_idx].set(finite, mode="drop")
    now = jnp.asarray(r, jnp.float32) * jnp.float32(tick_s)
    comp_time = now + t_user
    tick_end = now + jnp.float32(tick_s)
    with jax.named_scope("round.aggregate"):
        queue, delivered, wstale, delivered_upd, diag = async_queue_step(
            queue, client_params, dispatch, comp_time, data_sizes, r,
            tick_end, staleness_alpha, admit_idx=admit_idx)
        params = aggregate_weighted(params, delivered_upd, delivered,
                                    data_sizes, wstale,
                                    fedavg_backend=fedavg_backend,
                                    clip_norm=clip_norm)
    return params, queue, delivered, diag


def camped_bs(dist: jnp.ndarray) -> jnp.ndarray:
    """[N] int32 serving cell: the geometrically nearest BS.

    Camping follows large-scale signal (distance), not the per-round
    Rayleigh draw — handover between camped cells is the mobility-driven
    quantity the cluster-HFL paper (arXiv 2108.09103) studies, and defining
    it on geometry keeps the metric free of fading noise.
    """
    return jnp.argmin(dist, axis=1).astype(jnp.int32)


def hierarchical_round(loss_fn, global_params: PyTree, edge_params: PyTree,
                       edge_weight: jnp.ndarray, prev_bs: jnp.ndarray,
                       x_clients, y_clients, keys, assign, selected, serving,
                       data_sizes, r, *, tau_global: int, epochs: int,
                       batch_size: int, lr: float, compute: str = "full",
                       select_cap: int | None = None,
                       fedavg_backend: str = "jax",
                       delivered=None, corrupt=None, corrupt_mode_id=0,
                       corrupt_scale=1.0, clip_norm=None, compress=None,
                       topk_frac: float = 1.0, compress_key=None):
    """One hierarchical data-plane round (arXiv 2108.09103's architecture).

    Each client pulls the edge model of its serving (camped) cell — so a
    user that handed over since last round trains from the NEW cell's,
    possibly diverged, model — runs local SGD, and its update
    edge-aggregates into the BS the *scheduler* assigned its upload to, via
    the per-BS segmented Eq. (2) (download follows camping, upload follows
    the Eq. (8) assignment; the two usually agree but the scheduler may
    load-balance).  Every ``tau_global`` rounds the edge models sync into
    the global model, weighted by the data each edge aggregated since the
    last sync.  Fully traced: ``r`` may be a host int or the fused scan's
    round counter.

    Returns ``(global_params, edge_params, edge_weight, serving,
    handover_rate)``.  For evaluation between syncs, mix the edges with
    :func:`repro.fl.server.edge_global_sync` (the virtual global: edge
    mixture by accumulated weight, the plain global right after a sync) —
    callers do this INSIDE their eval ``lax.cond`` so non-eval rounds skip
    the O(M x model) reduction.
    """
    moved = (serving != prev_bs) & (prev_bs >= 0)
    handover_rate = jnp.mean(moved.astype(jnp.float32))
    # delivery masks the assignment: an undelivered client's upload reaches
    # no BS (its assignment column zeroes out of the segment weights)
    assign_eff = assign if delivered is None else assign & delivered[:, None]

    if compute == "selected":
        # sparse selected state: gather the serving-cell index FIRST, then
        # pull only the selected clients' edge models — e[serving[idx]] ==
        # e[serving][idx] exactly, but the per-client init pytree is born
        # [cap, model] and the dense [N, model] copy never materialises
        n = x_clients.shape[0]
        cap = n if select_cap is None else min(int(select_cap), n)
        idx = fl_client.topk_selected_indices(selected, cap)
        serving_r = serving[idx]
        init = fl_client.gather_client_tree(edge_params, serving_r)
        client_params = fl_client.fleet_local_sgd_per_client(
            loss_fn, init, x_clients[idx], y_clients[idx], keys[idx],
            epochs=epochs, batch_size=batch_size, lr=lr)
        assign_r, sizes = assign_eff[idx], data_sizes[idx]
        corr = None if corrupt is None else corrupt[idx]
    elif compute == "full":
        serving_r = serving
        init = fl_client.gather_client_tree(edge_params, serving_r)
        client_params = fl_client.fleet_local_sgd_per_client(
            loss_fn, init, x_clients, y_clients, keys,
            epochs=epochs, batch_size=batch_size, lr=lr)
        assign_r, sizes, corr = assign_eff, data_sizes, corrupt
    else:
        raise ValueError(f"unknown compute mode {compute!r}; "
                         f"choose from {COMPUTE_MODES}")
    if corr is not None:
        client_params = fl_faults.corrupt_updates(
            client_params, corr, corrupt_mode_id, corrupt_scale)

    with jax.named_scope("round.aggregate"):
        # edge Eq. (2): every BS aggregates its users in one segment-reduce
        if compress is not None:
            # compressed uplink: deltas vs the SERVING edge model (what
            # the client trained from), decoded into the ASSIGNED BS's
            # aggregation — the [N, model] client tensor never
            # reconstructs densely on the pallas backend
            # (docs/COMPRESSION.md)
            codes, scales, finite = _compress_updates(
                init, client_params, compress, topk_frac, compress_key,
                fedavg_backend)
            assign_r = assign_r & finite[:, None]
            if fedavg_backend == "pallas":
                from repro.kernels.compress_topk import \
                    fedavg_decompress_segment_reduce
                edge_params = fedavg_decompress_segment_reduce(
                    edge_params, codes, scales, assign_r, serving_r, sizes,
                    clip_norm=clip_norm)
            else:
                from repro.kernels.ref import \
                    fedavg_decompress_segment_reduce
                edge_params = fedavg_decompress_segment_reduce(
                    edge_params, codes, scales, assign_r, serving_r, sizes,
                    clip_norm=clip_norm)
        elif fedavg_backend == "pallas":
            from repro.kernels.fedavg_reduce import fedavg_segment_reduce
            edge_params = fedavg_segment_reduce(edge_params, client_params,
                                                assign_r, sizes,
                                                clip_norm=clip_norm)
        else:
            edge_params = fl_server.fedavg_segmented(
                edge_params, client_params, assign_r, sizes,
                clip_norm=clip_norm)
        _, bs_totals = fl_server.segment_weights(assign_r, sizes)
        edge_weight = edge_weight + bs_totals

        def sync(args):
            g, e, wgt = args
            g2 = fl_server.edge_global_sync(g, e, wgt)
            e2 = jax.tree.map(
                lambda gl, el: jnp.broadcast_to(gl[None], el.shape), g2, e)
            return g2, e2, jnp.zeros_like(wgt)

        global_params, edge_params, edge_weight = jax.lax.cond(
            (r + 1) % tau_global == 0, sync, lambda a: a,
            (global_params, edge_params, edge_weight))
    return global_params, edge_params, edge_weight, serving, handover_rate


# ------------------------------------------------- canonical round step --
@dataclasses.dataclass(frozen=True)
class RoundPlan:
    """The STATIC half of a round step: every knob that shapes the traced
    graph (and therefore keys a compile bucket).  Hashable by construction,
    so it can ride ``jax.jit`` static arguments directly.

    ``world`` picks the step's PRNG/world flavor:

      * ``"engine"`` — :class:`FLSimulation`'s trajectory: per-round
        ``split(key, 5|6)``, mobility by static model name, channel via
        :func:`repro.core.channel.make_problem`, scheduler through the
        registry.  Bit-identical to the pre-refactor engine.
      * ``"sweep"``  — the batched learning sweep's trajectory: per-round
        ``split(key, 6|7)`` (separate SNR/tcomp subkeys), mobility by
        traced ``model_id`` switch, scenario knobs as DATA, and the DAGSA
        greedy called directly so int8/bf16 channel codes stream through
        selection.  Bit-identical to the pre-refactor
        ``sweep._one_learning_cell``.

    The two flavors draw different random worlds by construction (they
    always did); everything downstream of the drawn world — fault realize,
    latency, data plane, bookkeeping — is ONE shared code path.
    """

    scheduler: str
    epochs: int
    batch_size: int
    lr: float
    eval_every: int
    compute: str = "full"
    select_cap: int | None = None
    fedavg_backend: str = "jax"
    aggregation: str = "single"
    tau_global: int = 1
    async_on: bool = False
    tick_s: float = 1.0
    staleness_alpha: float = 0.0
    buffer_size: int = 1
    faults_on: bool = False
    clip_on: bool = False
    backend: str = "jax"
    user_chunk: int | None = None
    channel_dtype: str = "f32"
    world: str = "engine"
    compress: str | None = None     # uplink compression mode (COMPRESS_MODES)
                                    # — STATIC: None compiles the exact
                                    # uncompressed graph
    topk_frac: float = 1.0          # fraction of entries uploaded per leaf


def make_round_step(plan: RoundPlan, w: WirelessConfig, *, scenario, faults,
                    x_clients, y_clients, data_sizes, x_test, y_test,
                    bs_pos, bs_bw, k_shadow, min_participants: int,
                    params0, pos0, aux0, counts0, key0, clip_norm=None,
                    prev_bs0=None, edge_params0=None, edge_weight0=None,
                    queue0=None):
    """Build ONE canonical fused round step: ``(init_state, step_fn)``.

    ``step_fn(state, r) -> (state', out)`` is a pure
    :class:`~repro.core.types.RoundState` transform — ``lax.scan`` it over
    round indices (the fused engines), call it per round under jit (step
    mode), or vmap the whole scan over seeds x scenarios (the learning
    sweep).  Every consumer — :class:`FLSimulation`,
    ``launch.sweep._one_learning_cell``, ``launch.shard_sweep``,
    ``launch.fl_sim`` — routes through here; there is no second round-step
    body.

    Args:
      scenario: ``world="engine"``: a dict of STATIC scenario knobs
        (``mob_model``, ``pause_s``, ``gm_memory``, ``shadow_sigma``).
        ``world="sweep"``: the traced per-scenario parameter struct from
        ``launch.sweep._scenario_params`` (one row — knobs are data).
      faults: fault-severity params (``repro.fl.faults.fault_params``
        layout), host floats or traced arrays; consumed only when
        ``plan.faults_on``.
      clip_norm: the engine world's static norm-clip value (None = off);
        the sweep world clips by the traced ``faults["clip_norm"]`` when
        ``plan.clip_on``.
      min_participants: Eq. (8h) floor as a static int (the sweep world
        builds its SchedulingProblem from it; the engine world's
        ``make_problem`` recomputes the identical value).
      *0: initial carry values.  Optional slots default to the canonical
        initialisation (prev_bs -1-sentinel, edge models broadcast from the
        global, empty async queue, fresh SchedulerState) when the feature
        is on and the caller passed None.

    Returns:
      ``(init_state, step_fn)`` with ``init_state`` a fully-populated
      :class:`RoundState` whose optional slots are ``None`` exactly when
      the corresponding feature is off (static carry structure per compile
      bucket).
    """
    hier = plan.aggregation == "hierarchical"
    need_prev = hier or plan.faults_on
    fp = faults
    n = w.n_users

    # -- compressed uplink (STATIC; docs/COMPRESSION.md): the per-user
    # payload s_k = ratio * S scales the Eq. (1)/(3) bandwidth-time
    # coefficients; compress=None threads payload=None and compiles the
    # exact uncompressed graph (the faults_on gating pattern).
    compress_on = plan.compress is not None
    if compress_on:
        from repro.kernels import compress_topk as _ct
        up_mbit = w.model_mbit * _ct.compression_ratio(
            params0, plan.topk_frac, plan.compress == "topk-int8")
    else:
        up_mbit = w.model_mbit
    payload0 = jnp.full((n,), up_mbit, jnp.float32) if compress_on else None

    # -- per-user device heterogeneity: one FIXED draw u ~ U[0,1) per user
    # stretches compute by spread**u and scales the uplink PSD by
    # -spread_db * u dB.  The engine world gates STATICALLY on the scenario
    # knobs (defaults compile the exact homogeneous graph); the sweep world
    # applies the traced knobs unconditionally — the defaults 1.0 / 0.0 dB
    # are IEEE-exact no-ops (x * 1.0**u == x, 10**(-0.0) == 1.0).
    if plan.world == "engine":
        c_spread = scenario.get("compute_spread", 1.0)
        p_spread_db = scenario.get("power_spread_db", 0.0)
        hetero_on = c_spread != 1.0 or p_spread_db != 0.0
    else:
        c_spread = scenario["compute_spread"]
        p_spread_db = scenario["power_spread_db"]
        hetero_on = True
    if hetero_on:
        u_het = jax.random.uniform(jax.random.fold_in(k_shadow, 1), (n,))
        het_tcomp = jnp.asarray(c_spread, jnp.float32) ** u_het
        het_power = 10.0 ** (-jnp.asarray(p_spread_db, jnp.float32)
                             * u_het / 10.0)
    else:
        het_tcomp = het_power = None

    if need_prev and prev_bs0 is None:
        prev_bs0 = jnp.full((n,), -1, jnp.int32)
    if hier and edge_params0 is None:
        edge_params0 = jax.tree.map(
            lambda q: jnp.repeat(q[None], w.n_bs, axis=0), params0)
    if hier and edge_weight0 is None:
        edge_weight0 = jnp.zeros((w.n_bs,), jnp.float32)
    if plan.async_on and queue0 is None:
        queue0 = async_queue_init(params0, n, plan.buffer_size)

    init_state = RoundState(
        world=WorldState(pos=pos0, mob_aux=aux0),
        clients=ClientState(counts=counts0,
                            prev_bs=prev_bs0 if need_prev else None),
        server=ServerState(params=params0,
                           edge_params=edge_params0 if hier else None,
                           edge_weight=edge_weight0 if hier else None,
                           queue=queue0 if plan.async_on else None),
        sched=sched.scheduler_state_init(plan.scheduler, n),
        key=key0)

    def step_fn(state: RoundState, r):
        params = state.server.params
        pos, aux = state.world.pos, state.world.mob_aux
        counts, key = state.clients.counts, state.key
        prev_bs = state.clients.prev_bs

        # -- 1+2. world advance + channel observation (per-flavor PRNG) ----
        if plan.world == "engine":
            if plan.faults_on:
                # one extra subkey for the fault realization — gated
                # statically so fault-free runs keep the exact trajectory
                key, k_mob, k_prob, k_sched, k_fleet, k_fault = \
                    jax.random.split(key, 6)
            else:
                key, k_mob, k_prob, k_sched, k_fleet = \
                    jax.random.split(key, 5)
            pos, aux = mobility.step_named(
                scenario["mob_model"], k_mob, pos, aux, w,
                pause_s=scenario["pause_s"], gm_memory=scenario["gm_memory"])
            mstate = MobilityState(user_pos=pos, bs_pos=bs_pos)
            shadow_db = None
            if scenario["shadow_sigma"] > 0.0:
                with jax.named_scope("round.channel"):
                    shadow_db = scenario["shadow_sigma"] * \
                        channel.sample_shadowing(k_shadow, pos, bs_pos, w,
                                                 sigma_db=1.0)
            prob = channel.make_problem(k_prob, mstate, w, counts, r,
                                        bs_bw=bs_bw, shadow_db=shadow_db,
                                        tcomp_scale=het_tcomp,
                                        power_scale=het_power,
                                        payload_mbit=payload0)
            snr_store, snr_scale = prob.snr, None
            if need_prev:
                # geometry the hierarchy / fault layer observes (CSE'd
                # against make_problem's internal distance computation)
                dist = mstate.distances()
        elif plan.world == "sweep":
            p = scenario
            if plan.faults_on:
                key, k_mob, k_snr, k_tc, k_sched, k_fleet, k_fault = \
                    jax.random.split(key, 7)
            else:
                key, k_mob, k_snr, k_tc, k_sched, k_fleet = \
                    jax.random.split(key, 6)
            pos, aux = mobility.step_switch(
                p["model_id"], k_mob, pos, aux, w.area_m,
                w.round_duration_s, p["speed"], p["pause_s"], p["gm_memory"])
            with jax.named_scope("round.channel"):
                # same k_shadow every round -> the field is consistent in
                # time
                dist, shadow_db = channel.dist_and_shadow(
                    pos, bs_pos, p["shadow_sigma"], k_shadow, w,
                    plan.user_chunk)
                # device PSD spread scales SNR BEFORE encoding, so int8/bf16
                # channel codes carry the heterogeneous link (exact no-op
                # at the 0 dB default: het_power == 1.0 elementwise)
                snr_raw = channel.sample_snr(k_snr, dist, w,
                                             shadow_db=shadow_db) \
                    * het_power[:, None]
                snr_store, snr_scale, snr_lin = channel.encode_channel(
                    snr_raw, plan.channel_dtype)
                if plan.channel_dtype == "int8":
                    # Eq. (11) needs real coefficients — derive from the
                    # dequantised plane (f32; the codes carry only ranks+dB)
                    coeff = channel.bandwidth_time_coeff(
                        snr_lin, w, payload_mbit=payload0)
                else:
                    coeff = channel.compress_channel(
                        channel.bandwidth_time_coeff(snr_store, w,
                                                     payload_mbit=payload0),
                        plan.channel_dtype)
                u = jax.random.uniform(k_tc, (n,))
                tcomp = (p["tcomp_min"] + u * (p["tcomp_max"]
                                               - p["tcomp_min"])) * het_tcomp
                # Eq. (8g), post-round requirement (matches make_problem)
                necessary = counts < w.rho1 * (r + 1.0)
                prob = SchedulingProblem(
                    snr=snr_lin, tcomp=tcomp, bs_bw=bs_bw, coeff=coeff,
                    necessary=necessary, min_participants=min_participants,
                    payload_mbit=payload0)
        else:
            raise ValueError(f"unknown world {plan.world!r}; "
                             f"choose 'engine' or 'sweep'")

        # -- 2b. hierarchy / fault geometry --------------------------------
        if need_prev:
            serving = camped_bs(dist)
        if plan.faults_on:
            edge_frac = fl_faults.edge_proximity(dist, serving, w)
            handover = (serving != prev_bs) & (prev_bs >= 0)
            # pre-scheduling delivery estimate — what dagsa-r discounts by
            p_est = fl_faults.delivery_probability(fp, edge_frac, handover)
            if plan.world == "engine":
                prob = dataclasses.replace(prob, p_deliver=p_est)

        # -- 3. schedule (static dispatch by name) -------------------------
        with jax.named_scope("round.select"):
            sched_state = state.sched
            if plan.scheduler in sched.STATEFUL_SCHEDULERS:
                res, sched_state = sched.schedule_stateful(
                    plan.scheduler, prob, w, k_sched, sched_state)
            elif plan.world == "engine":
                res = sched.schedule(plan.scheduler, prob, w, k_sched)
            elif plan.scheduler in ("dagsa_jit", "dagsa-r"):
                # direct greedy call: the sweep streams the (possibly
                # int8/bf16) channel codes + scale through the selection
                # kernels
                score, scale = snr_store, snr_scale
                if plan.faults_on and plan.scheduler == "dagsa-r":
                    # the delivery-discounted candidate score (the per-user
                    # scale leaves each user's best-BS argmax unchanged)
                    score = prob.snr * jnp.clip(p_est, 0.0, 1.0)[:, None]
                    scale = None
                assign, selected, user_bw, t_k, t_star, *_ = \
                    dagsa_jit._schedule(
                        score, prob.coeff, prob.tcomp, bs_bw, prob.necessary,
                        min_participants, k_sched, backend=plan.backend,
                        selection_block=plan.user_chunk, snr_scale=scale)
                res = ScheduleResult(assign=assign, selected=selected,
                                     bw=user_bw, bs_time=t_k, t_round=t_star)
            else:
                res = sched.schedule(plan.scheduler, prob, w, k_sched)

        # -- 3b. realize faults: stragglers stretch tcomp, outages/crashes
        # kill uplinks, the deadline drops late survivors (truncated Eq. 3)
        if plan.faults_on:
            tcomp_eff, alive, corrupt = fl_faults.sample_round_faults(
                k_fault, fp, edge_frac, handover, prob.tcomp)
            t_user = latency.per_user_latency(prob, res, tcomp=tcomp_eff)
            gate = alive & latency.on_time(t_user, fp["deadline_s"])
            clip = (clip_norm if plan.world == "engine"
                    else (fp["clip_norm"] if plan.clip_on else None))
        else:
            corrupt, clip = None, None
            if plan.async_on:
                t_user = latency.per_user_latency(prob, res)
                gate = jnp.ones_like(res.selected)

        # -- 4. data plane: local SGD + Eq. (2) aggregation ----------------
        keys = jax.random.split(k_fleet, n)
        # stochastic-rounding noise key: per-round (k_fleet varies), derived
        # by fold_in so no client's key stream shifts; None when the mode
        # needs no randomness (statically gated — compression-off graphs
        # split the exact same keys as before)
        ck = (jax.random.fold_in(k_fleet, n + 1)
              if plan.compress == "topk-int8" else None)
        edge = state.server.edge_params
        edge_w = state.server.edge_weight
        queue = state.server.queue
        if plan.async_on:
            # faults gate at dispatch: a dead/late uplink never enters the
            # queue (same delivery mask as the sync engine carries over)
            eligible = res.selected & ~async_busy(queue, n)
            dispatch = eligible & gate
            params, queue, delivered, diag = async_round_tick(
                cnn.loss_fn, params, queue, x_clients, y_clients, keys,
                dispatch, t_user, data_sizes, r, tick_s=plan.tick_s,
                staleness_alpha=plan.staleness_alpha, epochs=plan.epochs,
                batch_size=plan.batch_size, lr=plan.lr,
                fedavg_backend=plan.fedavg_backend, compute=plan.compute,
                select_cap=plan.select_cap, corrupt=corrupt,
                corrupt_mode_id=fp["corrupt_mode_id"],
                corrupt_scale=fp["corrupt_scale"], clip_norm=clip,
                compress=plan.compress, topk_frac=plan.topk_frac,
                compress_key=ck)
            t_round = jnp.full((), plan.tick_s, jnp.float32)
            eval_args, eval_model = params, lambda q: q
        else:
            if plan.faults_on:
                delivered = res.selected & gate
                t_round = latency.deadline_round_latency(
                    t_user, res.selected, fp["deadline_s"])
            else:
                delivered = res.selected
                t_round = res.t_round
            if hier:
                (params, edge, edge_w, prev_bs, handover_rate) = \
                    hierarchical_round(
                        cnn.loss_fn, params, edge, edge_w, prev_bs,
                        x_clients, y_clients, keys, res.assign,
                        res.selected, serving, data_sizes, r,
                        tau_global=plan.tau_global, epochs=plan.epochs,
                        batch_size=plan.batch_size, lr=plan.lr,
                        compute=plan.compute, select_cap=plan.select_cap,
                        fedavg_backend=plan.fedavg_backend,
                        delivered=delivered if plan.faults_on else None,
                        corrupt=corrupt,
                        corrupt_mode_id=fp["corrupt_mode_id"],
                        corrupt_scale=fp["corrupt_scale"], clip_norm=clip,
                        compress=plan.compress, topk_frac=plan.topk_frac,
                        compress_key=ck)
                # eval sees the virtual global (edge mixture); built inside
                # the cond so non-eval rounds skip the O(M x model) mixture
                eval_args = (params, edge, edge_w)
                eval_model = lambda a: fl_server.edge_global_sync(*a)
            else:
                params = train_and_aggregate(
                    cnn.loss_fn, params, x_clients, y_clients, keys,
                    res.selected, data_sizes, epochs=plan.epochs,
                    batch_size=plan.batch_size, lr=plan.lr,
                    compute=plan.compute, select_cap=plan.select_cap,
                    fedavg_backend=plan.fedavg_backend,
                    delivered=delivered if plan.faults_on else None,
                    corrupt=corrupt,
                    corrupt_mode_id=fp["corrupt_mode_id"],
                    corrupt_scale=fp["corrupt_scale"], clip_norm=clip,
                    compress=plan.compress, topk_frac=plan.topk_frac,
                    compress_key=ck)
                eval_args, eval_model = params, lambda q: q

        # -- 5. bookkeeping + eval.  Participation follows DELIVERY under
        # faults: a user whose update was lost stays "necessary" (Eq. 8g),
        # so the fairness loop self-heals failures.
        counts = counts + delivered.astype(counts.dtype)

        def evaluate(a):
            with jax.named_scope("round.eval"):
                return cnn.accuracy(eval_model(a), x_test, y_test)

        if plan.eval_every:
            acc = jax.lax.cond(
                (r + 1) % plan.eval_every == 0, evaluate,
                lambda a: jnp.float32(jnp.nan), eval_args)
        else:
            acc = jnp.float32(jnp.nan)

        out = {
            "t_round": t_round,
            "test_acc": acc,
            "min_part_rate": jnp.min(counts) / (r + 1.0),
        }
        n_sel = jnp.sum(eligible) if plan.async_on else jnp.sum(res.selected)
        if plan.world == "engine":
            # engine records keep integer dtypes (host RoundRecords)
            out["n_selected"] = n_sel.astype(jnp.int32)
            # clients whose local SGD the round ran: the whole fleet, or
            # the static gather's cap
            out["n_trained"] = jnp.int32(
                n if plan.compute == "full"
                else fl_client.resolve_cap(n, plan.select_cap))
            if plan.async_on:
                n_del = diag["n_delivered"]
                out["n_delivered"] = n_del
                # deliveries lag dispatches in async, so normalise by the
                # fleet (bounded [0,1]) rather than the eligible count
                out["delivered_rate"] = (n_del / n).astype(jnp.float32)
                out["goodput_mbit_s"] = (
                    n_del * up_mbit / plan.tick_s).astype(jnp.float32)
                out["n_inflight"] = diag["n_inflight"]
                out["n_dropped"] = diag["n_dropped"]
            elif plan.faults_on:
                n_del = jnp.sum(delivered)
                out["n_delivered"] = n_del.astype(jnp.int32)
                out["delivered_rate"] = (
                    n_del / jnp.maximum(jnp.sum(res.selected), 1)
                ).astype(jnp.float32)
                out["goodput_mbit_s"] = (
                    n_del * up_mbit / jnp.maximum(t_round, 1e-9)
                ).astype(jnp.float32)
        else:
            # sweep records are all-f32 (they stack across seeds/scenarios)
            out["n_selected"] = n_sel.astype(jnp.float32)
            if plan.async_on:
                n_del = diag["n_delivered"].astype(jnp.float32)
                out["n_delivered"] = n_del
                out["delivered_rate"] = n_del / n
                out["goodput_mbit_s"] = (n_del * up_mbit
                                         / jnp.float32(plan.tick_s))
                out["n_inflight"] = diag["n_inflight"].astype(jnp.float32)
                out["n_dropped"] = diag["n_dropped"].astype(jnp.float32)
            elif plan.faults_on:
                n_del = jnp.sum(delivered).astype(jnp.float32)
                out["n_delivered"] = n_del
                out["delivered_rate"] = n_del / jnp.maximum(
                    jnp.sum(res.selected).astype(jnp.float32), 1.0)
                out["goodput_mbit_s"] = (n_del * up_mbit
                                         / jnp.maximum(t_round, 1e-9))
        if hier:
            out["handover_rate"] = handover_rate

        if need_prev and not hier:
            prev_bs = serving
        new_state = RoundState(
            world=WorldState(pos=pos, mob_aux=aux),
            clients=ClientState(counts=counts,
                                prev_bs=prev_bs if need_prev else None),
            server=ServerState(params=params,
                               edge_params=edge if hier else None,
                               edge_weight=edge_w if hier else None,
                               queue=queue if plan.async_on else None),
            sched=sched_state, key=key)
        return new_state, out

    return init_state, step_fn


class FLSimulation:
    """Owns all state of one FL run; `run(n_rounds)` yields RoundRecords."""

    def __init__(self, cfg: FLConfig):
        self.cfg = cfg
        spec = get_scenario(cfg.scenario) if cfg.scenario else None
        w = spec.wireless(cfg.wireless) if spec else cfg.wireless
        if cfg.speed_mps is not None:      # explicit CLI/config override wins
            if spec and spec.mobility == "static" and cfg.speed_mps > 0.0:
                raise ValueError(
                    f"scenario {spec.name!r} uses the 'static' mobility "
                    f"model, which ignores speed; speed_mps="
                    f"{cfg.speed_mps} would silently do nothing — pick a "
                    f"mobile scenario or drop the speed override")
            w = dataclasses.replace(w, speed_mps=cfg.speed_mps)
        self.scenario = spec
        self.wireless = w                  # resolved wireless config

        # -- aggregation architecture (explicit config beats the scenario) --
        agg = cfg.aggregation or (spec.aggregation if spec else "single")
        if cfg.tau_global is not None and agg != "hierarchical":
            raise ValueError(
                f"tau_global={cfg.tau_global} only applies to "
                f"aggregation='hierarchical' (resolved aggregation is "
                f"{agg!r}); it would silently do nothing")
        if agg == "hierarchical":
            if cfg.tau_global is not None:
                tau = cfg.tau_global
            elif spec is not None and spec.aggregation == "hierarchical":
                tau = spec.tau_global
            else:
                tau = DEFAULT_TAU_GLOBAL
            if cfg.scheduler not in FUSED_SCHEDULERS:
                raise ValueError(
                    f"aggregation='hierarchical' needs a traced round step; "
                    f"scheduler {cfg.scheduler!r} is host-side — pick one "
                    f"of {FUSED_SCHEDULERS}")
        else:
            tau = 1
        self.aggregation, self.tau_global = agg, tau
        self._hier = agg == "hierarchical"

        # -- buffered-async aggregation (docs/ASYNC.md) ---------------------
        self._async = cfg.aggregation_async
        if self._async:
            if self._hier:
                raise ValueError(
                    "aggregation_async composes with the single-tier "
                    "Eq. (2) only; the resolved aggregation is "
                    "'hierarchical'")
            if cfg.scheduler not in FUSED_SCHEDULERS:
                raise ValueError(
                    f"aggregation_async lives in the traced round step; "
                    f"scheduler {cfg.scheduler!r} is host-side — pick one "
                    f"of {FUSED_SCHEDULERS}")
        self._tick_s = float(cfg.tick_s) if cfg.tick_s is not None else None
        self._alpha = float(cfg.staleness_alpha)
        self._buffer_size = (int(cfg.buffer_size)
                             if cfg.buffer_size is not None else w.n_users)

        # -- fault model (explicit config beats the scenario) ---------------
        fs = cfg.faults
        if isinstance(fs, str):
            fs = fl_faults.get_faults(fs)
        if fs is None:
            fs = (spec.faults if spec is not None and spec.faults is not None
                  else fl_faults.NO_FAULTS)
        if cfg.deadline_s is not None:
            fs = dataclasses.replace(fs, deadline_s=cfg.deadline_s)
        self.faults: fl_faults.FaultSpec = fs
        # STATIC switch: an inert spec compiles the exact fault-free graph
        # (same PRNG split count -> bit-identical baseline trajectories).
        self._faulty = fs.active
        self._fault_params = fl_faults.fault_params(fs)

        # -- compressed uplink (explicit config beats the scenario) ---------
        comp = cfg.compress if cfg.compress is not None else (
            spec.compress if spec else None)
        if cfg.topk_frac is not None:
            if comp is None:
                raise ValueError(
                    f"topk_frac={cfg.topk_frac} only applies with a "
                    f"compress mode (the resolved mode is off); it would "
                    f"silently do nothing")
            frac = float(cfg.topk_frac)
        else:
            frac = float(spec.topk_frac) if spec is not None else 1.0
        self._compress, self._topk_frac = comp, frac

        # -- per-user device heterogeneity (scenario-only knobs) ------------
        self._compute_spread = spec.compute_spread if spec else 1.0
        self._power_spread_db = spec.power_spread_db if spec else 0.0
        self._hetero = (self._compute_spread != 1.0
                        or self._power_spread_db != 0.0)
        if ((comp is not None or self._hetero)
                and cfg.scheduler not in FUSED_SCHEDULERS):
            raise ValueError(
                f"compressed uplink / device heterogeneity live in the "
                f"traced round step; scheduler {cfg.scheduler!r} is "
                f"host-side — pick one of {FUSED_SCHEDULERS}")

        key = jax.random.PRNGKey(cfg.seed)
        (k_data, k_part, k_pos, k_model, k_bw, self._key) = \
            jax.random.split(key, 6)

        ds_name = cfg.dataset
        self.data = make_dataset(ds_name, seed=cfg.seed, n_train=cfg.n_train,
                                 n_test=cfg.n_test)
        # -- non-IID partition (explicit config beats the scenario) ---------
        part = cfg.partition or (spec.partition if spec else "shard")
        alpha = (cfg.dirichlet_alpha if cfg.dirichlet_alpha is not None
                 else (spec.dirichlet_alpha if spec else None))
        if part == "dirichlet":
            if alpha is None:
                raise ValueError(
                    "partition='dirichlet' needs dirichlet_alpha > 0")
            idx = dirichlet_partition(
                k_part, self.data.y_train, w.n_users,
                int(self.data.y_train.shape[0]) // w.n_users, float(alpha),
                n_classes=int(jnp.max(self.data.y_train)) + 1)
        else:
            if alpha is not None:
                raise ValueError(
                    f"dirichlet_alpha={alpha} only applies with "
                    f"partition='dirichlet' (the resolved partition is "
                    f"{part!r}); it would silently do nothing")
            idx = shard_partition(k_part, self.data.y_train, w.n_users,
                                  cfg.shards_per_user)
        self.partition = part
        self.x_clients = self.data.x_train[idx]      # [N, n_i, H, W, C]
        self.y_clients = self.data.y_train[idx]      # [N, n_i]
        self.data_sizes = jnp.full((w.n_users,), idx.shape[1])
        if cfg.shard:
            # client-dim data parallelism: with the [N, ...] batches placed
            # on a ("data",) mesh, GSPMD spreads the fleet's local SGD over
            # devices and all-reduces the FedAvg sum.  (Deferred import:
            # launch imports fl, so fl cannot import launch at module load.)
            from jax.sharding import NamedSharding, PartitionSpec

            from repro.launch.mesh import make_data_mesh
            mesh = make_data_mesh(cfg.mesh_devices)
            n_dev = mesh.devices.size
            if w.n_users % n_dev:
                raise ValueError(
                    f"shard=True needs n_users ({w.n_users}) divisible by "
                    f"the mesh size ({n_dev}); pass mesh_devices=D for a "
                    f"divisor D")
            client_sharding = NamedSharding(mesh, PartitionSpec("data"))
            self.x_clients = jax.device_put(self.x_clients, client_sharding)
            self.y_clients = jax.device_put(self.y_clients, client_sharding)
            self.data_sizes = jax.device_put(self.data_sizes,
                                             client_sharding)

        h, wd, c = self.data.x_train.shape[1:]
        self.cnn_cfg = cfg.cnn or cnn.CNNConfig(height=h, width=wd, channels=c)
        self.params = cnn.init(k_model, self.cnn_cfg)

        layout = spec.bs_layout if spec else cfg.bs_layout
        if layout == "uniform":
            self.mob = mobility.init_positions(k_pos, w)
        else:
            self.mob = mobility.init_positions_grid_bs(k_pos, w)
        # mobility model + kinematic aux state (scenario engine); plain RD
        # with an unused aux when no scenario is set.
        self._mob_model = spec.mobility if spec else "rd"
        self._mob_pause = spec.pause_s if spec else 0.0
        self._mob_gm = spec.gm_memory if spec else 0.75
        self._mob_aux = mobility.init_aux(jax.random.fold_in(k_pos, 1),
                                          w.n_users, w)
        self._shadow_sigma = (spec.shadow_sigma_db
                              if spec and spec.shadowing else 0.0)
        self._k_shadow = jax.random.fold_in(k_bw, 7)
        self.part = ParticipationState.init(w.n_users)
        if cfg.hetero_bw:
            self.bs_bw = jax.random.uniform(k_bw, (w.n_bs,), minval=0.5,
                                            maxval=1.5)
        elif spec is not None:
            self.bs_bw = spec.sample_bs_bw(k_bw, w)
        else:
            self.bs_bw = jnp.full((w.n_bs,), w.bs_bandwidth_mhz)

        self.wall_clock = 0.0
        self.round_idx = 0
        self._select_cap = (cfg.select_cap if cfg.select_cap is not None
                            else int(np.ceil(w.rho2 * w.n_users)))

        # hierarchical state: per-BS edge models (all start at the global
        # model), the data weight each edge aggregated since the last
        # global sync, and last round's serving BS for handover detection.
        # The fault layer needs prev_bs too (handover outage hazard), so it
        # rides the carry whenever either feature is on.
        if self._hier:
            self.edge_params = jax.tree.map(
                lambda p: jnp.repeat(p[None], w.n_bs, axis=0), self.params)
            self.edge_weight = jnp.zeros((w.n_bs,), jnp.float32)
        if self._hier or self._faulty:
            self._prev_bs = jnp.full((w.n_users,), -1, jnp.int32)

        # async state: the in-flight event queue (rides the scan carry)
        if self._async:
            self._queue = async_queue_init(self.params, w.n_users,
                                           self._buffer_size)

        # one compiled graph for the whole fleet's local training (eager path)
        self._fleet = jax.jit(partial(
            fl_client.fleet_local_sgd, cnn.loss_fn,
            epochs=cfg.local_epochs, batch_size=cfg.batch_size, lr=cfg.lr))
        self._acc = jax.jit(cnn.accuracy)
        # the fused round step, compiled once each way it is used
        self._step_jit = jax.jit(self._round_step)
        self._scan_jit = jax.jit(self._run_scan,
                                 static_argnames=("n_rounds",))
        self._async_scan_jit = jax.jit(self._run_async_scan,
                                       static_argnames=("n_rounds",))
        # python-side trace counter: increments only when _async_step is
        # (re)traced, so tests can assert ONE compile per shape bucket
        self._async_traces = 0

        # -- the canonical fused round step (shared with the learning sweep,
        # shard sweep and serving stub — ROADMAP item 5's seam) ------------
        self._plan = RoundPlan(
            scheduler=cfg.scheduler, epochs=cfg.local_epochs,
            batch_size=cfg.batch_size, lr=cfg.lr, eval_every=cfg.eval_every,
            compute=cfg.compute, select_cap=self._select_cap,
            fedavg_backend=cfg.fedavg_backend, aggregation=agg,
            tau_global=tau, async_on=self._async,
            tick_s=(self._tick_s if self._async else 1.0),
            staleness_alpha=self._alpha, buffer_size=self._buffer_size,
            faults_on=self._faulty,
            clip_on=self.faults.clip_norm is not None, world="engine",
            compress=self._compress, topk_frac=self._topk_frac)
        scenario_cp = {"mob_model": self._mob_model,
                       "pause_s": self._mob_pause,
                       "gm_memory": self._mob_gm,
                       "shadow_sigma": self._shadow_sigma,
                       "compute_spread": self._compute_spread,
                       "power_spread_db": self._power_spread_db}
        init_state, self._step_fn = make_round_step(
            self._plan, w, scenario=scenario_cp, faults=self._fault_params,
            x_clients=self.x_clients, y_clients=self.y_clients,
            data_sizes=self.data_sizes, x_test=self.data.x_test,
            y_test=self.data.y_test, bs_pos=self.mob.bs_pos,
            bs_bw=self.bs_bw, k_shadow=self._k_shadow,
            min_participants=int(np.ceil(w.rho2 * w.n_users)),
            params0=self.params, pos0=self.mob.user_pos,
            aux0=self._mob_aux, counts0=self.part.counts, key0=self._key,
            clip_norm=self.faults.clip_norm)
        # stateful online schedulers (ucb, pf, ...) carry per-user estimates
        # across rounds; None for the stateless registry entries
        self._sched_state = init_state.sched

    # -------------------------------------------------------- fused engine --
    @property
    def fused_capable(self) -> bool:
        return self.cfg.scheduler in FUSED_SCHEDULERS

    def _carry(self) -> RoundState:
        """The engine's attributes as one typed :class:`RoundState`.

        Optional slots are ``None`` exactly when the feature is off, so the
        carry's pytree STRUCTURE is a static function of the compile bucket
        (same leaves -> same traced graph -> no silent recompiles)."""
        need_prev = self._hier or self._faulty
        return RoundState(
            world=WorldState(pos=self.mob.user_pos, mob_aux=self._mob_aux),
            clients=ClientState(
                counts=self.part.counts,
                prev_bs=self._prev_bs if need_prev else None),
            server=ServerState(
                params=self.params,
                edge_params=self.edge_params if self._hier else None,
                edge_weight=self.edge_weight if self._hier else None,
                queue=self._queue if self._async else None),
            sched=self._sched_state, key=self._key)

    def _set_carry(self, state: RoundState) -> None:
        self.params = state.server.params
        self.mob = MobilityState(user_pos=state.world.pos,
                                 bs_pos=self.mob.bs_pos)
        self._mob_aux = state.world.mob_aux
        self.part = ParticipationState(counts=state.clients.counts,
                                       round_idx=self.round_idx)
        self._key = state.key
        self._sched_state = state.sched
        if self._hier:
            self.edge_params = state.server.edge_params
            self.edge_weight = state.server.edge_weight
        if self._hier or self._faulty:
            self._prev_bs = state.clients.prev_bs
        if self._async:
            self._queue = state.server.queue

    def _round_step(self, carry: RoundState, r) -> tuple[RoundState, dict]:
        """One fully-traced round: mobility -> channel -> schedule -> local
        SGD -> masked FedAvg (single-tier Eq. (2) or per-BS edge
        aggregation + tau_global sync) -> eval under ``lax.cond``.  ``r``
        may be a host int (per-round step) or a traced counter (fused
        scan).  The body is the canonical :func:`make_round_step` step —
        the same function the learning sweep scans."""
        return self._step_fn(carry, r)

    def _run_scan(self, carry: tuple, r0, n_rounds: int):
        """n_rounds of :meth:`_round_step` as one ``lax.scan``."""
        rs = r0 + jnp.arange(n_rounds)
        return jax.lax.scan(self._round_step, carry, rs)

    # ------------------------------------------------- buffered-async engine --
    def _async_step(self, carry: RoundState, r) -> tuple[RoundState, dict]:
        """One fully-traced async tick: mobility -> channel -> schedule ->
        dispatch the non-busy scheduled clients with their Eq. (1)
        completion times -> advance the event queue -> staleness-weighted
        Eq. (2) over this tick's deliveries -> eval under ``lax.cond``.

        The control plane (mobility/channel/scheduling and, when active,
        the fault realization) splits the SAME subkeys in the SAME order as
        :meth:`_round_step`, which is what makes the degenerate sync limit
        (tick covering the slowest client, alpha=0) bit-identical rather
        than a different random trajectory.
        """
        self._async_traces += 1          # python side effect: trace-time only
        return self._step_fn(carry, r)

    def _run_async_scan(self, carry: tuple, r0, n_rounds: int):
        """n_rounds ticks of :meth:`_async_step` as one ``lax.scan``."""
        rs = r0 + jnp.arange(n_rounds)
        return jax.lax.scan(self._async_step, carry, rs)

    # ------------------------------------------------------------------ API
    def run(self, n_rounds: int, mode: str | None = None) -> list[RoundRecord]:
        """Run ``n_rounds``; returns one :class:`RoundRecord` per round.

        ``mode``: ``"fused"`` (one compiled scan, default when the scheduler
        is jit-able), ``"step"`` (one jitted dispatch per round, records
        accumulated on device and transferred once at the end), ``"eager"``
        (the seed's per-round host path — the only option for the
        host-numpy ``dagsa`` scheduler), or ``"async"`` (the buffered-async
        tick engine — one compiled scan; the default and only mode when
        ``aggregation_async=True``).
        """
        if mode is None:
            mode = ("async" if self._async
                    else "fused" if self.fused_capable else "eager")
        if mode == "async" and not self._async:
            raise ValueError(
                "mode='async' needs FLConfig(aggregation_async=True, "
                "tick_s=...) — the event-queue carry is sized at init")
        if self._async and mode != "async":
            raise ValueError(
                f"aggregation_async=True runs mode='async' only (the event "
                f"queue rides the scan carry); got mode={mode!r}")
        if mode in ("fused", "step") and not self.fused_capable:
            raise ValueError(
                f"scheduler {self.cfg.scheduler!r} does not trace; "
                f"mode={mode!r} needs one of {FUSED_SCHEDULERS} "
                f"(use mode='eager')")
        if mode == "eager" and self._hier:
            raise ValueError(
                "aggregation='hierarchical' lives in the traced round step; "
                "use mode='fused' or mode='step'")
        if mode == "eager" and (self._compress is not None or self._hetero):
            raise ValueError(
                "compressed uplink / device heterogeneity live in the "
                "traced round step; use mode='fused' or mode='step'")
        if mode == "eager" and self.cfg.scheduler in sched.STATEFUL_SCHEDULERS:
            raise ValueError(
                f"stateful scheduler {self.cfg.scheduler!r} carries per-user "
                f"estimates in the fused RoundState; mode='eager' would "
                f"restart them every round — use mode='fused' or 'step'")
        if n_rounds <= 0:
            return []
        # host spans on the profiler's clock: the whole call, building the
        # carry and enqueueing the rounds, then bringing records back
        # (``fl.fetch`` in :meth:`_finish`); each names its first round
        r0 = self.round_idx
        with jax.profiler.TraceAnnotation("fl.call", round=r0):
            if mode == "eager":
                return [self._run_round_eager() for _ in range(n_rounds)]
            with jax.profiler.TraceAnnotation("fl.dispatch", round=r0,
                                              rounds=n_rounds):
                carry, outs = self._dispatch(mode, n_rounds)
            self.round_idx += n_rounds
            self._set_carry(carry)
            return self._finish(outs, n_rounds)

    def _dispatch(self, mode: str, n_rounds: int) -> tuple[RoundState, dict]:
        """Enqueue ``n_rounds`` compiled rounds from the current carry;
        returns the new carry and the stacked device records."""
        if mode == "async":
            return self._async_scan_jit(self._carry(), self.round_idx,
                                        n_rounds=n_rounds)
        if mode == "fused":
            return self._scan_jit(self._carry(), self.round_idx,
                                  n_rounds=n_rounds)
        if mode == "step":
            carry, collected = self._carry(), []
            for r in range(self.round_idx, self.round_idx + n_rounds):
                carry, out = self._step_jit(carry, r)
                collected.append(out)
            return carry, {k: jnp.stack([o[k] for o in collected])
                           for k in collected[0]}
        raise ValueError(f"unknown mode {mode!r}")

    def _finish(self, outs: dict, n_rounds: int) -> list[RoundRecord]:
        """Stacked device records -> host RoundRecords (ONE transfer)."""
        r0 = self.round_idx - n_rounds         # round_idx already advanced
        with jax.profiler.TraceAnnotation("fl.fetch", round=r0):
            outs = jax.tree.map(np.asarray, outs)    # the only host sync
        first = r0 + 1
        wall = self.wall_clock + np.cumsum(outs["t_round"], dtype=np.float64)
        hand = outs.get("handover_rate")
        n_del = outs.get("n_delivered")
        n_inf = outs.get("n_inflight")
        n_drp = outs.get("n_dropped")
        recs = [RoundRecord(round_idx=first + i,
                            t_round=float(outs["t_round"][i]),
                            wall_clock=float(wall[i]),
                            n_selected=int(outs["n_selected"][i]),
                            test_acc=float(outs["test_acc"][i]),
                            min_part_rate=float(outs["min_part_rate"][i]),
                            n_trained=int(outs["n_trained"][i]),
                            handover_rate=(float(hand[i]) if hand is not None
                                           else float("nan")),
                            n_delivered=(int(n_del[i]) if n_del is not None
                                         else -1),
                            delivered_rate=(
                                float(outs["delivered_rate"][i])
                                if n_del is not None else float("nan")),
                            goodput_mbit_s=(
                                float(outs["goodput_mbit_s"][i])
                                if n_del is not None else float("nan")),
                            n_inflight=(int(n_inf[i]) if n_inf is not None
                                        else -1),
                            n_dropped=(int(n_drp[i]) if n_drp is not None
                                       else -1))
                for i in range(n_rounds)]
        self.wall_clock = float(wall[-1])
        return recs

    def run_round(self) -> RoundRecord:
        """One round, returned as a host RoundRecord (syncs: this is the
        interactive per-round API; use :meth:`run` for throughput)."""
        if self._async:
            return self.run(1, mode="async")[0]
        if not self.fused_capable:
            return self._run_round_eager()
        carry, out = self._step_jit(self._carry(), self.round_idx)
        self.round_idx += 1
        self._set_carry(carry)
        return self._finish({k: jnp.stack([v]) for k, v in out.items()}, 1)[0]

    # ---------------------------------------------------------- eager path --
    def _run_round_eager(self) -> RoundRecord:
        """The seed's original per-round path: eager control plane, separate
        fleet/aggregation dispatches, per-round host syncs.  Required for
        the host-numpy ``dagsa`` scheduler; kept verbatim as the benchmark
        baseline for the fused engine."""
        cfg, w = self.cfg, self.wireless
        fp = self._fault_params
        if self._faulty:
            self._key, k_mob, k_prob, k_sched, k_fleet, k_fault = \
                jax.random.split(self._key, 6)
        else:
            self._key, k_mob, k_prob, k_sched, k_fleet = \
                jax.random.split(self._key, 5)

        pos, self._mob_aux = mobility.step_named(
            self._mob_model, k_mob, self.mob.user_pos, self._mob_aux, w,
            pause_s=self._mob_pause, gm_memory=self._mob_gm)
        self.mob = MobilityState(user_pos=pos, bs_pos=self.mob.bs_pos)
        shadow_db = None
        if self._shadow_sigma > 0.0:
            shadow_db = self._shadow_sigma * channel.sample_shadowing(
                self._k_shadow, pos, self.mob.bs_pos, w, sigma_db=1.0)
        prob = channel.make_problem(k_prob, self.mob, w, self.part.counts,
                                    self.part.round_idx, bs_bw=self.bs_bw,
                                    shadow_db=shadow_db)
        if self._faulty:
            dist = self.mob.distances()
            serving = camped_bs(dist)
            handover = (serving != self._prev_bs) & (self._prev_bs >= 0)
            edge_frac = fl_faults.edge_proximity(dist, serving, w)
            prob = dataclasses.replace(
                prob, p_deliver=fl_faults.delivery_probability(
                    fp, edge_frac, handover))
        res = sched.schedule(cfg.scheduler, prob, w, k_sched,
                             seed=cfg.seed * 100003 + self.round_idx)
        if self._faulty:
            tcomp_eff, alive, corrupt = fl_faults.sample_round_faults(
                k_fault, fp, edge_frac, handover, prob.tcomp)
            t_user = latency.per_user_latency(prob, res, tcomp=tcomp_eff)
            delivered = (res.selected & alive
                         & latency.on_time(t_user, fp["deadline_s"]))
            t_round = float(latency.deadline_round_latency(
                t_user, res.selected, fp["deadline_s"]))
        else:
            delivered = res.selected
            t_round = float(res.t_round)
        keys = jax.random.split(k_fleet, w.n_users)
        client_params = self._fleet(self.params, self.x_clients,
                                    self.y_clients, keys)
        if self._faulty:
            client_params = fl_faults.corrupt_updates(
                client_params, corrupt, fp["corrupt_mode_id"],
                fp["corrupt_scale"])
            self._prev_bs = serving
        # donated: the fleet's [N, ...] buffers die into the reduction
        self.params = fl_server.fedavg_donating(
            self.params, client_params, delivered, self.data_sizes,
            clip_norm=self.faults.clip_norm)
        # participation follows delivery (lost updates stay necessary)
        self.part = ParticipationState(
            counts=self.part.counts + delivered.astype(self.part.counts.dtype),
            round_idx=self.part.round_idx + 1)
        self.wall_clock += t_round
        self.round_idx += 1

        acc = float("nan")
        if cfg.eval_every and self.round_idx % cfg.eval_every == 0:
            acc = float(self._acc(self.params, self.data.x_test,
                                  self.data.y_test))
        min_rate = float(jnp.min(self.part.counts)) / max(self.round_idx, 1)
        rec = RoundRecord(round_idx=self.round_idx, t_round=t_round,
                          wall_clock=self.wall_clock,
                          n_selected=int(res.selected.sum()),
                          test_acc=acc, min_part_rate=min_rate,
                          n_trained=w.n_users)
        if self._faulty:
            n_sel = max(int(res.selected.sum()), 1)
            n_del = int(delivered.sum())
            rec = dataclasses.replace(
                rec, n_delivered=n_del, delivered_rate=n_del / n_sel,
                goodput_mbit_s=n_del * w.model_mbit / max(t_round, 1e-9))
        return rec


def accuracy_at_budget(records: list[RoundRecord],
                       budget_s: float) -> float:
    """Best test accuracy reached within a simulated time budget (the
    paper's comparison metric: 'accuracy under the same time budget')."""
    accs = [r.test_acc for r in records
            if r.wall_clock <= budget_s and r.test_acc == r.test_acc]
    return max(accs) if accs else float("nan")
