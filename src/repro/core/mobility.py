"""Mobility models behind a common registry (``MOBILITY_MODELS``).

Paper §II-B uses Random Direction (RD): at the beginning of each
communication round every user picks a fresh direction d ~ U[0, 2*pi) and
moves at speed ``v`` for the round duration; on hitting the boundary of the
L x L area it reflects symmetrically about the boundary normal.  Under RD
the stationary user distribution is uniform, which is why the paper picks
it.

Beyond the paper, the scenario engine needs alternatives, all registered in
``MOBILITY_MODELS`` (name -> step function, mirroring ``SCHEDULERS``):

  * ``rd``           — the paper's Random Direction model (default).
  * ``waypoint``     — Random Waypoint with pause times: move toward a
    uniformly drawn target at speed v; on arrival pause for ``pause_s``
    seconds, then draw a fresh target.  Round-granular: the leftover time
    of the arrival round is forfeited (dt is one communication round).
  * ``gauss_markov`` — first-order AR(1) velocity process with tunable
    memory ``gm_memory`` in [0, 1):  v_t = a*v_{t-1} + sqrt(1-a^2)*u_t
    where u_t is a fresh RD velocity draw.  a=0 reduces EXACTLY to RD
    (same keys -> same positions); a->1 approaches straight-line motion.
    The sqrt(1-a^2) innovation scaling keeps E|v_t|^2 = v^2 invariant.
  * ``static``       — v=0 fixed point (paper Fig. 4's stuck-geometry
    regime); positions never change.

Every model shares one step signature so the whole registry is jit/vmap
friendly and can sit behind a traced ``lax.switch`` (:func:`step_switch`)
inside a fully-compiled multi-scenario sweep:

    step_fn(key, pos, aux, area, dt, speed, pause_s, gm_memory)
        -> (new_pos, new_aux)

``aux`` is the RNG-free kinematic state every model carries (a dict with
``vel`` [N, 2], ``target`` [N, 2], ``pause_s`` [N]); models ignore the
fields they do not use, which is what makes the pytree structure identical
across ``lax.switch`` branches.

Reflection is implemented as the triangle-wave folding of the unbounded
displacement, which handles an arbitrary number of bounces in closed form
(needed for large v*dt); Gauss-Markov additionally flips the carried
velocity by the local fold slope so momentum points away from the wall.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.types import MobilityState, WirelessConfig


def _reflect(x: jnp.ndarray, length: float) -> jnp.ndarray:
    """Fold unbounded coordinates back into [0, length] by specular reflection.

    The trajectory of a particle bouncing between two walls is the triangle
    wave of period 2*length: ref(x) = length - |mod(x, 2 length) - length|.
    """
    period = 2.0 * length
    return length - jnp.abs(jnp.mod(x, period) - length)


def _fold_slope(x: jnp.ndarray, length: float) -> jnp.ndarray:
    """d ref(x)/dx in {-1, +1}: the sign a carried velocity picks up when the
    unbounded coordinate ``x`` is folded back into [0, length]."""
    return jnp.where(jnp.mod(x, 2.0 * length) < length, 1.0, -1.0)


def _rd_velocity(key: jax.Array, n: int, speed) -> jnp.ndarray:
    """[N, 2] fresh Random-Direction velocity: uniform heading, |v| = speed."""
    theta = jax.random.uniform(key, (n,), minval=0.0, maxval=2.0 * jnp.pi)
    return speed * jnp.stack([jnp.cos(theta), jnp.sin(theta)], axis=-1)


# ------------------------------------------------------------------- init --
def init_positions(key: jax.Array, cfg: WirelessConfig) -> MobilityState:
    """Uniform users + uniform BSs in the L x L area (paper §IV)."""
    ku, kb = jax.random.split(key)
    user_pos = jax.random.uniform(ku, (cfg.n_users, 2), minval=0.0,
                                  maxval=cfg.area_m)
    bs_pos = jax.random.uniform(kb, (cfg.n_bs, 2), minval=0.0,
                                maxval=cfg.area_m)
    return MobilityState(user_pos=user_pos, bs_pos=bs_pos)


def grid_bs_positions(key: jax.Array, n_bs: int, area_m: float) -> jnp.ndarray:
    """[M, 2] BSs on a near-square jittered grid covering the area.

    The grid itself is host-side math (n_bs is static), so this traces
    cleanly inside jit; only the jitter is a traced op.
    """
    cols = int(np.ceil(np.sqrt(n_bs)))
    rows = (n_bs + cols - 1) // cols
    xs = (np.arange(n_bs) % cols + 0.5) / cols * area_m
    ys = (np.arange(n_bs) // cols + 0.5) / rows * area_m
    grid = jnp.asarray(np.stack([xs, ys], axis=-1), jnp.float32)
    jitter = jax.random.uniform(key, (n_bs, 2), minval=-0.05,
                                maxval=0.05) * area_m
    return jnp.clip(grid + jitter, 0.0, area_m)


def init_positions_grid_bs(key: jax.Array, cfg: WirelessConfig) -> MobilityState:
    """Users uniform; BSs on a jittered grid ("uniformly distributed" reading
    that avoids the degenerate all-BSs-in-one-corner draw for small M)."""
    ku, kb = jax.random.split(key)
    user_pos = jax.random.uniform(ku, (cfg.n_users, 2), minval=0.0,
                                  maxval=cfg.area_m)
    bs_pos = grid_bs_positions(kb, cfg.n_bs, cfg.area_m)
    return MobilityState(user_pos=user_pos, bs_pos=bs_pos)


def init_aux(key: jax.Array, n_users: int, cfg: WirelessConfig,
             speed_mps=None) -> dict:
    """Kinematic state shared by every registered model.

    ``vel`` seeds Gauss-Markov with a valid |v|=speed velocity, ``target``
    seeds Random Waypoint, ``pause_s`` starts everyone moving.
    """
    v = cfg.speed_mps if speed_mps is None else speed_mps
    kv, kt = jax.random.split(key)
    return {
        "vel": _rd_velocity(kv, n_users, v),
        "target": jax.random.uniform(kt, (n_users, 2), minval=0.0,
                                     maxval=cfg.area_m),
        "pause_s": jnp.zeros((n_users,)),
    }


# ------------------------------------------------------------ step kernels --
def _step_rd(key, pos, aux, area, dt, speed, pause_s, gm_memory):
    delta = _rd_velocity(key, pos.shape[0], speed) * dt
    return _reflect(pos + delta, area), aux


def _step_static(key, pos, aux, area, dt, speed, pause_s, gm_memory):
    return pos, aux


def _step_gauss_markov(key, pos, aux, area, dt, speed, pause_s, gm_memory):
    u = _rd_velocity(key, pos.shape[0], speed)
    a = gm_memory
    vel = a * aux["vel"] + jnp.sqrt(jnp.maximum(1.0 - a * a, 0.0)) * u
    unfolded = pos + vel * dt
    # momentum survives the bounce: flip by the fold slope at the endpoint
    new_vel = vel * _fold_slope(unfolded, area)
    return _reflect(unfolded, area), {**aux, "vel": new_vel}


def _step_waypoint(key, pos, aux, area, dt, speed, pause_s, gm_memory):
    target, pause = aux["target"], aux["pause_s"]
    to_t = target - pos
    dist = jnp.linalg.norm(to_t, axis=-1)
    paused = pause > 0.0
    reach = speed * dt
    arrive = ~paused & (dist <= reach)
    step_len = jnp.where(paused, 0.0, jnp.minimum(reach, dist))
    direction = to_t / jnp.maximum(dist, 1e-9)[:, None]
    new_pos = pos + direction * step_len[:, None]
    new_target = jnp.where(arrive[:, None],
                           jax.random.uniform(key, pos.shape, minval=0.0,
                                              maxval=area),
                           target)
    new_pause = jnp.where(arrive, jnp.asarray(pause_s, pos.dtype),
                          jnp.maximum(pause - dt, 0.0))
    return new_pos, {**aux, "target": new_target, "pause_s": new_pause}


# --------------------------------------------------------------- registry --
# name -> step function; insertion order defines the lax.switch branch index.
MOBILITY_MODELS: dict = {
    "rd": _step_rd,
    "waypoint": _step_waypoint,
    "gauss_markov": _step_gauss_markov,
    "static": _step_static,
}


def register_mobility_model(name: str, step_fn) -> None:
    """Add a custom model; it becomes usable in ScenarioSpec/sweeps at once.

    ``step_fn`` must follow the shared signature documented in the module
    docstring and return ``(new_pos, new_aux)`` with the aux structure of
    :func:`init_aux`.
    """
    if name in MOBILITY_MODELS:
        raise ValueError(f"mobility model {name!r} already registered")
    MOBILITY_MODELS[name] = step_fn


def model_index(name: str) -> int:
    """Stable integer id of a registered model (lax.switch branch index)."""
    try:
        return list(MOBILITY_MODELS).index(name)
    except ValueError:
        raise ValueError(f"unknown mobility model {name!r}; choose from "
                         f"{tuple(MOBILITY_MODELS)}") from None


def step_named(name: str, key: jax.Array, pos: jnp.ndarray, aux: dict,
               cfg: WirelessConfig, speed_mps=None, pause_s: float = 0.0,
               gm_memory: float = 0.75) -> tuple[jnp.ndarray, dict]:
    """One round of the model ``name`` (static dispatch by string)."""
    if name not in MOBILITY_MODELS:
        raise ValueError(f"unknown mobility model {name!r}; choose from "
                         f"{tuple(MOBILITY_MODELS)}")
    v = cfg.speed_mps if speed_mps is None else speed_mps
    with jax.named_scope("round.mobility"):
        return MOBILITY_MODELS[name](key, pos, aux, cfg.area_m,
                                     cfg.round_duration_s, v, pause_s,
                                     gm_memory)


def step_switch(model_id, key: jax.Array, pos: jnp.ndarray, aux: dict,
                area: float, dt: float, speed, pause_s,
                gm_memory) -> tuple[jnp.ndarray, dict]:
    """One round of a TRACED model id via ``lax.switch``.

    This is what lets one compiled sweep cover scenarios with different
    mobility models: ``model_id`` is data, not a Python branch, so vmapping
    over scenarios does not re-trace.  All registered models execute and the
    right one is selected — fine for a handful of cheap kinematic updates.
    """
    branches = [
        (lambda k, p, a, s, ps, gm, fn=fn:
         fn(k, p, a, area, dt, s, ps, gm))
        for fn in MOBILITY_MODELS.values()
    ]
    with jax.named_scope("round.mobility"):
        return jax.lax.switch(model_id, branches, key, pos, aux, speed,
                              pause_s, gm_memory)


# ------------------------------------------------------- legacy RD surface --
def step(key: jax.Array, state: MobilityState, cfg: WirelessConfig,
         speed_mps: float | None = None) -> MobilityState:
    """Advance one communication round of RD mobility (paper default).

    Each user draws a fresh heading, advances speed * round_duration metres,
    and reflects off the area boundary.
    """
    v = cfg.speed_mps if speed_mps is None else speed_mps
    theta = jax.random.uniform(key, (state.user_pos.shape[0],),
                               minval=0.0, maxval=2.0 * jnp.pi)
    disp = v * cfg.round_duration_s
    delta = disp * jnp.stack([jnp.cos(theta), jnp.sin(theta)], axis=-1)
    new_pos = _reflect(state.user_pos + delta, cfg.area_m)
    return MobilityState(user_pos=new_pos, bs_pos=state.bs_pos)


def trajectory(key: jax.Array, state: MobilityState, cfg: WirelessConfig,
               n_rounds: int) -> jnp.ndarray:
    """[n_rounds, N, 2] positions over a whole run (scan, fully compiled)."""

    def body(pos, k):
        s = step(k, MobilityState(user_pos=pos, bs_pos=state.bs_pos), cfg)
        return s.user_pos, s.user_pos

    keys = jax.random.split(key, n_rounds)
    _, traj = jax.lax.scan(body, state.user_pos, keys)
    return traj
