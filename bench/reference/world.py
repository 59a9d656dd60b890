"""The simulated world, drawn from a seed without the program's code.

These are copies of the generators the system under test uses to turn a
seed into its inputs: the synthetic MNIST set, the paper's label-shard
split, the CNN's initial weights, user and BS positions, Random Direction
mobility and the Rayleigh channel.  They are copied, not imported, so that
a later change to the program cannot move the yardstick: the reference
draws the same world from the same seed, or the comparison says so.

Two PRNG conventions exist, as in the program: the FL engine's
(``FLSimulation``) and the wireless sweep's (``run_sweep``).  Each is
written out in full below.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

N_CLASSES = 10
MAX_GREEDY_STEPS = 4096
DATA_NOISE = {"mnist": 3.0}


# ----------------------------------------------------------------- data --
def _smooth_prototypes(key, h, w, c):
    raw = jax.random.normal(key, (N_CLASSES, h, w, c))
    k = jnp.ones((5,)) / 5.0
    for _ in range(3):
        raw = jax.vmap(lambda img: jnp.apply_along_axis(
            lambda v: jnp.convolve(v, k, mode="same"), 0, img))(raw)
        raw = jax.vmap(lambda img: jnp.apply_along_axis(
            lambda v: jnp.convolve(v, k, mode="same"), 1, img))(raw)
    raw = raw / jnp.maximum(raw.std(axis=(1, 2, 3), keepdims=True), 1e-6)
    return raw * 2.0


def _sample_split(key, protos, n, noise):
    ky, kx = jax.random.split(key)
    labels = jnp.tile(jnp.arange(N_CLASSES), n // N_CLASSES + 1)[:n]
    labels = jax.random.permutation(ky, labels)
    eps = jax.random.normal(kx, (n,) + protos.shape[1:]) * noise
    return (protos[labels] + eps).astype(jnp.float32), labels.astype(jnp.int32)


def dataset(seed: int, n_train: int, n_test: int, shape, noise: float):
    """(x_train, y_train, x_test, y_test): class prototypes plus noise."""
    h, w, c = shape
    kp, ktr, kte = jax.random.split(jax.random.PRNGKey(seed), 3)
    protos = _smooth_prototypes(kp, h, w, c)
    x_tr, y_tr = _sample_split(ktr, protos, n_train, noise)
    x_te, y_te = _sample_split(kte, protos, n_test, noise)
    return x_tr, y_tr, x_te, y_te


def shard_split(key, labels, n_users: int, shards_per_user: int):
    """[N, n_i] indices: label-sorted equal shards, ``shards_per_user``
    drawn at random for each user (paper section IV)."""
    n = labels.shape[0]
    n_shards = n_users * shards_per_user
    shard_size = n // n_shards
    order = jnp.argsort(labels, stable=True)
    n_keep = n_shards * shard_size
    keep = np.arange(n_keep) * n // n_keep
    order = order[jnp.asarray(keep)]
    shards = order.reshape(n_shards, shard_size)
    perm = jax.random.permutation(key, n_shards)
    return shards[perm].reshape(n_users, shards_per_user * shard_size)


def cnn_init(key, model: dict):
    """He-normal conv/dense weights, zero biases."""
    k1, k2, k3, k4 = jax.random.split(key, 4)
    c, c1, c2 = model["channels"], model["c1"], model["c2"]
    hid, ncls = model["hidden"], model["n_classes"]
    flat = (model["height"] // 4) * (model["width"] // 4) * c2

    def he(k, shape, fan_in):
        return jax.random.normal(k, shape) * jnp.sqrt(2.0 / fan_in)

    return {
        "conv1": {"w": he(k1, (3, 3, c, c1), 9 * c), "b": jnp.zeros((c1,))},
        "conv2": {"w": he(k2, (3, 3, c1, c2), 9 * c1), "b": jnp.zeros((c2,))},
        "fc1": {"w": he(k3, (flat, hid), flat), "b": jnp.zeros((hid,))},
        "fc2": {"w": he(k4, (hid, ncls), hid), "b": jnp.zeros((ncls,))},
    }


# -------------------------------------------------------------- geometry --
def grid_bs(key, n_bs: int, area: float):
    cols = int(np.ceil(np.sqrt(n_bs)))
    rows = (n_bs + cols - 1) // cols
    xs = (np.arange(n_bs) % cols + 0.5) / cols * area
    ys = (np.arange(n_bs) // cols + 0.5) / rows * area
    grid = jnp.asarray(np.stack([xs, ys], axis=-1), jnp.float32)
    jitter = jax.random.uniform(key, (n_bs, 2), minval=-0.05,
                                maxval=0.05) * area
    return jnp.clip(grid + jitter, 0.0, area)


def reflect(x, length: float):
    """Specular reflection into [0, length] (triangle wave)."""
    return length - jnp.abs(jnp.mod(x, 2.0 * length) - length)


def rd_step(key, pos, speed, area: float, dt: float):
    """Random Direction: a fresh heading per user, ``speed * dt`` metres."""
    theta = jax.random.uniform(key, (pos.shape[0],), minval=0.0,
                               maxval=2.0 * jnp.pi)
    vel = speed * jnp.stack([jnp.cos(theta), jnp.sin(theta)], axis=-1)
    return reflect(pos + vel * dt, area)


def distances(user_pos, bs_pos):
    d = jnp.linalg.norm(user_pos[:, None, :] - bs_pos[None, :, :], axis=-1)
    return jnp.maximum(d, 1.0)


def faded_snr(key, dist, w: dict):
    """Linear SNR: 128.1 + 37.6 log10(d_km) path loss, Rayleigh |h|^2."""
    gain = jax.random.exponential(key, dist.shape)
    pl = 128.1 + 37.6 * jnp.log10(jnp.maximum(dist, 1.0) / 1000.0)
    snr_db = w["tx_dbm_mhz"] - w["noise_dbm_mhz"] - pl
    return jnp.power(10.0, snr_db / 10.0) * gain


def time_coeff(snr, model_mbit: float):
    """c = S / log2(1 + snr), MHz * s."""
    return model_mbit / jnp.maximum(jnp.log2(1.0 + snr), 1e-9)


def to_bf16(x):
    """Round to bfloat16 storage (round to nearest even)."""
    return jax.lax.reduce_precision(x.astype(jnp.float32), exponent_bits=8,
                                    mantissa_bits=7).astype(jnp.bfloat16)


def min_participants(w: dict) -> int:
    return int(math.ceil(w["rho2"] * w["n_users"]))


def _forced_draws(key, m: int):
    def body(k, _):
        k, kr = jax.random.split(k)
        return k, kr
    _, krs = jax.lax.scan(body, key, None, length=MAX_GREEDY_STEPS)
    return jax.vmap(lambda kr: jax.random.randint(kr, (), 0, m))(krs)


# The greedy's per-iteration random BS draws: one key split per iteration,
# a uniform BS index from each.
forced_draws = jax.jit(_forced_draws, static_argnums=1)


# ----------------------------------------------------------- FL engine --
class EngineWorld:
    """The FL engine's world: initial state and one round's problem.

    Mirrors the engine's seed layout: ``split(PRNGKey(seed), 6)`` into
    data, partition, positions, model, bandwidth and run keys, then
    ``split(key, 5)`` per round into mobility, problem, scheduler and
    fleet keys.
    """

    def __init__(self, seed: int, cfg: dict):
        self.w = cfg["wireless"]
        fl = cfg["fl"]
        key = jax.random.PRNGKey(seed)
        (_, k_part, k_pos, k_model, _, self.key) = jax.random.split(key, 6)
        ds = cfg["dataset"]
        x_tr, y_tr, self.x_test, self.y_test = dataset(
            seed, fl["n_train"], fl["n_test"],
            (ds["height"], ds["width"], ds["channels"]), DATA_NOISE[ds["name"]])
        idx = shard_split(k_part, y_tr, self.w["n_users"],
                          fl["shards_per_user"])
        self.x_clients = x_tr[idx]
        self.y_clients = y_tr[idx]
        self.data_sizes = jnp.full((self.w["n_users"],), idx.shape[1],
                                   jnp.float32)
        self.params0 = cnn_init(k_model, cfg["model"])
        ku, kb = jax.random.split(k_pos)
        self.pos = jax.random.uniform(ku, (self.w["n_users"], 2), minval=0.0,
                                      maxval=self.w["area_m"])
        self.bs_pos = grid_bs(kb, self.w["n_bs"], self.w["area_m"])
        self.bs_bw = np.full((self.w["n_bs"],), self.w["bs_bandwidth_mhz"])
        self.counts = np.zeros((self.w["n_users"],))

    def next_round(self, r: int, speed: float):
        """Advance the world one round; returns the round's problem as host
        arrays and the fleet key."""
        w = self.w
        self.key, k_mob, k_prob, k_sched, k_fleet = jax.random.split(
            self.key, 5)
        self.pos = rd_step(k_mob, self.pos, jnp.float32(speed), w["area_m"],
                           w["round_duration_s"])
        k_snr, k_tc = jax.random.split(k_prob)
        dist = distances(self.pos, self.bs_pos)
        snr = faded_snr(k_snr, dist, w)
        tcomp = jax.random.uniform(k_tc, (w["n_users"],),
                                   minval=w["tcomp_min_s"],
                                   maxval=w["tcomp_max_s"])
        coeff = time_coeff(snr, w["model_mbit"])
        necessary = self.counts < np.float32(w["rho1"]) * np.float32(r + 1)
        draws = forced_draws(k_sched, w["n_bs"])
        serving = np.asarray(jnp.argmin(dist, axis=1))
        return dict(snr=np.asarray(snr, np.float64),
                    coeff=np.asarray(coeff, np.float64),
                    tcomp=np.asarray(tcomp, np.float64),
                    bs_bw=self.bs_bw, necessary=np.asarray(necessary),
                    draws=np.asarray(draws), serving=serving,
                    min_participants=min_participants(w)), k_fleet


# ------------------------------------------------------ wireless sweep --
@jax.jit
def _sweep_world_keys(seed_key):
    key = jax.random.split(seed_key, 1)[0]
    return jax.random.split(key, 6)


def sweep_decision_inputs(world_seed: int, w: dict, speed: float,
                          top: int):
    """Round 0 of the wireless sweep's world for ``world_seed``, reduced to
    what the greedy reads: each BS's ``top`` best users (descending bf16
    SNR, lower index first on ties) with their SNR, bf16 coefficient and
    compute time, plus the forced draws.

    Bf16 storage of both planes is the configuration's; the greedy never
    looks past a BS's best remaining user, so the ``top`` best per BS hold
    every candidate while fewer than ``top`` users are picked.
    """
    n, m, area = w["n_users"], w["n_bs"], w["area_m"]
    k_pos, k_bs, _, _, _, k_run = _sweep_world_keys(
        jax.random.PRNGKey(world_seed))
    out = _sweep_planes(k_pos, k_bs, k_run, n=n, m=m, area=area,
                        speed=speed, w=tuple(sorted(w.items())), top=top)
    idx, val, coeff, tcomp, draws = (np.asarray(a) for a in out)
    return dict(cand_idx=idx, cand_val=val.astype(np.float64),
                cand_coeff=coeff.astype(np.float64),
                cand_tcomp=tcomp.astype(np.float64),
                bs_bw=np.full((m,), w["bs_bandwidth_mhz"]),
                draws=draws, min_participants=min_participants(w))


def _sweep_planes_impl(k_pos, k_bs, k_run, *, n, m, area, speed, w, top):
    w = dict(w)
    pos = jax.random.uniform(k_pos, (n, 2), minval=0.0, maxval=area)
    _, ku = jax.random.split(k_bs)
    bs_pos = jax.random.uniform(ku, (m, 2), minval=0.0, maxval=area)
    _, k_mob, k_snr, k_tc, k_sched = jax.random.split(k_run, 5)
    pos = rd_step(k_mob, pos, jnp.float32(speed), area,
                  w["round_duration_s"])
    snr = to_bf16(faded_snr(k_snr, distances(pos, bs_pos), w))
    coeff = to_bf16(time_coeff(snr, w["model_mbit"]))
    u = jax.random.uniform(k_tc, (n,))
    t_lo = jnp.float32(w["tcomp_min_s"])
    tcomp = t_lo + u * (jnp.float32(w["tcomp_max_s"]) - t_lo)
    val, idx = jax.lax.top_k(snr.T.astype(jnp.float32), top)   # [M, top]
    cc = jnp.take_along_axis(coeff.T, idx, axis=1).astype(jnp.float32)
    return idx, val, cc, tcomp[idx], forced_draws(k_sched, m)


_sweep_planes = jax.jit(_sweep_planes_impl, static_argnames=(
    "n", "m", "area", "speed", "w", "top"))
