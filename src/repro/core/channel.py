"""Wireless channel model: 3GPP-style path loss + Rayleigh small-scale fading.

Paper Eq. (4): uplink rate r = B * log2(1 + p |h|^2 / N0) with path-loss model
PL(dB) = 128.1 + 37.6 log10(D_km).  Powers are spectral densities (dBm/MHz) so
the SNR inside the log is independent of the allocated bandwidth — this is
what makes the bandwidth sub-problem (10) convex with the clean KKT solution.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.core.types import MobilityState, SchedulingProblem, WirelessConfig


def path_loss_db(dist_m: jnp.ndarray) -> jnp.ndarray:
    """128.1 + 37.6 log10(D) with D in km (paper §II-C)."""
    return 128.1 + 37.6 * jnp.log10(jnp.maximum(dist_m, 1.0) / 1000.0)


def mean_snr(dist_m: jnp.ndarray, cfg: WirelessConfig) -> jnp.ndarray:
    """Linear mean SNR (large-scale only): 10^((p - N0 - PL)/10)."""
    snr_db = cfg.tx_dbm_mhz - cfg.noise_dbm_mhz - path_loss_db(dist_m)
    return jnp.power(10.0, snr_db / 10.0)


def sample_snr(key: jax.Array, dist_m: jnp.ndarray,
               cfg: WirelessConfig,
               shadow_db: jnp.ndarray | None = None) -> jnp.ndarray:
    """Rayleigh-faded linear SNR: |h|^2 ~ Exp(1) on top of the mean SNR.

    ``shadow_db`` optionally adds per-(user,BS) log-normal shadowing —
    unlike fast fading it persists while the user is static, which is what
    makes v=0 runs geometry-stuck (paper Fig. 4 mechanism).
    """
    gain = jax.random.exponential(key, dist_m.shape)
    snr = mean_snr(dist_m, cfg) * gain
    if shadow_db is not None:
        snr = snr * jnp.power(10.0, shadow_db / 10.0)
    return snr


def sample_shadowing(key: jax.Array, user_pos: jnp.ndarray,
                     bs_pos: jnp.ndarray, cfg: WirelessConfig,
                     sigma_db: float = 8.0,
                     corr_dist_m: float = 50.0) -> jnp.ndarray:
    """Spatially-correlated log-normal shadowing field, [N, M] dB.

    Implemented as a per-BS random field evaluated at the user position via
    smooth random Fourier features — users that barely move see barely
    changing shadowing (correlation distance ~corr_dist_m), so the field is
    CONSISTENT across rounds given the same key.
    """
    n_feat = 64
    kw, kp = jax.random.split(key)
    m = bs_pos.shape[0]
    freqs = jax.random.normal(kw, (m, n_feat, 2)) / corr_dist_m
    phases = jax.random.uniform(kp, (m, n_feat), maxval=2.0 * jnp.pi)
    # [N, M, F]: cos(w . x + phi) per BS field
    proj = jnp.einsum("nd,mfd->nmf", user_pos, freqs) + phases[None]
    field = jnp.sqrt(2.0 / n_feat) * jnp.sum(jnp.cos(proj), axis=-1)
    return sigma_db * field


def spectral_efficiency(snr: jnp.ndarray) -> jnp.ndarray:
    """log2(1 + SNR), bits/s/Hz."""
    return jnp.log2(1.0 + snr)


def bandwidth_time_coeff(snr: jnp.ndarray, cfg: WirelessConfig,
                         payload_mbit: jnp.ndarray | None = None
                         ) -> jnp.ndarray:
    """c_{i,k} = s_i / log2(1+snr_{i,k})  [MHz * s].

    Upload latency of user i on BS k with bandwidth B is c_{i,k} / B; this
    coefficient is the only thing the bandwidth solver needs per user.
    ``payload_mbit`` optionally supplies a PER-USER uplink payload s_i
    ([N], Mbit) — the compressed-uplink seam (docs/COMPRESSION.md): scaling
    the coefficient rows is all Eq. (1)/(3)/(11) need, because every
    downstream consumer reads payload only through c_{i,k}.  ``None``
    keeps the uniform ``cfg.model_mbit`` exactly (no scaling op is
    emitted, so compression-off graphs are unchanged).
    """
    se = jnp.maximum(spectral_efficiency(snr), 1e-9)
    if payload_mbit is None:
        return cfg.model_mbit / se
    return jnp.asarray(payload_mbit, jnp.float32)[:, None] / se


# ------------------------------------------------- compact channel storage --
# Bytes/user budget (docs/SCALING.md): the [N, M] channel matrices dominate
# per-round memory at fleet scale.  SNR spans many orders of magnitude but
# selection/equalisation only need ~0.3 dB fidelity, so bf16 (8-bit mantissa,
# exact under monotone casts -> identical argmax ties) halves bytes/user and
# int8 dB codes with a per-BS scale quarter them.
CHANNEL_DTYPES = ("f32", "bf16", "int8")


def compress_channel(x: jnp.ndarray, channel_dtype: str) -> jnp.ndarray:
    """Cast a channel-plane array to its storage dtype ("f32" is a no-op).

    ``"int8"`` is not a plain cast (it needs the per-BS scale row) — use
    :func:`encode_channel` for the full storage tuple.
    """
    if channel_dtype == "f32":
        return x
    if channel_dtype == "bf16":
        # Round explicitly: XLA may fuse a bare cast into its consumers and
        # keep f32 precision there, so a consumer that reads the stored
        # plane (a Pallas kernel) and one fused with the cast would see
        # different values.
        return jax.lax.reduce_precision(
            x.astype(jnp.float32), exponent_bits=8,
            mantissa_bits=7).astype(jnp.bfloat16)
    if channel_dtype == "int8":
        raise ValueError("channel_dtype 'int8' carries a per-BS scale row; "
                         "encode with channel.encode_channel, not "
                         "compress_channel")
    raise ValueError(f"unknown channel_dtype {channel_dtype!r}; "
                     f"choose from {CHANNEL_DTYPES}")


def encode_channel(snr: jnp.ndarray, channel_dtype: str):
    """Encode one round's linear SNR into its channel-plane storage.

    Returns ``(snr_store, snr_scale, snr_linear)``:

      * ``snr_store`` — what selection consumes: the (possibly compressed)
        linear SNR for f32/bf16, or the int8 dB codes.  Feed it to
        ``dagsa_jit._schedule`` together with ``snr_scale`` — the selection
        kernels dequantise in-block.
      * ``snr_scale`` — the [M] per-BS dequantisation scale (int8 only,
        else None).
      * ``snr_linear`` — a linear-domain SNR for everything that needs
        values rather than ranks (delivery discounts, baseline schedulers,
        rate estimates).  For f32/bf16 this IS ``snr_store`` (bit-identical
        to the pre-int8 path); for int8 it is the dequantised f32 plane.
    """
    if channel_dtype == "int8":
        q, scale = quantize_snr_int8(snr)
        return q, scale, dequantize_snr_int8(q, scale)
    s = compress_channel(snr, channel_dtype)
    return s, None, s


def dist_and_shadow(pos: jnp.ndarray, bs_pos: jnp.ndarray, shadow_sigma,
                    k_shadow: jax.Array, cfg: WirelessConfig,
                    user_chunk: int | None):
    """[N, M] distances + shadowing field, optionally in user blocks.

    The shadowing field evaluates 64 random Fourier features per (user, BS)
    pair — the O(N x M x F) intermediate that dominates memory at fleet
    scale.  ``user_chunk`` bounds it: a ``lax.map`` over ceil(N/user_chunk)
    user blocks keeps the peak at [user_chunk, M, F] while producing
    bit-identical values (both terms are per-user independent, and the
    field's frequencies/phases depend only on ``k_shadow``).  A final
    partial block is padded with dummy rows and sliced off — per-row
    determinism means real rows are unaffected, so arbitrary fleet sizes
    work with any chunk.
    """
    def block(pos_blk):
        d = MobilityState(user_pos=pos_blk, bs_pos=bs_pos).distances()
        sh = shadow_sigma * sample_shadowing(k_shadow, pos_blk, bs_pos, cfg,
                                             sigma_db=1.0)
        return d, sh

    n = pos.shape[0]
    if not user_chunk or user_chunk >= n:
        return block(pos)
    pad = (-n) % user_chunk
    if pad:
        pos = jnp.pad(pos, ((0, pad), (0, 0)))
    d, sh = jax.lax.map(block, pos.reshape(-1, user_chunk, 2))
    return d.reshape(n + pad, -1)[:n], sh.reshape(n + pad, -1)[:n]


def quantize_snr_int8(snr: jnp.ndarray):
    """Per-BS symmetric int8 quantisation of linear SNR, dB domain.

    Returns (q [N, M] int8, scale [M] f32) with
    ``dB = 10 log10(snr) ~= q * scale``.  dB -> code is monotone per BS, so
    a per-BS (column) argmax on raw codes is EXACT; cross-BS comparisons
    (per-user best BS, greedy candidate ranking) must compare ``q * scale``
    — the selection kernels dequantise in-block for exactly this reason.
    Worst-case dB error is scale/2, i.e. relative linear-SNR error
    ``10^(scale/20) - 1``.
    """
    db = 10.0 * jnp.log10(jnp.maximum(snr.astype(jnp.float32), 1e-12))
    scale = jnp.maximum(jnp.max(jnp.abs(db), axis=0), 1e-6) / 127.0
    q = jnp.clip(jnp.round(db / scale[None, :]), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_snr_int8(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    """Inverse of :func:`quantize_snr_int8`: linear SNR from dB codes."""
    db = q.astype(jnp.float32) * scale.astype(jnp.float32)[None, :]
    return jnp.power(10.0, db / 10.0)


def sample_tcomp(key: jax.Array, cfg: WirelessConfig,
                 scale: jnp.ndarray | None = None) -> jnp.ndarray:
    """Per-user local computation latency ~ U(tmin, tmax) (paper §IV).

    ``scale`` optionally stretches each user's draw by a per-user compute
    multiplier ([N]; the device-heterogeneity knob, docs/COMPRESSION.md) —
    ``None`` emits the homogeneous-fleet graph unchanged.
    """
    t = jax.random.uniform(key, (cfg.n_users,), minval=cfg.tcomp_min_s,
                           maxval=cfg.tcomp_max_s)
    return t if scale is None else t * scale


def make_problem(key: jax.Array, state: MobilityState, cfg: WirelessConfig,
                 part_counts: jnp.ndarray, round_idx,
                 bs_bw: jnp.ndarray | None = None,
                 shadow_db: jnp.ndarray | None = None,
                 tcomp_scale: jnp.ndarray | None = None,
                 power_scale: jnp.ndarray | None = None,
                 payload_mbit: jnp.ndarray | None = None) -> SchedulingProblem:
    """Assemble one round's SchedulingProblem from the physical state.

    ``necessary`` implements Eq. (8g): user i must participate this round if
    sitting it out would leave its participation count below the post-round
    floor rho1 * (round_idx + 1) — after this round, round_idx + 1 rounds
    have elapsed.  (Testing against the PRE-round floor rho1 * round_idx
    marks users necessary one round late and can never mark anyone at round
    0.)  ``shadow_db`` optionally stacks a [N, M] shadowing field (dB) on
    top of the Rayleigh fading (scenario engine's ``shadowing`` option).

    Device-heterogeneity / compression hooks (all ``None`` = the exact
    homogeneous full-payload graph): ``tcomp_scale`` [N] stretches compute
    latency, ``power_scale`` [N] scales the LINEAR uplink SNR (a per-user
    transmit-power deficit), ``payload_mbit`` [N] replaces the uniform
    Eq. (1) payload S in the bandwidth-time coefficients.
    """
    with jax.named_scope("round.channel"):
        k_snr, k_tc = jax.random.split(key)
        snr = sample_snr(k_snr, state.distances(), cfg, shadow_db=shadow_db)
        if power_scale is not None:
            snr = snr * power_scale[:, None]
        tcomp = sample_tcomp(k_tc, cfg, scale=tcomp_scale)
        coeff = bandwidth_time_coeff(snr, cfg, payload_mbit=payload_mbit)
        if bs_bw is None:
            bs_bw = jnp.full((cfg.n_bs,), cfg.bs_bandwidth_mhz)
        # works for both host ints and traced round counters (fused round scan)
        necessary = part_counts < cfg.rho1 * (round_idx + 1)
    # host math: min_participants must stay a static int under tracing
    min_participants = int(math.ceil(cfg.rho2 * cfg.n_users))
    return SchedulingProblem(snr=snr, tcomp=tcomp, bs_bw=bs_bw, coeff=coeff,
                             necessary=necessary,
                             min_participants=min_participants,
                             payload_mbit=payload_mbit)
