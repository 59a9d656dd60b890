"""Batched Eq. (11) root-finder as a Pallas TPU kernel — the control plane's
hot spot at fleet scale (BS x users x Monte-Carlo sweeps).

Each row is one BS's solve: users live in lanes, the solver state (bracket
+ iterate) lives in per-row VMEM scratch, and the fixed-iteration loop
does one masked lane-reduction per step.  No data-dependent control flow
-> trivially vmappable across thousands of simulated cells.

Two methods share the kernel skeleton (see repro.core.bandwidth for the
derivation): "newton" (default) runs the safeguarded Newton iteration —
tangent step clamped to the live bisection bracket, ~8 steps to float32
tolerance — and "bisect" reproduces the seed's fixed 60-halving loop.  An
optional ``lo`` row vector warm-starts the bracket (t_k^* is monotone
nondecreasing in the scheduled set, so a greedy caller passes the previous
per-BS time).

Layout: coeff/tcomp/mask [K, U], bw/lo [K, 1]; a tcomp shared by every
row (the greedy's dense trials) comes as one [1, U] row, so it is never
broadcast to [K, U] in HBM.  A row of a million users
does not fit VMEM, so the user axis streams through it: the grid is
(row blocks, 1 + iters passes, user blocks), users innermost.  Pass 0
accumulates the bracket (user count, sum c_i, max tcomp_i); pass p >= 1
accumulates f and f' at the current iterate and takes the solver step at
the row's last user block.  Within a block the sums run over lane chunks
into elementwise partial accumulators, so the live set stays a few vregs.
Ragged edges (K or U not a multiple of the block) are not padded in HBM:
the kernel zeroes out-of-range users, and out-of-range rows are dropped
on write.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.bandwidth import default_iters

DEFAULT_ROW_BLOCK = 8
# Users per grid step: 3 f32 planes x 2 pipeline buffers x 8 rows x 16384
# lanes = 3 MiB of VMEM, well inside the default scoped limit.
_USER_BLOCK = 16384
_LANE_CHUNK = 512


def _fold_users(c_ref, t_ref, m_ref, step, acc, *, jb: int, n_users: int,
                block: int):
    """Fold ``step(c, tc, m, acc)`` over the lane chunks of one user block.

    ``jb`` is the block's grid index; users at or past ``n_users`` (the
    ragged final block) are zeroed, which is exactly a masked-out user.
    """
    chunk = _chunk(block)

    def body(i, acc):
        off = pl.multiple_of(i * chunk, chunk)
        cols = ((slice(None), pl.ds(off, chunk)) if chunk < block
                else (slice(None), slice(None)))
        c = c_ref[cols].astype(jnp.float32)
        tc = jnp.broadcast_to(t_ref[cols].astype(jnp.float32), c.shape)
        m = m_ref[cols].astype(jnp.float32)
        if n_users % block:
            user = (jb * block + off
                    + jax.lax.broadcasted_iota(jnp.int32, c.shape, 1))
            ok = user < n_users
            c = jnp.where(ok, c, 0.0)
            tc = jnp.where(ok, tc, 0.0)
            m = jnp.where(ok, m, 0.0)
        return step(c, tc, m, acc)

    if chunk == block:
        return body(0, acc)
    return jax.lax.fori_loop(0, block // chunk, body, acc)


def _chunk(block: int) -> int:
    """Lane width of one partial-sum step within a user block."""
    return _LANE_CHUNK if block % _LANE_CHUNK == 0 else block


def _bw_kernel(c_ref, t_ref, m_ref, bw_ref, lo_ref, o_ref,
               cnt_s, csum_s, tmax_s, f_s, df_s, lo_s, hi_s, t_s, *,
               iters: int, method: str, n_users: int, block: int):
    p = pl.program_id(1)              # 0: bracket pass; p >= 1: solver step
    jb = pl.program_id(2)
    first = jb == 0
    last = jb == pl.num_programs(2) - 1
    fold = functools.partial(_fold_users, c_ref, t_ref, m_ref, jb=jb,
                             n_users=n_users, block=block)
    zeros = jnp.zeros((cnt_s.shape[0], _chunk(block)), jnp.float32)

    def lane_sum(x):
        return jnp.sum(x, axis=-1, keepdims=True)

    @pl.when(p == 0)
    def _bracket():
        @pl.when(first)
        def _():
            cnt_s[...] = jnp.zeros_like(cnt_s)
            csum_s[...] = jnp.zeros_like(csum_s)
            tmax_s[...] = jnp.full_like(tmax_s, -jnp.inf)

        def step(c, tc, m, acc):
            cnt, csum, tmax = acc
            return (cnt + m, csum + c * m,
                    jnp.maximum(tmax, jnp.where(m > 0, tc, -jnp.inf)))

        cnt, csum, tmax = fold(step, (zeros, zeros,
                                      jnp.full_like(zeros, -jnp.inf)))
        cnt_s[...] += lane_sum(cnt)
        csum_s[...] += lane_sum(csum)
        tmax_s[...] = jnp.maximum(tmax_s[...],
                                  jnp.max(tmax, axis=-1, keepdims=True))

        @pl.when(last)
        def _():
            bw = bw_ref[...].astype(jnp.float32)
            tmax_r = jnp.where(cnt_s[...] > 0, tmax_s[...], 0.0)
            hi = tmax_r + csum_s[...] / jnp.maximum(bw, 1e-12) + 1e-9
            lo_s[...] = jnp.clip(lo_ref[...].astype(jnp.float32), tmax_r, hi)
            hi_s[...] = hi
            t_s[...] = hi                 # Newton starts at the upper end

    @pl.when(p > 0)
    def _solve():
        @pl.when(first)
        def _():
            f_s[...] = jnp.zeros_like(f_s)
            df_s[...] = jnp.zeros_like(df_s)

        # bisect evaluates at the midpoint, Newton at its iterate
        t = 0.5 * (lo_s[...] + hi_s[...]) if method == "bisect" else t_s[...]

        def step(c, tc, m, acc):
            # one divide per lane: demand term c*r, slope term -c*r^2
            f, df = acc
            r = 1.0 / jnp.maximum(t - tc, 1e-12)
            inv = jnp.where(m > 0, c * r, 0.0)
            return f + inv, df + inv * r

        f, df = fold(step, (zeros, zeros))
        f_s[...] += lane_sum(f)
        df_s[...] += lane_sum(df)

        @pl.when(last)
        def _():
            lo, hi = lo_s[...], hi_s[...]
            f = f_s[...] - bw_ref[...].astype(jnp.float32)
            if method == "bisect":
                too_fast = f > 0
                lo_s[...] = jnp.where(too_fast, t, lo)
                hi_s[...] = jnp.where(too_fast, hi, t)
            else:
                df = -df_s[...]
                below = f > 0                 # t left of the root
                lo = jnp.where(below, t, lo)
                hi = jnp.where(below, hi, t)
                t_newton = t - f / jnp.minimum(df, -1e-12)
                safe = (t_newton > lo) & (t_newton < hi)
                lo_s[...] = lo
                hi_s[...] = hi
                t_s[...] = jnp.where(safe, t_newton, 0.5 * (lo + hi))

    @pl.when((p == iters) & last)
    def _out():
        t = (0.5 * (lo_s[...] + hi_s[...]) if method == "bisect"
             else t_s[...])
        o_ref[...] = jnp.where(cnt_s[...] > 0, t, 0.0).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("row_block", "iters", "method",
                                             "interpret"))
def bandwidth_solve(coeff: jnp.ndarray, tcomp: jnp.ndarray,
                    mask: jnp.ndarray, bw: jnp.ndarray,
                    lo: jnp.ndarray | None = None,
                    row_block: int = DEFAULT_ROW_BLOCK,
                    iters: int | None = None, method: str = "newton",
                    interpret: bool | None = None) -> jnp.ndarray:
    """coeff/mask [K, U], tcomp [K, U] or [U] (one row every solve shares);
    bw (and optional warm-start lo) [K] -> t* [K].

    ``interpret=None`` auto-enables interpret mode off-TPU so the same entry
    point runs everywhere (CPU tests/benches vs real TPU lowering).
    """
    method_default = default_iters(method)   # rejects unknown methods
    if iters is None:
        iters = method_default
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    k, u = coeff.shape
    rb = min(row_block, k)
    ub = min(_USER_BLOCK, u)
    # compact channel storage may hand us bf16 coeff — solve in f32
    coeff = coeff.astype(jnp.float32)
    tcomp = tcomp.astype(jnp.float32)
    mask_f = mask.astype(jnp.float32)
    lo = jnp.zeros((k,), jnp.float32) if lo is None else lo
    bw2 = bw.astype(jnp.float32).reshape(-1, 1)
    lo2 = lo.astype(jnp.float32).reshape(-1, 1)
    plane = pl.BlockSpec((rb, ub), lambda r, p, j: (r, j))
    row = pl.BlockSpec((rb, 1), lambda r, p, j: (r, 0))
    t_spec = plane
    if tcomp.ndim == 1:           # shared: every row block reads the one row
        tcomp = tcomp.reshape(1, u)
        t_spec = pl.BlockSpec((1, ub), lambda r, p, j: (0, j))
    out = pl.pallas_call(
        functools.partial(_bw_kernel, iters=iters, method=method,
                          n_users=u, block=ub),
        grid=(pl.cdiv(k, rb), 1 + iters, pl.cdiv(u, ub)),
        in_specs=[plane, t_spec, plane, row, row],
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct((k, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((rb, 1), jnp.float32)] * 8,
        interpret=interpret,
    )(coeff, tcomp, mask_f, bw2, lo2)
    return out[:, 0]
