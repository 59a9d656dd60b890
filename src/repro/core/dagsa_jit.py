"""DAGSA-X: a fully-compiled (jit/vmap-able) variant of Algorithm 1.

Beyond-paper contribution: the host greedy in :mod:`repro.core.dagsa` is
faithful but Python-sequential; this variant expresses the same greedy
policy with ``lax.while_loop`` so thousands of simulated cells can be
scheduled in parallel (vmap over problems) on accelerator — the fleet-scale
use the Pallas ``bandwidth_solve`` kernel exists for.

Greedy order differs slightly from the listing (one (BS,user) addition per
iteration instead of a per-BS inner while), which is an equally valid
instance of the paper's "add a small number of users at a time" rule; tests
assert constraint-equivalence and latency parity with the host version.

Performance notes (the control-plane hot path):

* The while-loop state carries the per-BS candidate evaluations, so the
  ``cond``/``body`` pair computes ``_bs_times_with_candidate`` ONCE per
  greedy step (the seed evaluated every candidate twice — once in ``cond``,
  once in ``body``).
* The state also carries the current per-BS optimal times ``t_bs``; since
  t_k^* is monotone nondecreasing as users are added, each candidate solve
  passes ``t_bs`` to Eq. (11) as a tighter lower bracket.  The compiled
  solvers run a FIXED iteration budget, so this buys accuracy per
  iteration rather than wall-clock — it is what makes a reduced ``iters``
  knob safe, and it lets the host-numpy mirror (which does early-exit)
  stop after a couple of Newton steps.
* :func:`dagsa_schedule_batch` vmaps the whole greedy over a stacked fleet
  of problems; ``backend="pallas"`` routes the per-step [M, N] candidate
  solves through the :mod:`repro.kernels.bandwidth_solve` kernel.
"""
from __future__ import annotations

from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.core import bandwidth
from repro.core.types import ScheduleResult, SchedulingProblem

_BACKENDS = ("jax", "pallas")


def _bs_times_with_candidate(coeff, tcomp, assign, bs_bw, cand,
                             t_bs=None, method="newton", iters=None,
                             backend="jax", interpret=None):
    """t_k* if BS k additionally got its candidate user cand[k].

    ``t_bs`` ([M], optional) warm-starts each solve with the BS's current
    optimal time as the lower bracket.  ``backend="pallas"`` solves all M
    trial rows in one :func:`repro.kernels.bandwidth_solve` call.
    """
    m = bs_bw.shape[0]
    if backend == "pallas":
        from repro.kernels.bandwidth_solve import bandwidth_solve
        trial = assign.T.at[jnp.arange(m), cand].set(True)     # [M, N]
        tc = jnp.broadcast_to(tcomp[None, :], trial.shape)
        return bandwidth_solve(coeff.T, tc, trial, bs_bw, method=method,
                               iters=iters, lo=t_bs, interpret=interpret)
    if backend != "jax":
        raise ValueError(f"unknown backend {backend!r}; "
                         f"choose from {_BACKENDS}")

    def per_bs(c_k, mask_k, bw_k, i_k, hint_k):
        trial = mask_k.at[i_k].set(True)
        return bandwidth.bs_time(c_k, tcomp, trial, bw_k, method=method,
                                 iters=iters, lo_hint=hint_k)

    hints = jnp.zeros((m,), coeff.dtype) if t_bs is None else t_bs
    return jax.vmap(per_bs, in_axes=(1, 1, 0, 0, 0))(coeff, assign, bs_bw,
                                                     cand, hints)


@partial(jax.jit, static_argnames=("min_participants", "method", "iters",
                                   "backend", "interpret", "selection_block"))
def _schedule(snr, coeff, tcomp, bs_bw, necessary, min_participants, key,
              method="newton", iters=None, backend="jax", interpret=None,
              selection_block=None, snr_scale=None):
    """The greedy: ``(assign, selected, user_bw, t_k, t_round,
    greedy_steps)``, the last the int32 number of while-loop iterations,
    one user added each (step 3 onwards).  Selection runs under
    the ``dagsa.select`` scope and every Eq. (11) solve under
    ``dagsa.eq11``, so a trace attributes their device time by name."""
    n, m = snr.shape
    solve = partial(_bs_times_with_candidate, method=method, iters=iters,
                    backend=backend, interpret=interpret)

    # Selection routing (Algorithm 1 steps 1 and 3): the dense seed path
    # materialises masked [N, M] temporaries; backend="pallas" streams user
    # blocks through the kernels in repro.kernels.select_topk, and a static
    # ``selection_block`` streams the same blocks in pure jnp (the
    # --user-chunk CPU path).  All three share jnp.argmax tie semantics, so
    # decisions are identical.  ``snr_scale`` ([M], optional) dequantises
    # int8-coded SNR inside the selection pass; candidate comparison values
    # then live in the (order-equivalent) dB domain.
    if backend == "pallas":
        from repro.kernels import select_topk as _sel
        # the kernel's block is bounded by VMEM, not by the caller's chunk:
        # at 8192 users and M=100 its lane-padded [block, 1] tiles ask for
        # 19.8 MiB of the 16 MiB scoped VMEM.  Decisions do not depend on
        # the block size.
        _ub = min(selection_block or _sel.DEFAULT_USER_BLOCK,
                  _sel.DEFAULT_USER_BLOCK)

        def _best_bs(s):
            return _sel.best_bs_argmax(s, snr_scale, user_block=_ub,
                                       interpret=interpret)

        def _cands(s, rem):
            return _sel.masked_bs_argmax(s, rem, snr_scale, user_block=_ub,
                                         interpret=interpret)
    elif selection_block is not None:
        from repro.kernels import select_topk as _sel

        def _best_bs(s):
            return _sel.best_bs_argmax_chunked(s, selection_block, snr_scale)

        def _cands(s, rem):
            return _sel.masked_bs_argmax_chunked(s, rem, selection_block,
                                                 snr_scale)
    else:
        from repro.kernels import ref as _ref

        def _best_bs(s):
            return _ref.best_bs_argmax(s, snr_scale)

        def _cands(s, rem):
            return _ref.masked_bs_argmax(s, rem, snr_scale)

    # -- step 1: necessary users to their best-channel BS ------------------
    with jax.named_scope("dagsa.select"):
        best_bs = _best_bs(snr)
    assign0 = (jax.nn.one_hot(best_bs, m, dtype=bool)
               & necessary[:, None])
    remaining0 = ~necessary

    with jax.named_scope("dagsa.eq11"):
        t_bs0 = jax.vmap(
            partial(bandwidth.bs_time, method=method, iters=iters),
            in_axes=(1, None, 1, 0))(coeff, tcomp, assign0, bs_bw)
    t_star0 = jnp.max(t_bs0)

    def n_selected(assign):
        return jnp.sum(assign.any(axis=1))

    def candidates(assign, remaining, t_bs):
        """Best-channel remaining user per BS + its trial t_k^*."""
        with jax.named_scope("dagsa.select"):
            cand, cand_val = _cands(snr, remaining)           # [M], [M]
        with jax.named_scope("dagsa.eq11"):
            t_with = solve(coeff, tcomp, assign, bs_bw, cand, t_bs=t_bs)
        return cand, cand_val, t_with

    cand0, cval0, t_with0 = candidates(assign0, remaining0, t_bs0)

    def body(state):
        (assign, remaining, t_star, t_bs, cand, cand_val, t_with, key,
         steps) = state
        has_cand = jnp.any(remaining)
        feasible = (t_with <= t_star) & has_cand
        any_feasible = jnp.any(feasible)

        # pick the feasible BS whose candidate has the best channel; the
        # selection pass already produced each candidate's (masked,
        # dequantised) comparison value, == snr[cand, k] whenever any user
        # remains, so the greedy tie order matches the seed bit-for-bit
        score = jnp.where(feasible, cand_val, -jnp.inf)
        k_greedy = jnp.argmax(score)

        # otherwise force-add to a random BS and raise the threshold (8h);
        # m == 1 short-circuits the draw (mirrors the host greedy: a
        # determined draw must not consume entropy)
        if m > 1:
            key, krand = jax.random.split(key)
            k_forced = jax.random.randint(krand, (), 0, m)
        else:
            k_forced = jnp.int32(0)
        need_more = n_selected(assign) < min_participants
        k_star = jnp.where(any_feasible, k_greedy, k_forced)
        i_star = cand[k_star]
        do_add = has_cand & (any_feasible | need_more)

        new_assign = jnp.where(do_add, assign.at[i_star, k_star].set(True),
                               assign)
        new_remaining = jnp.where(do_add, remaining.at[i_star].set(False),
                                  remaining)
        # the accepted candidate evaluation IS the BS's new optimal time
        new_t_bs = jnp.where(do_add, t_bs.at[k_star].set(t_with[k_star]),
                             t_bs)
        raised = jnp.maximum(t_star, t_with[k_star])
        new_t_star = jnp.where(do_add & ~any_feasible, raised, t_star)
        new_cand, new_cval, new_t_with = candidates(new_assign,
                                                    new_remaining, new_t_bs)
        return (new_assign, new_remaining, new_t_star, new_t_bs, new_cand,
                new_cval, new_t_with, key, steps + 1)

    def cond(state):
        assign, remaining, t_star, t_bs, cand, cand_val, t_with, key, _ = state
        any_feasible = jnp.any((t_with <= t_star) & jnp.any(remaining))
        need_more = n_selected(assign) < min_participants
        return jnp.any(remaining) & (any_feasible | need_more)

    # cond holds only while a user remains and a BS takes one, so every
    # iteration adds exactly one user: ``steps`` counts them
    assign, *_, steps = jax.lax.while_loop(
        cond, body,
        (assign0, remaining0, t_star0, t_bs0, cand0, cval0, t_with0, key,
         jnp.int32(0)))

    with jax.named_scope("dagsa.eq11"):
        t_k, user_bw = bandwidth.solve_all(coeff, tcomp, assign, bs_bw,
                                           method=method, iters=iters)
    selected = assign.any(axis=1)
    return assign, selected, user_bw, t_k, jnp.max(t_k), steps


def dagsa_schedule_jit(problem: SchedulingProblem, key: jax.Array,
                       method: str = "newton", iters: int | None = None,
                       selection_block: int | None = None) -> ScheduleResult:
    assign, selected, bw, t_k, t_round, *_ = _schedule(
        problem.snr, problem.coeff, problem.tcomp, problem.bs_bw,
        problem.necessary, int(problem.min_participants), key,
        method=method, iters=iters, selection_block=selection_block)
    return ScheduleResult(assign=assign, selected=selected, bw=bw,
                          bs_time=t_k, t_round=t_round)


# --------------------------------------------------------------- batched --
def stack_problems(problems: Sequence[SchedulingProblem]) -> SchedulingProblem:
    """Stack a fleet of same-shape problems along a new leading axis.

    ``min_participants`` must agree across the fleet (it is a static
    argument of the compiled greedy).
    """
    mins = {int(p.min_participants) for p in problems}
    if len(mins) != 1:
        raise ValueError(f"fleet min_participants must agree, got {mins}")
    have_p = [p.p_deliver is not None for p in problems]
    if any(have_p) and not all(have_p):
        raise ValueError("fleet p_deliver must be set on all problems or "
                         "none")
    return SchedulingProblem(
        snr=jnp.stack([p.snr for p in problems]),
        tcomp=jnp.stack([p.tcomp for p in problems]),
        bs_bw=jnp.stack([p.bs_bw for p in problems]),
        coeff=jnp.stack([p.coeff for p in problems]),
        necessary=jnp.stack([p.necessary for p in problems]),
        min_participants=mins.pop(),
        p_deliver=(jnp.stack([p.p_deliver for p in problems])
                   if all(have_p) else None))


@partial(jax.jit, static_argnames=("min_participants", "method", "iters",
                                   "backend", "interpret", "selection_block"))
def _schedule_batch(snr, coeff, tcomp, bs_bw, necessary, min_participants,
                    keys, method="newton", iters=None, backend="jax",
                    interpret=None, selection_block=None, snr_scale=None):
    fn = partial(_schedule, min_participants=min_participants, method=method,
                 iters=iters, backend=backend, interpret=interpret,
                 selection_block=selection_block)
    return jax.vmap(
        lambda s, c, t, b, ne, k, sc: fn(s, c, t, b, ne, key=k,
                                         snr_scale=sc))(
        snr, coeff, tcomp, bs_bw, necessary, keys, snr_scale)


def dagsa_schedule_batch(problems, keys: jax.Array, method: str = "newton",
                         iters: int | None = None, backend: str = "jax",
                         interpret: bool | None = None,
                         selection_block: int | None = None,
                         snr_scale: jnp.ndarray | None = None
                         ) -> ScheduleResult:
    """DAGSA-X over a whole fleet of cells in ONE compiled call.

    Args:
      problems: a stacked :class:`SchedulingProblem` (leading fleet axis on
        every array field) or a sequence of same-shape problems.
      keys: [F, 2] PRNG keys, one per problem (``jax.random.split``).
      method/iters: Eq. (11) solver knobs (safeguarded Newton by default).
      backend: "jax" (vmapped scalar solver) or "pallas" (per-step [M, N]
        candidate solves through the ``bandwidth_solve`` kernel AND
        streaming segmented-argmax selection through
        ``kernels.select_topk``, so no [N, M] selection temporaries).
      interpret: pallas interpret-mode override (auto: True off-TPU).
      selection_block: static user-block size for streamed selection; with
        backend="jax" this switches Algorithm 1 steps 1/3 to the chunked
        jnp path (bit-identical decisions, [block, M] temporaries).
      snr_scale: [F, M] per-BS dequantisation scales when ``problems.snr``
        holds int8 dB codes (channel.quantize_snr_int8); None for linear
        SNR.  Selection compares dequantised values in-block.

    Returns:
      ScheduleResult with a leading fleet axis on every field.  Decisions
      are identical to calling :func:`dagsa_schedule_jit` per problem with
      the same keys.
    """
    if not isinstance(problems, SchedulingProblem):
        problems = stack_problems(problems)
    assign, selected, bw, t_k, t_round, *_ = _schedule_batch(
        problems.snr, problems.coeff, problems.tcomp, problems.bs_bw,
        problems.necessary, int(problems.min_participants), keys,
        method=method, iters=iters, backend=backend, interpret=interpret,
        selection_block=selection_block, snr_scale=snr_scale)
    return ScheduleResult(assign=assign, selected=selected, bw=bw,
                          bs_time=t_k, t_round=t_round)
