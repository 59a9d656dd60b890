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
* Each step's trial solves read each BS's compact member list, not an
  [M, N] mask over every user: the loop state carries ``[M, W]`` f32
  planes of the members' coefficients and computation times (zero past
  each row's count), and BS k's trial is its row plus one lane holding
  its candidate, ``[M, W + 1]`` with ``W + 1 = 128`` (one lane tile).
  Masked-out users add exact zeros to Eq. (11), so only the order of the
  f32 additions changes.  The compact path exists where ``N > W``; at
  ``N <= W`` the greedy is the dense loop alone.
* Hand-over: the compact loop stops when a BS holds ``W`` members, and a
  second ``while_loop`` continues from the same state with the dense
  [M, N] trial step (a ``lax.cond`` per step would run both branches
  under ``vmap``).  ``dense_steps`` counts its steps, so the share the
  compact path served is ``1 - dense_steps / greedy_steps``.
* :func:`dagsa_schedule_batch` vmaps the whole greedy over a stacked fleet
  of problems; ``backend="pallas"`` routes the per-step candidate solves
  through the :mod:`repro.kernels.bandwidth_solve` kernel.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp

from repro.core import bandwidth
from repro.core.types import ScheduleResult, SchedulingProblem

_BACKENDS = ("jax", "pallas")
# Members a BS's compact trial row holds; with its candidate the row is
# one 128-lane tile.
_MEMBER_WIDTH = 127


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
        return bandwidth_solve(coeff.T, tcomp, trial, bs_bw, method=method,
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


def _member_times_with_candidate(members, coeff, tcomp, bs_bw, cand, t_bs,
                                 method="newton", iters=None, backend="jax",
                                 interpret=None):
    """:func:`_bs_times_with_candidate` over each BS's member list.

    ``members`` is ``(member_coeff, member_tcomp, count)``: [M, W] f32
    planes, zero past each row's count, and [M] int32.  Trial row k is BS
    k's members plus a last lane holding cand[k], so the solve reads
    [M, W + 1] and no [M, N] temporary exists.
    """
    m_coeff, m_tcomp, count = members
    m, w = m_coeff.shape
    c = jnp.concatenate(
        [m_coeff, coeff[cand, jnp.arange(m)].astype(jnp.float32)[:, None]],
        axis=1)
    tc = jnp.concatenate(
        [m_tcomp, tcomp[cand].astype(jnp.float32)[:, None]], axis=1)
    lane = jnp.arange(w + 1)[None, :]
    trial = (lane < count[:, None]) | (lane == w)
    if backend == "pallas":
        from repro.kernels.bandwidth_solve import bandwidth_solve
        return bandwidth_solve(c, tc, trial, bs_bw, method=method,
                               iters=iters, lo=t_bs, interpret=interpret)
    return jax.vmap(partial(bandwidth.bs_time, method=method, iters=iters))(
        c, tc, trial, bs_bw, lo_hint=t_bs)


class _Greedy(NamedTuple):
    """The greedy's loop state, the same in the compact and dense loops."""
    assign: jax.Array       # [N, M] bool
    remaining: jax.Array    # [N] bool, not yet scheduled
    t_star: jax.Array       # the round's threshold t*
    t_bs: jax.Array         # [M] each BS's optimal time t_k^*
    cand: jax.Array         # [M] each BS's best-channel remaining user
    cand_val: jax.Array     # [M] its comparison value
    t_with: jax.Array       # [M] t_k^* with the candidate added
    key: jax.Array
    steps: jax.Array        # int32 users the loop added


@partial(jax.jit, static_argnames=("min_participants", "method", "iters",
                                   "backend", "interpret", "selection_block"))
def _schedule(snr, coeff, tcomp, bs_bw, necessary, min_participants, key,
              method="newton", iters=None, backend="jax", interpret=None,
              selection_block=None, snr_scale=None):
    """The greedy: ``(assign, selected, user_bw, t_k, t_round,
    greedy_steps, dense_steps)``, the last two int32: the while-loop
    iterations, one user added each (step 3 onwards), and those of them
    the dense [M, N] trial step took (all of them at ``N <= W``).
    Selection runs under the ``dagsa.select`` scope and every Eq. (11)
    solve under ``dagsa.eq11``, so a trace attributes their device time
    by name."""
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

    def dense_trials(s):
        with jax.named_scope("dagsa.eq11"):
            return solve(coeff, tcomp, s.assign, bs_bw, s.cand, t_bs=s.t_bs)

    with jax.named_scope("dagsa.select"):
        cand0, cval0 = _cands(snr, remaining0)              # [M], [M]
    state = _Greedy(assign0, remaining0, t_star0, t_bs0, cand0, cval0, None,
                    key, jnp.int32(0))

    def add_one(s):
        """Steps 3-4 once: add the picked user and select each BS's next
        candidate.  Returns the new state, whose ``t_with`` is still the
        old one, the picked BS and user, and whether the user was added."""
        has_cand = jnp.any(s.remaining)
        feasible = (s.t_with <= s.t_star) & has_cand
        any_feasible = jnp.any(feasible)

        # pick the feasible BS whose candidate has the best channel; the
        # selection pass already produced each candidate's (masked,
        # dequantised) comparison value, == snr[cand, k] whenever any user
        # remains, so the greedy tie order matches the seed bit-for-bit
        score = jnp.where(feasible, s.cand_val, -jnp.inf)
        k_greedy = jnp.argmax(score)

        # otherwise force-add to a random BS and raise the threshold (8h);
        # m == 1 short-circuits the draw (mirrors the host greedy: a
        # determined draw must not consume entropy)
        key = s.key
        if m > 1:
            key, krand = jax.random.split(key)
            k_forced = jax.random.randint(krand, (), 0, m)
        else:
            k_forced = jnp.int32(0)
        need_more = n_selected(s.assign) < min_participants
        k_star = jnp.where(any_feasible, k_greedy, k_forced)
        i_star = s.cand[k_star]
        do_add = has_cand & (any_feasible | need_more)

        new_assign = jnp.where(do_add, s.assign.at[i_star, k_star].set(True),
                               s.assign)
        new_remaining = jnp.where(do_add,
                                  s.remaining.at[i_star].set(False),
                                  s.remaining)
        # the accepted candidate evaluation IS the BS's new optimal time
        new_t_bs = jnp.where(do_add,
                             s.t_bs.at[k_star].set(s.t_with[k_star]), s.t_bs)
        raised = jnp.maximum(s.t_star, s.t_with[k_star])
        new_t_star = jnp.where(do_add & ~any_feasible, raised, s.t_star)
        with jax.named_scope("dagsa.select"):
            new_cand, new_cval = _cands(snr, new_remaining)
        return (s._replace(assign=new_assign, remaining=new_remaining,
                           t_star=new_t_star, t_bs=new_t_bs, cand=new_cand,
                           cand_val=new_cval, key=key, steps=s.steps + 1),
                k_star, i_star, do_add)

    def body(s):
        s, *_ = add_one(s)
        return s._replace(t_with=dense_trials(s))

    def cond(s):
        any_feasible = jnp.any((s.t_with <= s.t_star) & jnp.any(s.remaining))
        need_more = n_selected(s.assign) < min_participants
        return jnp.any(s.remaining) & (any_feasible | need_more)

    # cond holds only while a user remains and a BS takes one, so every
    # iteration of either loop adds exactly one user: ``steps`` counts them
    width = _MEMBER_WIDTH
    if n <= width:
        state = jax.lax.while_loop(cond, body,
                                   state._replace(t_with=dense_trials(state)))
        compact_steps = 0
    else:
        member_trials = partial(_member_times_with_candidate, method=method,
                                iters=iters, backend=backend,
                                interpret=interpret)

        def compact_body(carry):
            s, (m_coeff, m_tcomp, count) = carry
            s, k_star, i_star, do_add = add_one(s)
            # cond keeps every count under the width: the slot exists
            slot = count[k_star]
            m_coeff = m_coeff.at[k_star, slot].set(
                coeff[i_star, k_star].astype(jnp.float32))
            m_tcomp = m_tcomp.at[k_star, slot].set(
                tcomp[i_star].astype(jnp.float32))
            count = count.at[k_star].add(do_add.astype(jnp.int32))
            members = (m_coeff, m_tcomp, count)
            with jax.named_scope("dagsa.eq11"):
                t_with = member_trials(members, coeff, tcomp, bs_bw, s.cand,
                                       s.t_bs)
            return s._replace(t_with=t_with), members

        def compact_cond(carry):
            s, (_, _, count) = carry
            return cond(s) & jnp.all(count < width)

        members0 = _seed_members(coeff, tcomp, best_bs, necessary, width)
        with jax.named_scope("dagsa.eq11"):
            t_with0 = member_trials(members0, coeff, tcomp, bs_bw, cand0,
                                    t_bs0)
        # a BS past the width from step 1 has no compact trial: the dense
        # trials replace them, in a loop of zero or one trips (lax.cond
        # would run the dense solve on every decision under vmap), and
        # the compact loop then takes no step
        _, t_with0 = jax.lax.while_loop(
            lambda c: c[0],
            lambda c: (jnp.zeros((), bool), dense_trials(state)),
            (jnp.any(members0[2] > width), t_with0))
        state, _ = jax.lax.while_loop(
            compact_cond, compact_body,
            (state._replace(t_with=t_with0), members0))
        compact_steps = state.steps
        state = jax.lax.while_loop(cond, body, state)

    with jax.named_scope("dagsa.eq11"):
        t_k, user_bw = bandwidth.solve_all(coeff, tcomp, state.assign, bs_bw,
                                           method=method, iters=iters)
    selected = state.assign.any(axis=1)
    return (state.assign, selected, user_bw, t_k, jnp.max(t_k), state.steps,
            state.steps - compact_steps)


def _seed_members(coeff, tcomp, best_bs, necessary, width):
    """Step 1's member lists: each BS's necessary users, in user order, as
    ``(member_coeff [M, W], member_tcomp [M, W], count [M])``.  A row
    holds its first ``W`` users; ``count`` is the BS's full count."""
    n, m = coeff.shape
    on_bs = jnp.where(necessary, best_bs, m).astype(jnp.int32)  # m: none
    on_sorted, order = jax.lax.sort(
        (on_bs, jnp.arange(n, dtype=jnp.int32)), num_keys=1)
    start = jnp.searchsorted(on_sorted, jnp.arange(m + 1, dtype=jnp.int32))
    count = (start[1:] - start[:-1]).astype(jnp.int32)
    lane = jnp.arange(width)[None, :]
    user = order[jnp.minimum(start[:-1, None] + lane, n - 1)]    # [M, W]
    member = lane < count[:, None]
    m_coeff = jnp.where(member, coeff[user, jnp.arange(m)[:, None]], 0.0)
    m_tcomp = jnp.where(member, tcomp[user], 0.0)
    return (m_coeff.astype(jnp.float32), m_tcomp.astype(jnp.float32), count)


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
      backend: "jax" (vmapped scalar solver) or "pallas" (per-step
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
