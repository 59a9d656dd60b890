"""Plain DAGSA scheduling (Algorithm 1) and the Eq. (11) bandwidth split,
in float64 on the host.

Semantics, as the paper and the system state them:

* Users that must participate this round (Eq. (8g)) go to their best-SNR
  BS first.
* Then, one user at a time: each BS's candidate is its best-SNR user not
  yet scheduled, and its trial time t_k is the Eq. (11) root with that
  user added.  Among the BSs whose trial time does not exceed the round's
  current time t*, the one whose candidate has the best SNR takes it
  (lowest BS index on ties).  When none qualifies and fewer than
  ``min_participants`` (Eq. (8h)) are scheduled, the BS of the iteration's
  random draw takes its candidate and t* rises to its trial time.
* Eq. (11): t_k is the root of sum_i c_i / (t - tcomp_i) = B_k, solved by
  bisection to float64 resolution.

Candidates come from each BS's users in descending SNR order (lower user
index first on ties), so a problem can hand in every user or only each
BS's best few.
"""
from __future__ import annotations

import numpy as np


def eq11_root(c: np.ndarray, tc: np.ndarray, bw: float) -> float:
    """t with sum c / (t - tc) = bw; 0 for an empty set."""
    if c.size == 0:
        return 0.0
    lo = float(tc.max())
    hi = lo + float(c.sum()) / max(bw, 1e-12) + 1e-9
    for _ in range(200):
        mid = 0.5 * (lo + hi)
        if mid <= lo or mid >= hi:
            break
        if float(np.sum(c / (mid - tc))) > bw:
            lo = mid
        else:
            hi = mid
    return 0.5 * (lo + hi)


def schedule(order: np.ndarray, order_val: np.ndarray, coeff_of, tcomp_of,
             bs_bw: np.ndarray, necessary_best: dict, draws: np.ndarray,
             min_participants: int, n_users: int):
    """Run the greedy.

    Args:
      order: [M, K] users of each BS by descending SNR (ties: lower index).
      order_val: [M, K] their SNR values (the greedy's comparison values).
      coeff_of(i, k), tcomp_of(i): a user's Eq. (11) coefficient at BS k
        and compute time.
      bs_bw: [M] bandwidth budgets.
      necessary_best: {user: best BS} for the Eq. (8g) users.
      draws: the per-iteration random BS draws.
      min_participants: the Eq. (8h) floor.
      n_users: population size (how many users can ever be scheduled).

    Returns ``(sets, t_bs)``: the users of each BS and each BS's time.
    """
    m = bs_bw.shape[0]
    sets = [[] for _ in range(m)]
    for i, k in sorted(necessary_best.items()):
        sets[k].append(i)
    taken = set(necessary_best)
    cursor = np.zeros(m, np.int64)

    def root(k, extra=None):
        users = sets[k] + ([] if extra is None else [extra])
        c = np.array([coeff_of(i, k) for i in users], np.float64)
        tc = np.array([tcomp_of(i) for i in users], np.float64)
        return eq11_root(c, tc, float(bs_bw[k]))

    def candidate(k):
        while cursor[k] < order.shape[1] and order[k, cursor[k]] in taken:
            cursor[k] += 1
        if cursor[k] == order.shape[1]:
            raise RuntimeError(f"BS {k} ran out of candidates")
        return int(order[k, cursor[k]]), float(order_val[k, cursor[k]])

    t_bs = np.array([root(k) for k in range(m)])
    t_star = float(t_bs.max())
    remaining = n_users - len(taken)
    cand, cval, t_with = (np.zeros(m, np.int64), np.zeros(m),
                          np.zeros(m))

    def refresh(k):
        if remaining > 0:
            cand[k], cval[k] = candidate(k)
            t_with[k] = root(k, int(cand[k]))

    for k in range(m):
        refresh(k)
    step = 0
    while remaining > 0:
        feasible = t_with <= t_star
        n_sel = len(taken)
        if not feasible.any() and n_sel >= min_participants:
            break
        if feasible.any():
            k_star = int(np.argmax(np.where(feasible, cval, -np.inf)))
        else:
            k_star = int(draws[step])
            t_star = max(t_star, float(t_with[k_star]))
        step += 1
        i_star = int(cand[k_star])
        sets[k_star].append(i_star)
        taken.add(i_star)
        remaining -= 1
        t_bs[k_star] = t_with[k_star]
        # a BS's trial changes when its set grew or its candidate was taken
        for k in range(m):
            if k == k_star or cand[k] == i_star:
                refresh(k)
    t_bs = np.array([root(k) for k in range(m)])
    return sets, t_bs


def schedule_dense(snr: np.ndarray, coeff: np.ndarray, tcomp: np.ndarray,
                   bs_bw: np.ndarray, necessary: np.ndarray,
                   draws: np.ndarray, min_participants: int):
    """The greedy over full [N, M] planes.  Returns (sets, t_bs, selected)."""
    n = snr.shape[0]
    order = np.stack([np.lexsort((np.arange(n), -snr[:, k]))
                      for k in range(snr.shape[1])])
    order_val = np.take_along_axis(snr.T, order, axis=1)
    best = {int(i): int(np.argmax(snr[i])) for i in np.flatnonzero(necessary)}
    sets, t_bs = schedule(order, order_val, lambda i, k: coeff[i, k],
                          lambda i: tcomp[i], bs_bw, best, draws,
                          min_participants, n)
    selected = np.zeros(n, bool)
    for s in sets:
        selected[s] = True
    return sets, t_bs, selected
