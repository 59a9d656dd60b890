"""One control-plane decision of the wireless sweep, by the plain reference.

The world of ``world_seed`` is drawn on the device (positions, one Random
Direction step, the bf16 channel planes, compute times), each BS's best
users are handed to the host, and the float64 greedy schedules them.
"""
from __future__ import annotations

import numpy as np

from bench.reference import greedy
from bench.reference.world import sweep_decision_inputs

# Users per BS handed to the host greedy: a BS's candidate is its best
# remaining user, and a decision picks a few hundred users in all.
TOP_PER_BS = 2048


def decide(world_seed: int, w: dict, speed: float) -> dict:
    if w["rho1"] != 0.0:
        raise ValueError("the first decision of a world with rho1 > 0 makes "
                         "every user necessary; this reference covers "
                         "rho1 = 0")
    inp = sweep_decision_inputs(world_seed, w, speed, TOP_PER_BS)
    m = inp["cand_idx"].shape[0]
    coeff = {}
    tcomp = {}
    for k in range(m):
        for i, c, t in zip(inp["cand_idx"][k], inp["cand_coeff"][k],
                           inp["cand_tcomp"][k]):
            coeff[(int(i), k)] = c
            tcomp[int(i)] = t
    sets, t_bs = greedy.schedule(
        inp["cand_idx"], inp["cand_val"], lambda i, k: coeff[(i, k)],
        lambda i: tcomp[i], inp["bs_bw"], {}, inp["draws"],
        inp["min_participants"], w["n_users"])
    return {"t_round": float(t_bs.max()),
            "n_selected": int(sum(len(s) for s in sets))}
