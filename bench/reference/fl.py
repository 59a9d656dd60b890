"""Follow the first rounds of an FL run with the plain reference.

One round: users move, BSs observe the channel, DAGSA picks users and
splits bandwidth (float64 on the host), every scheduled client runs local
SGD from its model, the server aggregates by Eq. (2) (or each BS
edge-aggregates its users and the edges sync every ``tau_global`` rounds),
and the global model is evaluated on the test split.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import cnn, greedy
from bench.reference.world import EngineWorld


def follow(seed: int, cfg: dict, world: dict, n_rounds: int,
           dtype=jnp.float32, half_batch: bool = False,
           matmul: str | None = None, sync: str = "weighted") -> dict:
    """Records of the first ``n_rounds`` rounds, the models after them and
    how often each user was scheduled.

    Matrix products run at the configuration's ``matmul`` precision unless
    ``matmul`` names another.  ``dtype``, ``half_batch`` and ``sync`` exist
    for the control and the planted faults: a bfloat16 data plane, an
    Eq. (2) mean over only the first half of the scheduled clients, or a
    hierarchical world whose edges never sync into the global model
    (``sync="skipped"``) or sync with equal weights in place of the data
    each aggregated (``"equal"``).
    """
    matmul = matmul or cfg["matmul"]
    env = EngineWorld(seed, cfg)
    fl = cfg["fl"]
    w = env.w
    hier = world["aggregation"] == "hierarchical"
    params = env.params0
    m = w["n_bs"]
    edge = jax.tree.map(lambda p: jnp.repeat(p[None], m, axis=0), params)
    edge_w = jnp.zeros((m,), jnp.float32)
    sgd = dict(epochs=fl["local_epochs"], batch_size=fl["batch_size"],
               lr=fl["lr"], dtype=dtype, matmul=matmul)
    recs = []
    for r in range(n_rounds):
        prob, k_fleet = env.next_round(r, world["speed_mps"])
        sets, t_bs, selected = greedy.schedule_dense(
            prob["snr"], prob["coeff"], prob["tcomp"], prob["bs_bw"],
            prob["necessary"], prob["draws"], prob["min_participants"])
        assign = np.zeros((w["n_users"], m), bool)
        for k, users in enumerate(sets):
            assign[users, k] = True
        env.counts = env.counts + selected
        keys = jax.random.split(k_fleet, w["n_users"])
        sel = selected.copy()
        if half_batch:
            sel[np.flatnonzero(sel)[(sel.sum() + 1) // 2:]] = False
        wts = jnp.asarray(sel * np.asarray(env.data_sizes), jnp.float32)
        if hier:
            init = jax.tree.map(lambda e: e[prob["serving"]], edge)
            clients = cnn.fleet_sgd(init, env.x_clients, env.y_clients, keys,
                                    per_client=True, **sgd)
            w_nm = wts[:, None] * jnp.asarray(assign, jnp.float32)
            edge = cnn.edge_fedavg(edge, clients, w_nm, dtype=dtype,
                                   matmul=matmul)
            edge_w = edge_w + jnp.sum(w_nm, axis=0)
            if (r + 1) % world["tau_global"] == 0 and sync != "skipped":
                mix_w = jnp.ones_like(edge_w) if sync == "equal" else edge_w
                params = cnn.edge_mix(params, edge, mix_w, matmul=matmul)
                edge = jax.tree.map(
                    lambda g, e: jnp.broadcast_to(g[None], e.shape),
                    params, edge)
                edge_w = jnp.zeros_like(edge_w)
            evaluated = cnn.edge_mix(params, edge, edge_w, matmul=matmul)
        else:
            clients = cnn.fleet_sgd(params, env.x_clients, env.y_clients,
                                    keys, per_client=False, **sgd)
            params = cnn.fedavg(params, clients, wts, dtype=dtype,
                                matmul=matmul)
            evaluated = params
        acc = float(cnn.accuracy(evaluated, env.x_test, env.y_test,
                                 dtype=dtype, matmul=matmul))
        recs.append({"t_round": float(t_bs.max()),
                     "n_selected": int(selected.sum()), "test_acc": acc})
    return {"records": recs, "params0": env.params0, "params": params,
            "edge_params": edge if hier else None, "counts": env.counts}

