"""The comparisons that decide ``correct``: what the timed path produced
against what the plain reference computes from the same seed.

Readings (each has its limit in ``bench/limits/<cell>.json``):

* ``t_round_gap``: the largest relative gap of a round's Eq. (3) latency,
  max over the compared rounds of |t - t_ref| / t_ref.
* ``n_selected_gap``: the largest gap in a round's number of scheduled
  users (exact: limit 0).
* ``selection_gap``: the number of users scheduled a different number of
  times over the compared rounds (exact: limit 0).
* ``update_gap``: the worst leaf's gap between the norms of the model's
  change over the compared rounds, | ||p - p0|| - ||r - p0|| |, over the
  reference's norm of that leaf or of the median leaf, whichever is
  larger.  Leaves the reference leaves unmoved (norm under a thousandth of
  the median leaf's) are left out.
* ``update_dir_gap``: how far the program's model lies from the
  reference's, over the reference's change, for the whole model:
  ||p - r|| / ||r - p0|| over all leaves together.  Unlike the gap of
  norms it sees a change in the wrong direction, such as an Eq. (2) mean
  over half of the scheduled clients.

A hierarchical world has two models, the global one and the edge models
stacked by BS; each is compared on its own (the median leaf is its own),
and a reading is the worse of the two.

Read by ``bench/calibrate.py`` only, beside those (``extra``):
``update_dir_gap_leaf`` (the same distance by the worst moved leaf, on
``update_gap``'s scale), ``update_gap_median`` (the median leaf's gap of
norms), ``acc_gap`` (the largest gap of a round's test accuracy) and, in
a hierarchical world, ``update_gap_global`` and ``update_dir_gap_global``
(the global model's alone).
"""
from __future__ import annotations

import jax
import numpy as np


def host_tree(tree) -> list:
    """Leaves of a model pytree as float64 host arrays, in tree order."""
    return [np.asarray(a, np.float64) for a in jax.tree.leaves(tree)]


def leaf_gaps(got: list, ref: list, base: list) -> np.ndarray:
    """Each moved leaf's gap of change norms, on the scale of the larger of
    its own and the median leaf's reference change."""
    d_got = np.array([np.linalg.norm(g - b) for g, b in zip(got, base)])
    d_ref = np.array([np.linalg.norm(r - b) for r, b in zip(ref, base)])
    med = float(np.median(d_ref))
    moved = d_ref >= 1e-3 * med
    scale = np.maximum(d_ref, med)
    return (np.abs(d_got - d_ref) / scale)[moved]


def dir_gaps(got: list, ref: list, base: list) -> tuple[float, np.ndarray]:
    """The whole model's distance from the reference over the reference's
    change, and each moved leaf's distance on ``leaf_gaps``'s scale."""
    dist = np.array([np.linalg.norm(g - r) for g, r in zip(got, ref)])
    d_ref = np.array([np.linalg.norm(r - b) for r, b in zip(ref, base)])
    med = float(np.median(d_ref))
    moved = d_ref >= 1e-3 * med
    whole = float(np.sqrt(np.sum(dist ** 2)) / np.sqrt(np.sum(d_ref ** 2)))
    return whole, (dist / np.maximum(d_ref, med))[moved]


def schedule_gaps(got: list, ref: list) -> dict:
    return {
        "t_round_gap": max(abs(g["t_round"] - r["t_round"]) / r["t_round"]
                           for g, r in zip(got, ref)),
        "n_selected_gap": float(max(abs(g["n_selected"] - r["n_selected"])
                                    for g, r in zip(got, ref))),
    }


def models(out: dict) -> dict:
    """An FL run's records, schedule counts and models as host leaves: the
    global model and, in a hierarchical world, the edge models."""
    edges = out.get("edge_params")
    return {"records": out["records"], "counts": np.asarray(out["counts"]),
            "params": host_tree(out["params"]),
            "edge_params": None if edges is None else host_tree(edges)}


def fl_readings(first: dict, ref: dict, world: dict,
                extra: bool = False) -> dict:
    """``first``: the program's first records and models (:func:`models`);
    ``ref``: :func:`bench.reference.fl.follow`'s output."""
    base = host_tree(ref["params0"])
    pairs = [(first["params"], host_tree(ref["params"]), base)]
    if world["aggregation"] == "hierarchical":
        edges = host_tree(ref["edge_params"])
        # edge leaves are [M, ...]: their base is the shared start
        pairs.append((first["edge_params"], edges,
                      [np.broadcast_to(b[None], r.shape)
                       for b, r in zip(base, edges)]))
    gaps = [leaf_gaps(*p) for p in pairs]
    dirs = [dir_gaps(*p) for p in pairs]
    out = schedule_gaps(first["records"], ref["records"])
    out["update_gap"] = max(float(np.max(g)) for g in gaps)
    out["update_dir_gap"] = max(whole for whole, _ in dirs)
    out["selection_gap"] = float(np.sum(
        np.asarray(first["counts"]) != np.asarray(ref["counts"])))
    if extra:
        out["update_dir_gap_leaf"] = max(float(np.max(leaf))
                                         for _, leaf in dirs)
        out["update_gap_median"] = float(np.median(np.concatenate(gaps)))
        out["acc_gap"] = max(abs(g["test_acc"] - r["test_acc"]) for g, r in
                             zip(first["records"], ref["records"]))
        if len(pairs) > 1:
            out["update_gap_global"] = float(np.max(gaps[0]))
            out["update_dir_gap_global"] = dirs[0][0]
    return out
