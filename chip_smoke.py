#!/usr/bin/env python3
"""Smoke run of the two main paths on a TPU chip — a smoke, not a benchmark.

    python chip_smoke.py               # phases A and B on one chip
    python chip_smoke.py --four-chips  # sharded sweeps vs unsharded, 4 chips

Phase A (learning, paper width): ``FLSimulation`` with the paper-scale CNN
(16/32/64) in the paper-default world (N=50 users, M=8 BSs), 5 fused
rounds with the Pallas FedAvg reduction, then the same seed on the jnp
reduction.  The fused scan must lower the ``fedavg_reduce`` kernel to a
``tpu_custom_call``; ``t_round`` and ``n_selected`` must match exactly and
``test_acc`` within ``ACC_TOL``.

Phase B (control plane, a million users): the ``mega-fleet`` wireless
sweep at N=1e6, M=100, bf16 channel planes, 8192-user chunks and
rho2=3e-4 (about 300 users picked per round) on the Pallas route and on
the chunked jnp route; n_selected must match and per-round latency agree
to rtol 1e-5.  One round's decision on the same fleet goes through
``schedule_batch("dagsa_jit")`` on both routes: the selected sets must be
identical, and the Pallas decision must lower ``select_topk`` and
``bandwidth_solve`` to ``tpu_custom_call``s.

``--four-chips`` runs only ``run_shard_sweep`` and
``run_shard_learning_sweep`` over a 4-device mesh and their unsharded
twins on ``jax.devices()[0]``, and prints "byte-identical" or the largest
difference.

The script exits non-zero, printing no result line, when JAX finds no TPU
or when any check fails.  Its last line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import base64
import dataclasses
import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "src")

ACC_TOL = 0.01          # 10 of the 1000 test images may flip
T_RTOL = 1e-5
SWEEP = dict(n_users=1_000_000, n_bs=100, rho1=0.0, rho2=3e-4)
USER_CHUNK = 8192


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def check(cond: bool, msg: str) -> None:
    if not cond:
        fail(msg)


def tpu_kernels(lowered) -> list[str]:
    """Pallas kernel names in the ``tpu_custom_call``s of a lowered program.

    The kernel's Mosaic module travels base64-encoded in the custom call's
    backend config; its symbol table carries the kernel function's name.
    """
    names = []
    for body in re.findall(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22',
                           lowered.as_text()):
        names += [m.decode() for m in
                  re.findall(rb"_\w*kernel", base64.b64decode(body))]
    return sorted(set(names))


def report(phase: str, dev, **fields) -> None:
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    body = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[smoke, not a benchmark] phase {phase} on {dev.platform} "
          f"{dev.device_kind}: {body} process_peak_bytes={peak}", flush=True)


# ----------------------------------------------------------------- phase A --
def phase_a(dev) -> None:
    import numpy as np
    from repro.fl import FLConfig, FLSimulation
    from repro.models.cnn import CNNConfig

    def sim(backend):
        return FLSimulation(FLConfig(
            scheduler="dagsa_jit", cnn=CNNConfig.paper_scale(),
            dataset="mnist", n_train=4000, n_test=1000, local_epochs=10,
            batch_size=16, fedavg_backend=backend))

    rounds = 5
    pallas = sim("pallas")
    kernels = tpu_kernels(pallas._scan_jit.lower(
        pallas._carry(), pallas.round_idx, n_rounds=rounds))
    check("_fedavg_kernel" in kernels,
          f"phase A: fused step lowered no fedavg_reduce kernel: {kernels}")
    got = pallas.run(rounds, mode="fused")
    want = sim("jax").run(rounds, mode="fused")
    for g, w in zip(got, want):
        check(g.t_round == w.t_round and g.n_selected == w.n_selected,
              f"phase A round {g.round_idx}: pallas (t={g.t_round}, "
              f"n={g.n_selected}) != jnp (t={w.t_round}, n={w.n_selected})")
    acc_diff = max(abs(g.test_acc - w.test_acc) for g, w in zip(got, want))
    check(np.isfinite([g.test_acc for g in got]).all(),
          "phase A: non-finite accuracy")
    check(acc_diff <= ACC_TOL,
          f"phase A: |test_acc pallas - jnp| = {acc_diff} > {ACC_TOL}")
    report("A", dev, kernels=",".join(kernels),
           final_acc=got[-1].test_acc, max_acc_diff=acc_diff)


# ----------------------------------------------------------------- phase B --
def _fleet_problem(cfg):
    """One mega-fleet round's problem with bf16 channel planes."""
    import jax
    import jax.numpy as jnp
    from repro.core import channel
    from repro.core.dagsa_jit import stack_problems
    from repro.core.types import MobilityState

    k_pos, k_bs, k_prob = jax.random.split(jax.random.PRNGKey(1), 3)
    state = MobilityState(
        user_pos=jax.random.uniform(k_pos, (cfg.n_users, 2)) * cfg.area_m,
        bs_pos=jax.random.uniform(k_bs, (cfg.n_bs, 2)) * cfg.area_m)
    prob = channel.make_problem(k_prob, state, cfg,
                                jnp.zeros((cfg.n_users,)), 0)
    prob = dataclasses.replace(prob, snr=prob.snr.astype(jnp.bfloat16),
                               coeff=prob.coeff.astype(jnp.bfloat16))
    return stack_problems([prob])


def phase_b(dev) -> None:
    import jax
    import numpy as np
    from repro.core import dagsa_jit
    from repro.core.scheduler import schedule_batch
    from repro.core.types import WirelessConfig
    from repro.launch.sweep import run_sweep

    cfg = WirelessConfig(**SWEEP)
    rounds = 2

    def sweep(backend):
        return run_sweep(["mega-fleet"], n_seeds=1, n_rounds=rounds, cfg=cfg,
                         backend=backend, user_chunk=USER_CHUNK,
                         channel_dtype="bf16")

    got = sweep("pallas")[0]
    want = sweep("jax")[0]
    n_got, n_want = got["curves"]["n_selected"], want["curves"]["n_selected"]
    check(n_got == n_want, f"phase B sweep: n_selected pallas {n_got} != "
                           f"jnp {n_want}")
    t_got = np.asarray(got["curves"]["t_round_s"])
    t_want = np.asarray(want["curves"]["t_round_s"])
    check(np.allclose(t_got, t_want, rtol=T_RTOL, atol=0.0),
          f"phase B sweep: t_round pallas {t_got} vs jnp {t_want}")

    # one decision on both routes: identical selected sets, kernels lowered
    fleet = _fleet_problem(cfg)
    keys = jax.random.split(jax.random.PRNGKey(2), 1)

    def decide(backend):
        return schedule_batch("dagsa_jit", fleet, keys, backend=backend,
                              selection_block=USER_CHUNK)

    kernels = tpu_kernels(dagsa_jit._schedule_batch.lower(
        fleet.snr, fleet.coeff, fleet.tcomp, fleet.bs_bw, fleet.necessary,
        int(fleet.min_participants), keys, backend="pallas",
        selection_block=USER_CHUNK))
    for name in ("_select_kernel", "_rowmax_kernel", "_bw_kernel"):
        check(name in kernels, f"phase B: decision lowered no {name}: "
                               f"{kernels}")
    d_got, d_want = decide("pallas"), decide("jax")
    sel_got = np.asarray(d_got.selected)
    check(np.array_equal(sel_got, np.asarray(d_want.selected)),
          "phase B decision: selected sets differ between routes")
    check(np.array_equal(np.asarray(d_got.assign), np.asarray(d_want.assign)),
          "phase B decision: user->BS assignment differs between routes")
    check(np.allclose(np.asarray(d_got.t_round), np.asarray(d_want.t_round),
                      rtol=T_RTOL, atol=0.0),
          f"phase B decision: t_round {d_got.t_round} vs {d_want.t_round}")
    report("B", dev, kernels=",".join(kernels),
           n_selected=n_got, t_round_s=t_got.tolist(),
           decision_selected=int(sel_got.sum()))


# ------------------------------------------------------------- four chips --
def _max_diff(a, b) -> float | None:
    """Largest |a - b| over matching numeric leaves; None if the record
    structures differ."""
    if isinstance(a, dict) and isinstance(b, dict):
        if a.keys() != b.keys():
            return None
        diffs = [_max_diff(a[k], b[k]) for k in a]
    elif isinstance(a, list) and isinstance(b, list):
        if len(a) != len(b):
            return None
        diffs = [_max_diff(x, y) for x, y in zip(a, b)]
    elif isinstance(a, (int, float)) and isinstance(b, (int, float)):
        return abs(float(a) - float(b))
    else:
        return 0.0 if a == b else None
    return None if None in diffs else max(diffs, default=0.0)


def four_chips() -> None:
    import jax
    from repro.launch import shard_sweep, sweep

    devs = jax.devices()
    check(len(devs) >= 4, f"--four-chips needs 4 devices, have {len(devs)}")
    scenarios = ["paper-default", "high-mobility"]
    runs = {
        "wireless": (
            lambda: shard_sweep.run_shard_sweep(scenarios, n_seeds=8,
                                                n_rounds=3, n_devices=4),
            lambda: sweep.run_sweep(scenarios, n_seeds=8, n_rounds=3)),
        "learning": (
            lambda: shard_sweep.run_shard_learning_sweep(
                scenarios, n_seeds=4, n_rounds=2, n_devices=4),
            lambda: sweep.run_learning_sweep(scenarios, n_seeds=4,
                                             n_rounds=2)),
    }
    for name, (sharded_fn, plain_fn) in runs.items():
        sharded = sharded_fn()
        stats = [d.memory_stats() for d in devs[:4]]
        peaks = [st and st.get("peak_bytes_in_use") for st in stats]
        check(None in peaks or all(peaks),
              f"{name}: a mesh device held no data: {peaks}")
        with jax.default_device(devs[0]):
            plain = plain_fn()
        same = (json.dumps(sharded, sort_keys=True)
                == json.dumps(plain, sort_keys=True))
        diff = None if same else _max_diff(sharded, plain)
        verdict = ("byte-identical" if same else
                   "record structure differs" if diff is None else
                   f"largest difference {diff!r}")
        print(f"[smoke, not a benchmark] four-chips {name} on "
              f"{devs[0].platform} {devs[0].device_kind}: sharded over 4 vs "
              f"unsharded on device 0: {verdict}; "
              f"device_peak_bytes={peaks}", flush=True)
        check(same, f"four-chips {name}: sharded != unsharded ({verdict})")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded sweeps and their unsharded "
                         "twins over four chips")
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(SRC, "repro")):
        fail(f"no repro package under {SRC}: run from a checkout")
    sys.path.insert(0, SRC)
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"JAX found no TPU (default device is {dev.platform}); "
             f"this smoke never falls back to another backend")
    from repro.launch.cache import use_compile_cache
    print(f"compile cache: {use_compile_cache()}", flush=True)
    if args.four_chips:
        four_chips()
    else:
        phase_a(dev)
        phase_b(dev)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
