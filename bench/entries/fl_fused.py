"""Entry: the FL engine's fused round scan, ``FLSimulation.run(mode="fused")``.

Each call runs the traffic's ``rounds_per_call`` rounds as one compiled
``lax.scan`` of ``make_round_step`` and brings the per-round records to
the host.  Set-up builds one simulation from the seed and runs its first
calls, the first of which compiles; those first rounds are what the
reference follows.  They reach the first global sync of a hierarchical
world (:func:`compared_rounds`), so that the comparison covers it.
Route options (``fedavg_backend``) are passed while ``FLConfig`` still has
the field, and dropped, with a line on stderr, once it has not.
"""
from __future__ import annotations

import dataclasses
import math
import sys

from bench import compare
from bench.harness import free_device
from bench.lowering import Recorder


def build(config: dict, traffic: dict, seed: int):
    return FusedRounds(config, traffic, seed)


def tiny(cell):
    """The cell cut for a CPU rehearsal: 800 training images and 200 test
    images, one local epoch at batch 4."""
    cell.config.update(n_train=800, n_test=200, local_epochs=1,
                       batch_size=4)
    return cell


def rehearsal_counters() -> dict:
    """``counters()`` of three rounds at the cells' size: all 50 clients
    trained, as ``compute="full"`` does, and about half of them kept."""
    return {"rounds": 3, "n_selected": [50, 25, 26],
            "n_trained": [50, 50, 50]}


def compared_rounds(traffic: dict) -> int:
    """The set-up's rounds that the reference follows: whole calls, as
    many as reach round ``tau_global``, where a hierarchical world first
    syncs its edges into the global model (one call where every round
    syncs)."""
    k = int(traffic["rounds_per_call"])
    return k * -(-int(traffic["world"]["tau_global"]) // k)


def program_seed(seed: int) -> int:
    """The program's PRNG takes 32 bits; larger seeds wrap."""
    return seed % 2**32


def reference_config(config: dict) -> dict:
    """The configuration in the groups the reference reads."""
    wireless_keys = ("n_users", "n_bs", "area_m", "noise_dbm_mhz",
                     "tx_dbm_mhz", "model_mbit", "bs_bandwidth_mhz",
                     "tcomp_min_s", "tcomp_max_s", "speed_mps",
                     "round_duration_s", "rho1", "rho2")
    return {"wireless": {k: config[k] for k in wireless_keys},
            "fl": {k: config[k] for k in ("n_train", "n_test", "local_epochs",
                                          "batch_size", "lr",
                                          "shards_per_user")},
            "dataset": config["dataset"], "model": config["model"],
            "matmul": config["matmul_precision"]}


class FusedRounds:
    unit = "rounds"

    def __init__(self, config: dict, traffic: dict, seed: int):
        from repro.core.types import WirelessConfig
        from repro.fl import FLConfig, FLSimulation
        from repro.models.cnn import CNNConfig

        self.config, self.traffic, self.seed = config, traffic, seed
        self.k = int(traffic["rounds_per_call"])
        world = traffic["world"]
        w_fields = {f.name for f in dataclasses.fields(WirelessConfig)}
        wireless = WirelessConfig(**{k: v for k, v in config.items()
                                     if k in w_fields})
        m = config["model"]
        kw = dict(dataset=config["dataset"]["name"],
                  scheduler=config["scheduler"], wireless=wireless,
                  local_epochs=config["local_epochs"],
                  batch_size=config["batch_size"], lr=config["lr"],
                  shards_per_user=config["shards_per_user"],
                  eval_every=config["eval_every"], n_train=config["n_train"],
                  n_test=config["n_test"], seed=program_seed(seed),
                  scenario=world["scenario"],
                  cnn=CNNConfig(**{k: m[k] for k in (
                      "height", "width", "channels", "c1", "c2", "hidden",
                      "n_classes")}))
        fields = {f.name for f in dataclasses.fields(FLConfig)}
        for opt, val in traffic.get("options", {}).items():
            if opt in fields:
                kw[opt] = val
            else:
                print(f"bench: option {opt}={val!r} dropped: FLConfig no "
                      f"longer has it", file=sys.stderr, flush=True)
        self.sim = FLSimulation(FLConfig(**kw))
        self._check_world(world)
        self.first = None
        self.window = []
        self._scan = None
        if hasattr(self.sim, "_scan_jit"):     # watch the compiled scan
            self._scan = Recorder(self.sim._scan_jit)
            self.sim._scan_jit = self._scan
        else:
            print("bench: the engine has no _scan_jit: its program cannot "
                  "be lowered again", file=sys.stderr, flush=True)

    def _check_world(self, world: dict) -> None:
        """The scenario the program resolved is the one the traffic states."""
        sim = self.sim
        got = {"speed_mps": sim.wireless.speed_mps,
               "aggregation": getattr(sim, "aggregation", None),
               "tau_global": getattr(sim, "tau_global", None),
               "n_bs": sim.wireless.n_bs}
        want = {"speed_mps": world["speed_mps"],
                "aggregation": world["aggregation"],
                "tau_global": world["tau_global"],
                "n_bs": self.config["n_bs"]}
        if got != want:
            raise ValueError(f"scenario {world['scenario']!r} resolved to "
                             f"{got}, the traffic states {want}")

    def warm(self) -> None:
        recs = []
        for _ in range(compared_rounds(self.traffic) // self.k):
            recs += self.sim.run(self.k, mode="fused")
        hier = self.traffic["world"]["aggregation"] == "hierarchical"
        self.first = compare.models({
            "records": [_rec(r) for r in recs], "params": self.sim.params,
            "edge_params": self.sim.edge_params if hier else None,
            "counts": self.sim.part.counts})

    def call(self):
        recs = self.sim.run(self.k, mode="fused")
        self.window += [_rec(r) for r in recs]
        bad = sum(1 for r in recs
                  if not (math.isfinite(r.t_round) and math.isfinite(r.test_acc)))
        return len(recs), bad

    def counters(self) -> dict:
        return {"rounds": len(self.window),
                "n_selected": [r["n_selected"] for r in self.window],
                "n_trained": [r["n_trained"] for r in self.window]}

    def lowered(self):
        """The window's program, lowered again from its last call's own
        arguments; None where the engine's scan could not be watched."""
        return self._scan.lower() if self._scan is not None else None

    def release(self) -> None:
        self._scan = None
        self.sim = None

    def check(self, extra: bool = False) -> dict:
        from bench.reference import fl as ref_fl
        ref = ref_fl.follow(program_seed(self.seed),
                            reference_config(self.config),
                            self.traffic["world"], len(self.first["records"]))
        return compare.fl_readings(self.first, ref, self.traffic["world"],
                                   extra)


def sound(config: dict, traffic: dict, seed: int):
    """Calibration: ``(seed, readings)`` of a sound run, the set-up call's
    rounds against the reference, with the readings not compared."""
    entry = build(config, traffic, seed)
    entry.warm()
    entry.release()
    free_device()
    yield seed, entry.check(extra=True)


def controls(config: dict, traffic: dict, seed: int):
    """Calibration: ``(kind, seed, readings)`` of the control and the
    planted faults in the program's place.  The control is the reference
    computed in bfloat16, the precision below the configuration's
    float32; the faults are the reference with Eq. (2) over only the
    first half of the scheduled clients, the reference's own records
    with each round's latency off by one part in a thousand (an answer
    altered where it is produced), and, in a hierarchical world, the
    reference with its global sync left out or weighting every edge
    alike.  A state left unchanged reads 1 in ``update_gap`` and
    ``update_dir_gap`` and needs no run.  Beside them,
    ``highest_in_place``: the reference with its products at HIGHEST in
    the place of the one at the configuration's precision, how far that
    step alone moves the readings."""
    import jax.numpy as jnp

    from bench.reference import fl as ref_fl
    cfg = reference_config(config)
    world = traffic["world"]
    n = compared_rounds(traffic)
    s = program_seed(seed)
    ref = ref_fl.follow(s, cfg, world, n)
    kinds = [("highest_in_place", {"matmul": "highest"}),
             ("control_bf16", {"dtype": jnp.bfloat16}),
             ("fault_half_batch", {"half_batch": True})]
    if world["aggregation"] == "hierarchical":
        kinds += [("fault_sync_skipped", {"sync": "skipped"}),
                  ("fault_sync_equal", {"sync": "equal"})]
    for kind, kw in kinds:
        alt = compare.models(ref_fl.follow(s, cfg, world, n, **kw))
        yield kind, seed, compare.fl_readings(alt, ref, world, True)
    first = compare.models(ref)
    first["records"] = [dict(r, t_round=r["t_round"] * 1.001)
                        for r in ref["records"]]
    yield "fault_altered_answer", seed, compare.fl_readings(first, ref,
                                                            world, True)


def _rec(r) -> dict:
    return {"t_round": float(r.t_round), "n_selected": int(r.n_selected),
            "test_acc": float(r.test_acc),
            "n_trained": int(r.n_trained)}

