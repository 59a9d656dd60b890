"""Entry: one control-plane decision of the wireless sweep,
``repro.launch.sweep.run_sweep([scenario], n_seeds=1, n_rounds=1, ...)``.

A call makes one whole decision on each world of the traffic's
``world_seeds``: mobility, the channel planes, DAGSA selection and the
Eq. (11) split, as one compiled program whose records come back to the
host.  How much work a decision is depends on its world (how many users
the greedy adds), so every run decides the same worlds; the run's seed
sets their order and which decision the reference remakes.  Route
options (``backend``, ``user_chunk``, ``channel_dtype``) are passed while
``run_sweep`` still takes them, and dropped, with a line on stderr, once
it does not.
"""
from __future__ import annotations

import dataclasses
import inspect
import json
import math
import sys

import numpy as np

from bench.harness import free_device
from bench.lowering import Recorder


def build(config: dict, traffic: dict, seed: int):
    return Decisions(config, traffic, seed)


def tiny(cell):
    """The cell cut for a CPU rehearsal: 20,000 users picking about 60, on
    two of the cell's worlds, through the chunked jnp route, whose
    decisions equal the Pallas route's (Pallas interpret mode is slow on
    the CPU)."""
    cell.config.update(n_users=20000, rho2=3e-3)
    cell.traffic["options"]["backend"] = "jax"
    cell.traffic["world_seeds"] = cell.traffic["world_seeds"][:2]
    return cell


def rehearsal_counters() -> dict:
    """``counters()`` of one call at the cell's size: four decisions of
    about 300 picks, with the greedy steps of the cell's four worlds
    (PERF.md), none of them dense."""
    return {"decisions": 4, "n_selected": [321, 309, 301, 340],
            "greedy_steps": [351, 309, 301, 340], "dense_steps": [0, 0, 0, 0]}


COUNTERS = ("greedy_steps", "dense_steps")    # the program's, per decision


def wireless(config: dict) -> dict:
    keys = ("n_users", "n_bs", "area_m", "noise_dbm_mhz", "tx_dbm_mhz",
            "model_mbit", "bs_bandwidth_mhz", "tcomp_min_s", "tcomp_max_s",
            "speed_mps", "round_duration_s", "rho1", "rho2")
    return {k: config[k] for k in keys}


class Decisions:
    unit = "decisions"

    def __init__(self, config: dict, traffic: dict, seed: int):
        from repro.core.types import WirelessConfig
        from repro.launch.sweep import run_sweep

        self.config, self.traffic, self.seed = config, traffic, seed
        self.world = traffic["world"]
        fields = {f.name for f in dataclasses.fields(WirelessConfig)}
        self.cfg = WirelessConfig(**{k: v for k, v in config.items()
                                     if k in fields})
        takes = set(inspect.signature(run_sweep).parameters)
        self.opts = {}
        for opt, val in traffic.get("options", {}).items():
            if opt in takes:
                self.opts[opt] = val
            else:
                print(f"bench: option {opt}={val!r} dropped: run_sweep no "
                      f"longer takes it", file=sys.stderr, flush=True)
        self.run_sweep = run_sweep
        pool = list(traffic["world_seeds"])
        self.order = [pool[i] for i in
                      np.random.default_rng(seed).permutation(len(pool))]
        self.window = []
        # watch the compiled program that run_sweep calls, so that the
        # program the window ran can be lowered again
        from repro.launch import sweep
        self._bucket = None
        fn = getattr(sweep, "_sweep_bucket", None)
        if isinstance(fn, Recorder):        # an entry never released
            fn = fn.fn
        if callable(fn):
            self._bucket = Recorder(fn)
            sweep._sweep_bucket = self._bucket
        else:
            print("bench: the sweep has no _sweep_bucket: its program "
                  "cannot be lowered again", file=sys.stderr, flush=True)

    def _decide(self, ws: int) -> dict:
        rec = self.run_sweep([self.world["scenario"]], n_seeds=1, n_rounds=1,
                             cfg=self.cfg, seed=ws, **self.opts)[0]
        if rec.get("speed_mps") != self.world["speed_mps"]:
            raise ValueError(f"scenario {self.world['scenario']!r} moves at "
                             f"{rec.get('speed_mps')} m/s, the traffic states "
                             f"{self.world['speed_mps']}")
        curves = rec["curves"]
        return {"world_seed": ws, "t_round": float(curves["t_round_s"][0]),
                "n_selected": int(curves["n_selected"][0]),
                **{k: int(curves[k][0]) for k in COUNTERS}}

    def warm(self) -> None:
        self.first = self._decide(self.order[0])

    def call(self):
        recs = [self._decide(ws) for ws in self.order]
        self.window += recs
        return len(recs), sum(not math.isfinite(r["t_round"]) for r in recs)

    def counters(self) -> dict:
        """The window's decisions, their picks, their greedy steps and the
        dense ones among them."""
        return {"decisions": len(self.window),
                **{k: [r[k] for r in self.window]
                   for k in ("n_selected",) + COUNTERS}}

    def lowered(self):
        """The decision's program, lowered again from the window's last
        call's own arguments; None where it could not be watched."""
        return self._bucket.lower() if self._bucket is not None else None

    def release(self) -> None:
        if self._bucket is not None:
            from repro.launch import sweep
            sweep._sweep_bucket = self._bucket.fn
            self._bucket = None
        self.run_sweep = None

    def check(self, extra: bool = False) -> dict:
        """The reference remakes a decision of the window drawn from the
        seed."""
        from bench import compare
        from bench.reference.decision import decide
        pick = int(np.random.default_rng(self.seed).integers(len(self.window)))
        got = self.window[pick]
        ref = decide(got["world_seed"], wireless(self.config),
                     self.world["speed_mps"])
        return compare.schedule_gaps([got], [ref])


def _every_world(config: dict, traffic: dict, seed: int) -> tuple:
    """Every world the cell decides, once each, through the program."""
    entry = build(config, traffic, seed)
    entry.warm()
    recs = [entry.first] + [entry._decide(ws) for ws in entry.order[1:]]
    entry.release()
    free_device()
    return entry, recs


def sound(config: dict, traffic: dict, seed: int):
    """Calibration: ``(world seed, readings)`` of a sound decision on each
    world the cell decides."""
    entry, recs = _every_world(config, traffic, seed)
    for rec in recs:
        entry.window = [rec]
        yield rec["world_seed"], entry.check()


def controls(config: dict, traffic: dict, seed: int):
    """Calibration: ``(kind, world seed, readings)`` of the control, the
    program's own int8 channel path (the precision below the
    configuration's bf16 planes), on each world the cell decides."""
    alt = json.loads(json.dumps(traffic))
    alt["options"]["channel_dtype"] = "int8"
    entry, recs = _every_world(config, alt, seed)
    for rec in recs:
        entry.window = [rec]
        yield "control_int8", rec["world_seed"], entry.check()
