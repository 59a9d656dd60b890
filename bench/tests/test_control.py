"""Each cell's control, at a size the CPU holds (the chip readings at the
cells' own sizes, against which the limits were set, are in PERF.md).

* The control-plane cell: the program's own int8 channel path, the
  precision below the configuration's bf16 planes, comes out not correct
  against the cell's committed limits.
* Learning cells: the reference computed in bfloat16, the precision below
  the configuration's float32, put in the program's place.  On the chip
  at the cell's size it reads ``update_gap`` 0.83-0.95 against the limit
  0.004 and ``update_dir_gap`` 0.12-0.17 against 0.02.  XLA's CPU backend
  keeps more precision in bfloat16 arithmetic, so here it reads a few
  hundredths; the test holds it to what the limits rest on: the control
  reads far above the program.
"""
import json

import jax.numpy as jnp
import pytest

from bench import compare, harness
from bench.reference import fl as ref_fl
from bench.tests.conftest import run_tiny, tiny_cell


def _fails(readings: dict, limits: dict) -> list:
    return [n for n, v in readings.items() if not v <= limits[n]]


@pytest.mark.parametrize("name", ["paper-cnn.sync",
                                  "paper-cnn.hfl-high-mobility"])
def test_bf16_reference_in_the_programs_place_reads_far_above_the_program(
        name):
    cell = harness.find_cell(name)
    cell.config.update(n_train=1600, n_test=200, local_epochs=2,
                       batch_size=4)
    program = run_tiny(cell, seed=11)["checks"]
    entry = harness.load_module("entries", "fl_fused")
    cfg = entry.reference_config(cell.config)
    world = cell.traffic["world"]
    n = entry.compared_rounds(cell.traffic)
    ref = ref_fl.follow(11, cfg, world, n)
    alt = ref_fl.follow(11, cfg, world, n, dtype=jnp.bfloat16)
    readings = compare.fl_readings(compare.models(alt), ref, world)
    assert readings["update_gap"] > 10 * program["update_gap"]["value"], \
        (readings, program)


def test_int8_channel_path_fails():
    """At 20,000 users a decision picks about 60, and int8 codes move
    fewer of them than at the cell's million (where every one of the 4
    worlds fails, PERF.md): here at least one of the cell's worlds does."""
    from bench.reference.decision import decide
    cell = tiny_cell("mega-fleet.dagsa")
    traffic = json.loads(json.dumps(cell.traffic))
    traffic["options"]["channel_dtype"] = "int8"
    entry = harness.load_module("entries", "sweep_decision")
    program = entry.build(cell.config, traffic, 13)
    readings = [compare.schedule_gaps(
        [program._decide(ws)],
        [decide(ws, entry.wireless(cell.config),
                traffic["world"]["speed_mps"])])
        for ws in traffic["world_seeds"]]
    program.release()
    assert any(_fails(r, cell.limits) for r in readings), readings
