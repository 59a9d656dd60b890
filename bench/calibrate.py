#!/usr/bin/env python3
"""Readings that set a cell's correctness limits (not part of a run).

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 --control-seeds 4,5,6

For each of ``--seeds`` it prints the readings of sound runs of the
program against the reference, whose largest is a limit's lower end; for
each of ``--control-seeds``, the readings of the cell's control and
planted faults in the program's place.  What those are is the cell's
entry adapter's to say (its ``sound`` and ``controls``).  One JSON line
per reading; ``--out`` also writes them to a file.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))

    from bench import harness
    harness.use_compile_cache()
    cell = harness.find_cell(args.workload)
    entry = harness.load_module("entries", cell.traffic["entry"])
    out = open(args.out, "a") if args.out else None

    def emit(kind, label, readings, t0):
        line = json.dumps({"cell": cell.name, "kind": kind, "seed": label,
                           "readings": readings,
                           "seconds": time.perf_counter() - t0})
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    def seeds(s):
        return [int(x) for x in s.split(",") if x]

    for seed in seeds(args.seeds):
        t0 = time.perf_counter()
        for label, readings in entry.sound(cell.config, cell.traffic, seed):
            emit("sound", label, readings, t0)
    for seed in seeds(args.control_seeds):
        t0 = time.perf_counter()
        for kind, label, readings in entry.controls(cell.config,
                                                    cell.traffic, seed):
            emit(kind, label, readings, t0)


if __name__ == "__main__":
    main()
