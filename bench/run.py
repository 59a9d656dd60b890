#!/usr/bin/env python3
"""Chip benchmark of the FL round engine and the wireless control plane.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell is a ``workloads`` entry of
``BENCHMARK.json``; everything it needs is found by name under ``bench/``
(see ``bench/harness.py``).  The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer ones), ``device``
and, last, ``checks``: each number compared with its limit.  The same
numbers close standard error.

The run exits non-zero and prints no result when JAX finds no TPU, fewer
chips than the cell asks for, or no program beside the benchmark.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def fail(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    sys.exit(2)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    from bench import harness
    try:
        cell = harness.find_cell(args.workload)
    except (KeyError, FileNotFoundError) as e:
        fail(str(e))
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        fail(f"no program under {src}")
    sys.path.insert(0, src)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"JAX found no TPU (default device is {devices[0].platform}); "
             f"the benchmark never falls back to another backend")
    if len(devices) < cell.chips:
        fail(f"the cell asks for {cell.chips} chips, JAX found "
             f"{len(devices)}")
    result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                         T_START)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
