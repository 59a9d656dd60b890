"""One benchmark run of one cell, driven by the files the cell names.

A cell (``BENCHMARK.json`` ``workloads``) names a configuration and a
traffic mix.  Everything else is found by name:

* ``bench/traffic/<traffic>.json``: the entry adapter, the world, the
  rounds per call and the route options;
* ``bench/entries/<entry>.py``: ``build(config, traffic, seed)`` returns
  an object with ``warm()``, ``call() -> (units, failed)``,
  ``counters()`` (the program's own counts over the window),
  ``lowered()`` (the window's program lowered again from its last call,
  or None), ``release()`` and ``check() -> {reading: value}``; ``sound``
  and ``controls`` give ``bench/calibrate.py`` its readings; ``tiny(cell)``
  cuts a cell of the entry to a size the CPU rehearses in seconds, and
  ``rehearsal_counters()`` stands in for ``counters()`` where the
  rehearsal reads every metric without a run;
* ``bench/limits/<cell>.json``: the limit of each reading;
* ``bench/metrics/<metric>.py``: ``read(ctx) -> float | None`` for every
  metric of ``BENCHMARK.json``;
* an optional ``spans`` list in the configuration or traffic file: scopes
  of the cell's program that the span vocabulary of ``bench/spans.py``
  lacks.

A run: build the cell's world from the seed, warm its shapes (set-up),
call the entry back to back for at least ``seconds`` (or, traced, for the
traffic's ``trace_calls``), read the device's peak memory, free the
program, run the reference and compare.

A traced run also reduces the trace by span (``bench.spans.reduce``, the
reduction ``python3 -m bench.spans`` prints), so that readers get device
seconds by span.  It fails, with no result, where the yardstick cannot see
what it reads: a kernel that the timed program lowers has no call in the
trace, or a metric the cell lists reads nothing.  A kernel's roofline may
read nothing only where the timed program lowers none of its kernels.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import os
import re
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_traces"


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str, root: Path = ROOT):
    path = root / "bench" / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"no {kind} named {name!r} ({path})")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """A workload of ``BENCHMARK.json`` with the files it names."""

    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    e2e: list = field(default_factory=list)
    per_layer: list = field(default_factory=list)
    root: Path = ROOT           # the checkout whose files the cell names


def _reports(metric: dict, cell: str, e2e_names: set) -> bool:
    """A metric's cells: those it lists; else, for a per-layer metric,
    every cell that reports the end-to-end metric it moves; else all."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in e2e_names


def find_cell(name: str, root: Path = ROOT) -> Cell:
    spec = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = load_json(root / configs[w["config"]]["file"])
    traffic = load_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    limits = load_json(root / "bench" / "limits" / f"{name}.json")
    e2e = [m for m in spec["end_to_end"] if _reports(m, name, set())]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if _reports(m, name, e2e_names)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, limits=limits, e2e=e2e,
                per_layer=per_layer, root=root)


def free_device() -> None:
    """Let go of what the program held on the device: its arrays and its
    compiled programs."""
    import jax
    gc.collect()
    jax.clear_caches()


def use_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    or where ``JAX_COMPILATION_CACHE_DIR`` says."""
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


def peak_bytes(devices) -> int | None:
    """The most HBM one of ``devices`` held: its allocator's peak bytes in
    use plus its peak bytes reserved.  The TPU runtime reserves each loaded
    program's temporaries apart from the allocations it counts in use, so
    the bytes in use alone leave out a program's working set."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"])
                         + int(stats.get("peak_bytes_reserved", 0)))
    return max(peaks) if peaks else None


def device_peaks(kind: str) -> dict:
    """The peak rates of a device kind; a kind not in the table is an
    error, never a default."""
    table = load_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       f"bench/peaks.json")
    return table[kind]


@dataclass
class Context:
    """What a metric reader may read."""

    cell: Cell
    setup_s: float
    window_s: float
    units: int
    peak_bytes: int | None
    counters: dict
    peaks: dict
    trace: object = None          # bench.trace.Summary in a traced run
    kernel_calls: dict = field(default_factory=dict)
    spans: object = None          # bench.spans.Spans in a traced run

    @property
    def span_seconds(self) -> dict | None:
        """Device seconds by span over the traced window, in the cell's
        vocabulary (``python3 -m bench.spans --workload <cell>``)."""
        return None if self.spans is None else self.spans.device_s

    def span_total(self, names) -> float | None:
        """Device seconds under the spans ``names`` in the traced window.
        None where the run was not traced or its programs hold no span of
        the vocabulary at all.  A program that holds spans but not each of
        ``names``, or operations whose program the trace does not hold,
        are errors: time would read as none."""
        s = self.spans
        if s is None or not s.present:
            return None
        if s.unresolved_s > 0:
            raise RuntimeError(
                f"{s.unresolved_s!r} device seconds of {self.cell.name}'s "
                f"trace ran in no program it holds")
        missing = [n for n in names if n not in s.present]
        if missing:
            raise RuntimeError(
                f"the traced programs of {self.cell.name} hold no span "
                f"{missing} (they hold {sorted(s.present)})")
        return sum(s.device_s.get(n, 0.0) for n in names)


class GcPauses:
    """The garbage collector's pauses of 50 ms or more, by phase of the
    run, so that a call the collector stalled shows in the log."""

    def __init__(self):
        self.phase, self.pauses, self._t0 = "set-up", [], None
        gc.callbacks.append(self._on)

    def _on(self, stage: str, info: dict) -> None:
        if stage == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            secs = time.perf_counter() - self._t0
            if secs >= 0.05:
                self.pauses.append((self.phase, info["generation"], secs))

    def close(self) -> list:
        gc.callbacks.remove(self._on)
        return self.pauses


def unseen_kernels(kernels, kernel_calls: dict, summary) -> list:
    """The lowered kernels none of whose calls the trace shows."""
    return [k for k in kernels if not any(
        summary.call_counts.get(inst, 0)
        for inst, c in kernel_calls.items()
        if k in c.get("kernels", [c["kernel"]]))]


def reduce_trace(xplane: str, cell: Cell, kernels, kernel_calls: dict,
                 window_s: float | None):
    """A traced window's ``bench.trace.Summary``, and its device seconds by
    span of the cell's vocabulary as ``python3 -m bench.spans --workload
    <cell>`` prints them (``bench.spans.Spans``)."""
    from bench import spans
    from bench import trace as tr
    summary = tr.reduce(xplane, kernels, kernel_calls, window_s)
    return summary, spans.reduce(xplane, spans.vocabulary(cell.config,
                                                          cell.traffic))


def read_metrics(specs: list, ctx: Context, kernels) -> dict:
    """Every listed metric's reading.  One that reads nothing fails the
    run, unless it is a kernel's roofline (its reader names ``KERNELS``)
    and the timed program, as lowered, holds none of its kernels."""
    metrics = {}
    for m in specs:
        reader = load_module("metrics", m["name"], ctx.cell.root)
        value = reader.read(ctx)
        own = getattr(reader, "KERNELS", None)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        elif own is not None and kernels is not None \
                and set(own).isdisjoint(kernels):
            log(f"{m['name']}: the timed program lowers none of {own}, "
                f"so it reads nothing")
        else:
            raise RuntimeError(f"metric {m['name']} read nothing in "
                               f"{ctx.cell.name}")
    return metrics


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        t_start: float) -> dict:
    """One run; returns the result dict (the caller prints it)."""
    import jax

    from bench.lowering import CompileClock, footprint, tpu_kernels

    devices = jax.devices()
    log(f"compile cache: {use_compile_cache()}")
    clock = CompileClock()
    pauses = GcPauses()
    entry = load_module("entries", cell.traffic["entry"], cell.root).build(
        cell.config, cell.traffic, seed)
    entry.warm()
    setup_s = time.perf_counter() - t_start
    setup_compile_s, _ = clock.lap()
    log(f"set-up {setup_s:.3f} s, of which compiling {setup_compile_s:.3f} s")

    tdir = None
    pauses.phase = "window"
    if trace:
        tdir = TRACE_DIR / f"{cell.name}-{seed}"
        if tdir.exists():
            import shutil
            shutil.rmtree(tdir)
        jax.profiler.start_trace(str(tdir))
    units = failed = 0
    ends = []
    t0 = time.perf_counter()
    try:
        while True:
            u, bad = entry.call()
            units += u
            failed += bad
            ends.append(time.perf_counter() - t0)
            if trace and len(ends) >= int(cell.traffic.get("trace_calls", 1)):
                break
            if not trace and ends[-1] >= seconds:
                break
    finally:
        if trace:
            jax.profiler.stop_trace()
    window_s = ends[-1]
    pauses.phase = "after"
    _, window_compiles = clock.lap()
    peak = peak_bytes(devices[:cell.chips])
    counters = entry.counters()
    log(f"window {window_s:.3f} s, {units} {entry.unit}, {len(ends)} calls, "
        f"{window_compiles} compile events")
    log("call seconds: " + " ".join(
        f"{b - a:.4f}" for a, b in zip([0.0] + ends, ends)))
    log("collector pauses of 50 ms or more (phase, generation, s): "
        f"{[p for p in pauses.pauses if p[0] != 'after']}")

    low = entry.lowered()
    kernels = tpu_kernels(low) if low is not None else None
    log(f"kernels lowered by the timed program: {kernels}")
    compiled = low.compile() if low is not None else None
    program_bytes = footprint(compiled) if compiled is not None else None
    log(f"device peak in use and reserved {peak} B; the timed program's "
        f"compiled footprint {program_bytes} B")
    summary, kernel_calls, by_span = None, {}, None
    if trace:
        from bench import counting
        from bench import trace as tr
        if compiled is None:
            raise RuntimeError("the timed program cannot be lowered again, "
                               "so its kernels cannot be read in the trace")
        kernel_calls = counting.kernel_calls(compiled.as_text(), kernels)
        log(f"kernel calls of the compiled program: "
            f"{ {k: c['kernels'] for k, c in kernel_calls.items()} }")
        summary, by_span = reduce_trace(tr.find_xplane(tdir), cell, kernels,
                                        kernel_calls, window_s)
        log(f"device seconds by span {by_span.device_s}; spans in the "
            f"traced programs {sorted(by_span.present)}; of operations no "
            f"traced program names {by_span.unresolved_s!r}")
        unseen = unseen_kernels(kernels, kernel_calls, summary)
        if unseen:
            mine = sorted(i for i, c in kernel_calls.items()
                          if set(unseen) & set(c.get("kernels", [])))
            bases = {re.sub(r"\.\d+$", "", i) for i in mine}
            near = sorted(i for i in summary.op_seconds
                          if re.sub(r"\.\d+$", "", i) in bases)
            raise RuntimeError(f"kernels {unseen} of the timed program have "
                               f"no call in the trace (compiled calls "
                               f"{mine}, traced ops of those names {near})")
    entry.release()
    compiled = low = None
    pauses.close()
    free_device()
    readings = entry.check()

    checks = {}
    correct = failed == 0 and window_compiles == 0
    for name, value in readings.items():
        limit = cell.limits[name]
        ok = math.isfinite(value) and value <= limit
        correct = correct and ok
        checks[name] = {"value": value, "limit": limit}
    checks["compiles_in_window"] = {"value": window_compiles, "limit": 0}
    checks["failed_units"] = {"value": failed, "limit": 0}

    dev = devices[0]
    ctx = Context(cell=cell, setup_s=setup_s, window_s=window_s,
                  units=units, peak_bytes=peak, counters=counters,
                  peaks=device_peaks(dev.device_kind) if trace else {},
                  trace=summary, kernel_calls=kernel_calls, spans=by_span)
    metrics = read_metrics(cell.per_layer if trace else cell.e2e, ctx,
                           kernels)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": units, "failed": failed,
              "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = summary.breakdown()
    result["checks"] = checks
    return result
