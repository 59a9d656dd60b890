"""CPU rehearsal of the command: it refuses to report without a TPU, and
with the platform check skipped every entry adapter and metric reader runs
at a tiny size.  So does a cell added as files alone: its own entry,
traffic (with a span of its own), limits and readers beside a copy of the
committed benchmark, which nothing in the test edits."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness, spans
from bench.harness import peak_bytes    # the real one, not the CPU's stand-in
from bench.tests.conftest import run_tiny, tiny_cell

ROOT = harness.ROOT
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
METRICS = [m["name"] for m in SPEC["per_layer"] + SPEC["end_to_end"]]

# A cell that a later PR could add with files alone: a program of its own
# (a jitted step under a scope the base span vocabulary lacks), its entry,
# traffic, limits, and an end-to-end and a per-layer reader.
STUB = "toy.steps"
STUB_METRICS = ["toy_steps_per_s", "toy_layer_s"]
STUB_ENTRY = '''
import numpy as np


def tiny(cell):
    cell.config["n"] = 8
    return cell


def rehearsal_counters():
    return {"steps": 3}


def build(config, traffic, seed):
    return Toy(config["n"], seed)


class Toy:
    unit = "steps"

    def __init__(self, n, seed):
        import jax
        import jax.numpy as jnp

        def step(x):
            with jax.named_scope("toy.layer"):
                return jnp.tanh(x @ x.T / n)
        self.x0 = np.random.default_rng(seed).standard_normal(
            (n, n)).astype(np.float32)
        self.x, self.steps, self.fn = jnp.asarray(self.x0), 0, jax.jit(step)

    def warm(self):
        self.fn(self.x).block_until_ready()

    def call(self):
        self.x = self.fn(self.x).block_until_ready()
        self.steps += 1
        return 1, 0

    def counters(self):
        return {"steps": self.steps}

    def lowered(self):
        return self.fn.lower(self.x)

    def release(self):
        self.fn = None

    def check(self):
        ref = self.x0.astype(np.float64)
        for _ in range(self.steps):
            ref = np.tanh(ref @ ref.T / len(ref))
        return {"x_gap": float(np.max(np.abs(np.asarray(self.x) - ref)))}
'''
STUB_READERS = {
    "toy_steps_per_s": '''
def read(ctx):
    return ctx.units / ctx.window_s if "steps" in ctx.counters else None
''',
    "toy_layer_s": '''
SPANS = ("toy.layer",)


def read(ctx):
    secs = ctx.span_total(SPANS)
    return None if secs is None else secs / ctx.counters["steps"]
'''}


def stub_root(tmp_path) -> Path:
    """A checkout of the committed benchmark with the stub cell added as
    files: nothing that is there is edited but ``BENCHMARK.json``'s lists,
    which gain entries."""
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads(json.dumps(SPEC))
    spec["configs"].append({"name": "toy", "source": "https://example.org",
                            "file": "bench/configs/toy.json", "reduced": [],
                            "why": "a stand-in"})
    spec["workloads"].append({"name": STUB, "config": "toy",
                              "traffic": "toy", "chips": 1,
                              "why": "a stand-in"})
    spec["end_to_end"].append({"name": "toy_steps_per_s", "unit": "1/s",
                               "better": "higher", "bound": 0.01,
                               "source": "host_clock", "workloads": [STUB]})
    spec["per_layer"].append({"name": "toy_layer_s", "unit": "s",
                              "better": "lower", "source": "device_trace",
                              "layer": "toy", "moves": "toy_steps_per_s",
                              "workloads": [STUB]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    b = root / "bench"
    (b / "configs" / "toy.json").write_text(json.dumps({"n": 64}))
    (b / "traffic" / "toy.json").write_text(json.dumps(
        {"entry": "toy", "spans": ["toy.layer"], "trace_calls": 1}))
    (b / "limits" / f"{STUB}.json").write_text(json.dumps({"x_gap": 1e-4}))
    (b / "entries" / "toy.py").write_text(STUB_ENTRY)
    for name, text in STUB_READERS.items():
        (b / "metrics" / f"{name}.py").write_text(text)
    return root


def _command(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "2147483700", "--seconds", "1", "--trace", "0", *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_without_a_tpu():
    p = _command(ROOT)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "no TPU" in p.stderr


def test_refuses_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _command(tmp_path)
    assert p.returncode != 0
    assert p.stdout == ""


@pytest.mark.parametrize("name", CELLS + [STUB])
def test_entry_runs_and_is_correct_at_a_tiny_size(name, tmp_path):
    root = stub_root(tmp_path) if name == STUB else ROOT
    spec = json.loads((root / "BENCHMARK.json").read_text())
    res = run_tiny(tiny_cell(name, root))
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    e2e = {m["name"] for m in spec["end_to_end"]
           if name in m.get("workloads", [name])}
    assert set(res["metrics"]) == e2e
    assert list(res)[-1] == "checks"


def _summary():
    from bench.trace import Summary
    return Summary(busy_s=0.8, window_s=1.0,
                   op_seconds={"fusion.1": 0.5},
                   call_counts={"k.1": 4}, call_seconds={"k.1": 0.2},
                   gaps=[("host", 0.2)])


def rehearsal_context(cell: harness.Cell, kernel: str = "_other_kernel"):
    """A traced run's context without a run: the cell's entry's rehearsal
    counters, a trace summary, one kernel call and a second of device time
    under each span of the cell's vocabulary."""
    entry = harness.load_module("entries", cell.traffic["entry"], cell.root)
    names = spans.vocabulary(cell.config, cell.traffic)
    return harness.Context(
        cell=cell, setup_s=12.0, window_s=6.0, units=3,
        peak_bytes=2_000_000_000, counters=entry.rehearsal_counters(),
        peaks=harness.device_peaks("TPU v5 lite"), trace=_summary(),
        kernel_calls={"k.1": {"kernel": kernel, "bytes": 4e6,
                              "flops": 1e6}},
        spans=spans.Spans(busy_s=1.0, device_s={n: 1.0 for n in names},
                          present=set(names)))


@pytest.mark.parametrize("metric", METRICS + STUB_METRICS)
def test_every_metric_reader_reads(metric, tmp_path):
    root = stub_root(tmp_path) if metric in STUB_METRICS else ROOT
    spec = json.loads((root / "BENCHMARK.json").read_text())
    m = next(m for m in spec["per_layer"] + spec["end_to_end"]
             if m["name"] == metric)
    cells = [w["name"] for w in spec["workloads"]]
    cell = harness.find_cell(m.get("workloads", cells)[0], root)
    reader = harness.load_module("metrics", metric, root)
    ctx = rehearsal_context(cell, getattr(reader, "KERNELS",
                                          ["_other_kernel"])[0])
    value = reader.read(ctx)
    assert value is not None and value > 0
    if m["unit"] == "%":
        assert value <= 100
    if hasattr(reader, "KERNELS"):
        # 4 calls of 4 MB at 819 GB/s over 0.2 s of kernel time
        assert value == pytest.approx(100 * 4 * 4e6 / 819e9 / 0.2)
    ctx.trace = ctx.spans = None
    if m in spec["per_layer"]:
        assert reader.read(ctx) is None


SPAN_READERS = [n for n in METRICS
                if hasattr(harness.load_module("metrics", n), "SPANS")]


@pytest.mark.parametrize("metric", SPAN_READERS)
def test_a_span_reader_raises_where_the_program_lacks_its_spans(metric):
    m = next(m for m in SPEC["per_layer"] if m["name"] == metric)
    reader = harness.load_module("metrics", metric)
    ctx = rehearsal_context(harness.find_cell(m["workloads"][0]))
    # operations of a program the trace does not hold: an error
    ctx.spans.unresolved_s = 1e-6
    with pytest.raises(RuntimeError, match="in no program"):
        reader.read(ctx)
    # a program with spans, none of them the reader's: an error
    ctx.spans.unresolved_s = 0.0
    ctx.spans.present = {"world.init"} - set(reader.SPANS)
    with pytest.raises(RuntimeError, match="hold no span"):
        reader.read(ctx)
    # a program with no span of the vocabulary at all reads nothing
    ctx.spans = spans.Spans(busy_s=1.0, device_s={spans.NO_SPAN: 1.0})
    assert reader.read(ctx) is None


def test_counters_pass_the_programs_counts_through():
    """What the readers divide by is the program's own count."""
    ctx = rehearsal_context(harness.find_cell("mega-fleet.dagsa"))
    ctx.spans.device_s["dagsa.eq11"] = 0.1305
    read = harness.load_module("metrics", "eq11_ms_per_pass.decision").read
    assert read(ctx) == pytest.approx(0.1)     # 1,305 passes
    ctx.counters.pop("greedy_steps")
    with pytest.raises(RuntimeError, match="greedy steps"):
        read(ctx)
    ctx = rehearsal_context(harness.find_cell("paper-cnn.sync"))
    read = harness.load_module("metrics", "useful_sgd_share.learning").read
    assert read(ctx) == pytest.approx(100 * 101 / 150)
    ctx.counters["n_trained"] = [-1, -1, -1]
    with pytest.raises(RuntimeError, match="no clients trained"):
        read(ctx)


def _context(name, trace):
    return harness.Context(
        cell=harness.find_cell(name), setup_s=12.0, window_s=6.0, units=3,
        peak_bytes=2_000_000_000,
        counters={"rounds": 3, "n_selected": [50, 25, 26]},
        peaks=harness.device_peaks("TPU v5 lite"), trace=trace,
        kernel_calls={})


def test_a_lowered_kernel_that_the_trace_never_shows_is_named():
    calls = {"a.1": {"kernel": "_a_kernel"}, "b.1": {"kernel": "_b_kernel"}}
    summary = _summary()
    summary.call_counts = {"a.1": 2}
    assert harness.unseen_kernels(["_a_kernel", "_b_kernel", "_c_kernel"],
                                  calls, summary) == ["_b_kernel",
                                                      "_c_kernel"]
    # a call whose body names two kernels counts as each of them
    calls["a.1"]["kernels"] = ["_a_kernel", "_c_kernel"]
    assert harness.unseen_kernels(["_a_kernel", "_b_kernel", "_c_kernel"],
                                  calls, summary) == ["_b_kernel"]


def test_a_listed_metric_that_reads_nothing_fails_the_run():
    specs = [m for m in SPEC["per_layer"] if m["name"] == "mfu.learning"]
    with pytest.raises(RuntimeError, match="mfu.learning"):
        harness.read_metrics(specs, _context("paper-cnn.sync", None), [])


def test_a_roofline_reads_nothing_only_where_its_kernel_is_not_lowered():
    specs = [m for m in SPEC["per_layer"]
             if m["name"] == "fedavg_reduce_roofline"]
    ctx = _context("paper-cnn.sync", _summary())    # no call of the kernel
    assert harness.read_metrics(specs, ctx, ["_other_kernel"]) == {}
    for lowered in (["_fedavg_kernel"], None):      # lowered, or not known
        with pytest.raises(RuntimeError, match="fedavg_reduce_roofline"):
            harness.read_metrics(specs, ctx, lowered)


def test_peak_memory_counts_what_the_runtime_reserves_for_programs():
    class Chip:
        def __init__(self, stats):
            self.stats = stats

        def memory_stats(self):
            return self.stats
    chips = [Chip({"peak_bytes_in_use": 21_303_296,
                   "peak_bytes_reserved": 2_458_910_720}),
             Chip({"peak_bytes_in_use": 5})]
    assert peak_bytes(chips) == 21_303_296 + 2_458_910_720
    assert peak_bytes([Chip(None)]) is None


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        harness.device_peaks("TPU v99")


def test_a_cell_added_as_files_is_found_by_name(tmp_path):
    spec = json.loads(json.dumps(SPEC))
    base = next(w for w in spec["workloads"] if w["name"] == CELLS[0])
    spec["workloads"].append(dict(base, name="paper-cnn.added",
                                  traffic="added"))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    traffic = json.loads(
        (ROOT / "bench" / "traffic" / f"{base['traffic']}.json").read_text())
    traffic["rounds_per_call"] = 2
    (tmp_path / "bench" / "traffic" / "added.json").write_text(
        json.dumps(traffic))
    shutil.copy(ROOT / "bench" / "limits" / f"{CELLS[0]}.json",
                tmp_path / "bench" / "limits" / "paper-cnn.added.json")
    cell = tiny_cell("paper-cnn.added", Path(tmp_path))
    assert cell.traffic["rounds_per_call"] == 2
    res = run_tiny(cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] % 2 == 0
