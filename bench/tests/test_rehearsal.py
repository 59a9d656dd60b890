"""CPU rehearsal of the command: it refuses to report without a TPU, and
with the platform check skipped every entry adapter and metric reader runs
at a tiny size."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness
from bench.harness import peak_bytes    # the real one, not the CPU's stand-in
from bench.tests.conftest import hfl_cell, run_tiny, tiny_cell

ROOT = harness.ROOT
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]


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


@pytest.mark.parametrize("name", CELLS)
def test_entry_runs_and_is_correct_at_a_tiny_size(name):
    res = run_tiny(tiny_cell(name))
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    e2e = {m["name"] for m in SPEC["end_to_end"]
           if name in m.get("workloads", [name])}
    assert set(res["metrics"]) == e2e
    assert list(res)[-1] == "checks"


def test_hierarchical_traffic_is_correct_at_a_tiny_size():
    res = run_tiny(hfl_cell())
    assert res["correct"], res["checks"]


def _summary():
    from bench.trace import Summary
    return Summary(busy_s=0.8, window_s=1.0,
                   op_seconds={"fusion.1": 0.5},
                   call_counts={"k.1": 4}, call_seconds={"k.1": 0.2},
                   gaps=[("host", 0.2)])


@pytest.mark.parametrize("metric", [m["name"] for m in SPEC["per_layer"]
                                    + SPEC["end_to_end"]])
def test_every_metric_reader_reads(metric):
    spec = next(m for m in SPEC["per_layer"] + SPEC["end_to_end"]
                if m["name"] == metric)
    name = spec.get("workloads", CELLS)[0]
    cell = harness.find_cell(name)
    kernels = {"fedavg_reduce_roofline": "_fedavg_kernel",
               "fedavg_segment_reduce_roofline": "_segment_kernel",
               "select_topk_roofline": "_select_kernel",
               "bandwidth_solve_roofline": "_bw_kernel"}
    calls = {"k.1": {"kernel": kernels.get(metric, "_other_kernel"),
                     "bytes": 4e6, "flops": 1e6}}
    learning = cell.traffic["entry"] == "fl_fused"
    counters = ({"rounds": 3, "n_selected": [50, 25, 26]} if learning
                else {"decisions": 1, "n_selected": [300]})
    ctx = harness.Context(
        cell=cell, setup_s=12.0, window_s=6.0, units=3,
        peak_bytes=2_000_000_000,
        counters=counters,
        peaks=harness.device_peaks("TPU v5 lite"), trace=_summary(),
        kernel_calls=calls)
    value = harness.load_module("metrics", metric).read(ctx)
    assert value is not None and value > 0
    if spec["unit"] == "%":
        assert value <= 100
    if metric in kernels:
        # 4 calls of 4 MB at 819 GB/s over 0.2 s of kernel time
        assert value == pytest.approx(100 * 4 * 4e6 / 819e9 / 0.2)
    ctx.trace = None
    if spec in SPEC["per_layer"]:
        assert harness.load_module("metrics", metric).read(ctx) is None


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
    for c in spec["configs"]:
        dst = tmp_path / c["file"]
        dst.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(ROOT / c["file"], dst)
    (tmp_path / "bench" / "traffic").mkdir(parents=True)
    (tmp_path / "bench" / "limits").mkdir(parents=True)
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
