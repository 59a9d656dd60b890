"""Shared helpers of the benchmark's CPU tests: cells cut to a size the
CPU runs in seconds, and a compile cache kept out of the checkout."""
import time

import pytest

from bench import harness


def tiny_cell(name: str, root=harness.ROOT) -> harness.Cell:
    """The cell as committed, with its world cut for the CPU: 800 training
    images, one local epoch at batch 4; or 20,000 users picking about 60,
    on two of the cell's worlds.
    The control-plane cell takes the chunked jnp route, whose decisions
    equal the Pallas route's, because Pallas interpret mode is slow here."""
    cell = harness.find_cell(name, root)
    if cell.traffic["entry"] == "fl_fused":
        cell.config.update(n_train=800, n_test=200, local_epochs=1,
                           batch_size=4)
    else:
        cell.config.update(n_users=20000, rho2=3e-3)
        cell.traffic["options"]["backend"] = "jax"
        cell.traffic["world_seeds"] = cell.traffic["world_seeds"][:2]
    return cell


def hfl_cell() -> harness.Cell:
    """The hierarchical high-mobility traffic on the paper's configuration,
    cut like ``tiny_cell``: the reference's hierarchical path is kept
    tested while its chip cell waits (PERF.md, Open questions)."""
    b = harness.ROOT / "bench"
    config = harness.load_json(b / "configs" / "paper-cnn-n50.json")
    config.update(n_train=800, n_test=200, local_epochs=1, batch_size=4)
    spec = harness.load_json(harness.ROOT / "BENCHMARK.json")
    return harness.Cell(
        name="paper-cnn.hfl-high-mobility", chips=1, config=config,
        traffic=harness.load_json(b / "traffic" / "hfl-high-mobility.json"),
        limits=harness.load_json(b / "limits" / "paper-cnn.sync.json"),
        e2e=[m for m in spec["end_to_end"]
             if "paper-cnn.sync" in m.get("workloads", ["paper-cnn.sync"])])


def run_tiny(cell: harness.Cell, seed: int = 7, seconds: float = 0.1):
    return harness.run(cell, seed, seconds, False, time.perf_counter())


@pytest.fixture(autouse=True)
def _cache_out_of_checkout(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))


@pytest.fixture(autouse=True)
def _cpu_memory_stats(monkeypatch):
    """The CPU backend keeps no memory statistics: stand in one byte where
    a TPU would report its peak, so that ``peak_hbm_gb`` reads."""
    real = harness.peak_bytes
    monkeypatch.setattr(harness, "peak_bytes",
                        lambda devices: real(devices) or 1)
