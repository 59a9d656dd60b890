"""Shared helpers of the benchmark's CPU tests: cells cut to a size the
CPU runs in seconds, and a compile cache kept out of the checkout."""
import time

import pytest

from bench import harness


def tiny_cell(name: str, root=harness.ROOT) -> harness.Cell:
    """The cell as committed, with its world cut for the CPU by its own
    entry's ``tiny``."""
    cell = harness.find_cell(name, root)
    return harness.load_module("entries", cell.traffic["entry"],
                               cell.root).tiny(cell)


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
