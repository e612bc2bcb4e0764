"""Shared set-up of the benchmark's CPU tests: the checkout and the
program on the import path, and cells cut to a size a test can run with
the kernel in interpret mode."""

from __future__ import annotations

import os
import sys

import pytest

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (CHECKOUT, os.path.join(CHECKOUT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import cells  # noqa: E402

SMALL_VERTICES = 800
SEED = 2**33 + 5  # larger than 32 bits hold


def small_cell(name: str):
    """``(cell, bench, cfg, mix)`` of a cell at V=800 with chunks of a few
    dozen rows, so that many chunks, evictions and reloads still happen."""
    bench = cells.load_benchmark()
    cell = cells.find_cell(name, bench)
    cfg = cells.load_config(cell["config"])
    mix = cells.load_traffic(cell["traffic"])
    cfg["num_vertices"] = SMALL_VERTICES
    mix["chunk_bytes"] = 32 * cfg["dims"][0] * 4
    return cell, bench, cfg, mix


def run_small(name: str, trace: bool = False, seed: int = SEED) -> dict:
    import jax

    from bench import run

    cell, bench, cfg, mix = small_cell(name)
    peaks = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
    return run.run_cell(cell, bench, cfg, mix, seed, 0.01, trace,
                        jax.devices()[:1], peaks, backend="pallas-interpret")


@pytest.fixture(params=["sage-papers100m", "gcn-igb-large",
                        "sage-papers100m-inmem"])
def cell_name(request):
    return request.param
