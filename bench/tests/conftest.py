"""Shared set-up of the benchmark's CPU tests: the checkout and the
program on the import path, four host devices for the cells that run on
four chips, and cells cut to a size a test can run with the kernel in
interpret mode."""

from __future__ import annotations

import os
import sys

import pytest

# before JAX starts: the CPU backend then has four devices, enough for
# the sharded engine's mesh exchange
HOST_DEVICES = "--xla_force_host_platform_device_count=4"
if HOST_DEVICES not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " "
                               + HOST_DEVICES).strip()

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
    dozen rows, so that many chunks, evictions and reloads still happen.
    A cell on several chips splits the 800 vertices among them."""
    bench = cells.load_benchmark()
    cell = cells.find_cell(name, bench)
    mix = cells.load_traffic(cell["traffic"])
    cfg = cells.load_config(cell["config"])
    if mix.get("vertices_per_chip"):
        cfg["num_vertices"] = SMALL_VERTICES // cell["chips"]
    else:
        cfg["num_vertices"] = SMALL_VERTICES
    cfg = cells.sized_config(cfg, mix, cell["chips"])
    mix["chunk_bytes"] = 32 * cfg["dims"][0] * 4
    return cell, bench, cfg, mix


@pytest.fixture(scope="session", autouse=True)
def compile_cache(tmp_path_factory):
    """JAX's persistent compilation cache, on as ``bench/run.py`` has it
    on the chip: the sharded engine builds its exchange's program anew in
    every layer, and the window has to find it there."""
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      str(tmp_path_factory.mktemp("jax_cache")))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def run_small(name: str, trace: bool = False, seed: int = SEED) -> dict:
    import jax

    from bench import run

    cell, bench, cfg, mix = small_cell(name)
    peaks = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
    return run.run_cell(cell, bench, cfg, mix, seed, 0.01, trace,
                        jax.devices()[:cell["chips"]], peaks,
                        backend="pallas-interpret")


@pytest.fixture(params=["sage-papers100m", "gcn-igb-large",
                        "sage-papers100m-inmem", "sage-papers100m-4chip"])
def cell_name(request):
    return request.param
