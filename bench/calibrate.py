#!/usr/bin/env python3
"""Readings that a cell's limits are set from, in one process on the chip.

    python3 bench/calibrate.py --workload sage-papers100m \\
        --seeds 101,102,...,112 --control-seeds 201,202,203

For each of ``--seeds`` it makes a whole run of the cell with a one-pass
window (set-up, warm-up pass, one timed pass, the comparison) and prints
the numbers compared.  For each of ``--control-seeds`` it makes the
cell's inputs and prints the control's readings: the reference computed
at ``Precision.HIGH`` (``bench/reference.py``) in the program's place,
against the reference.  The lower reading of a limit is the largest the
program gives; the upper one, the smallest the control gives.  Needs a
TPU, like ``bench/run.py``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import run  # bench/run.py: puts the checkout and the program on the path
from bench import cells, graphs, reference


def control_reading(cfg: dict, seed: int) -> float:
    v = cfg["num_vertices"]
    indptr, indices = graphs.make_graph(cfg, seed)
    feats = graphs.make_features(v, cfg["dims"][0], seed)
    weights = graphs.make_weights(cfg["kind"], cfg["dims"], seed)
    args = (cfg["kind"], indptr, indices, feats, weights)
    ref = reference.forward(*args)
    return reference.max_gap(reference.forward(*args, precision="high"), ref)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control_seeds = [int(s) for s in args.control_seeds.split(",") if s]

    bench = cells.load_benchmark()
    cell = cells.find_cell(args.workload, bench)
    mix = cells.load_traffic(cell["traffic"])
    cfg = cells.sized_config(cells.load_config(cell["config"]), mix,
                             cell["chips"])
    run.enable_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        run.log("calibrate: no TPU")
        return 2
    peaks = run.load_peaks(devices[0].device_kind)
    program, control = [], []
    for seed in seeds:
        line = run.run_cell(cell, bench, cfg, mix, seed, 0.0, False,
                            devices[:cell["chips"]], peaks)
        gaps = {k: c["value"] for k, c in line["checks"].items()}
        program.append(gaps["max_gap"])
        print(json.dumps({"seed": seed, "program": gaps,
                          "edges_per_s": line["metrics"]["edges_per_s"]}),
              flush=True)
    for seed in control_seeds:
        t0 = time.perf_counter()
        gap = control_reading(cfg, seed)
        control.append(gap)
        print(json.dumps({"seed": seed, "control_max_gap": gap,
                          "seconds": time.perf_counter() - t0}), flush=True)
    print(json.dumps({"workload": args.workload,
                      "program_max_gap_largest": max(program, default=None),
                      "control_max_gap_smallest": min(control, default=None),
                      "program": program, "control": control}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
