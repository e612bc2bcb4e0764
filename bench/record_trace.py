#!/usr/bin/env python3
"""Record the small chip trace that ``bench/tests`` check the trace
reduction against, and describe a trace's planes and lines.

    python3 bench/record_trace.py --out bench/testdata/small.xplane.pb
    python3 bench/record_trace.py --describe PATH

Recording runs one small pass (V=3000, GraphSAGE 128 -> 256 -> 172,
64 KiB chunks) of the program on the chip, under the profiler, inside
the benchmark's anchor annotation, and needs a TPU.
"""

from __future__ import annotations

import argparse
import os
import shutil
import sys
import tempfile

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, CHECKOUT)
sys.path.insert(0, os.path.join(CHECKOUT, "src"))


def describe(path: str) -> None:
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        lines = list(plane.lines)
        print(f"plane {plane.name!r}: {len(lines)} lines")
        for line in lines:
            evs = list(line.events)
            names: dict[str, int] = {}
            for ev in evs:
                names[ev.name] = names.get(ev.name, 0) + 1
            top = sorted(names.items(), key=lambda kv: -kv[1])[:6]
            span = (f"{evs[0].start_ns:.0f}..{evs[-1].end_ns:.0f}"
                    if evs else "-")
            print(f"  line {line.name!r}: {len(evs)} events [{span}] {top}")


def record(out: str) -> None:
    import jax

    from bench import cells, run

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_trace: needs a TPU")
    cfg = cells.load_config("graphsage-papers100m")
    cfg["num_vertices"] = 3000
    mix = cells.load_traffic("ooc-quarter")
    mix["chunk_bytes"] = 1 << 16
    with tempfile.TemporaryDirectory() as td:
        cell = run.build(cfg, mix, 1, td, "pallas")
        cell.session.infer(cell.specs)  # compile outside the trace
        prof = os.path.join(td, "profile")
        run.start_profiler(prof)
        with jax.profiler.TraceAnnotation(run.ANCHOR):
            cell.session.infer(cell.specs)
        jax.profiler.stop_trace()
        cell.session.close()
        os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
        shutil.copyfile(run.trace_reduce.find_xplane(prof), out)
    print(f"wrote {out} ({os.path.getsize(out)} bytes)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out")
    ap.add_argument("--describe")
    args = ap.parse_args(argv)
    if args.out:
        record(args.out)
        describe(args.out)
    if args.describe:
        describe(args.describe)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
