#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python3 bench/run.py --workload sage-papers100m --seed 7 \\
        --seconds 45 --trace 0

Set-up makes the cell's graph, features and weights from ``--seed``,
builds the program's on-disk ``GraphStore`` in the mix's vertex order,
opens an ``AtlasSession`` with the compiled Pallas backend (a
``DistSession`` with one shard per chip, where the mix names an
exchange) and runs one whole warm-up pass, which compiles (or loads from
the persistent cache) every kernel shape the window will use.
``--trace 0`` then runs whole
``session.infer`` passes back to back until ``--seconds`` have passed and
reports the end-to-end metrics; ``--trace 1`` runs one pass under the
JAX profiler and the program's span tracer and reports the per-layer
metrics, the device's busy time and a breakdown.  Either way the final
layer of the last pass, read back from its spill files, is compared with
``bench/reference.py``; the numbers compared are printed with their
limits as the last lines of standard error and under ``checks``, the
last key of the result.

The last line of standard output is one JSON object.  With no TPU, or
fewer chips than the cell asks for, the run exits non-zero and prints no
result.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import tempfile
import time

T_START = time.perf_counter()

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, CHECKOUT)
sys.path.insert(0, os.path.join(CHECKOUT, "src"))

import numpy as np  # noqa: E402

from bench import cells, graphs, reference, trace_reduce, work  # noqa: E402

ANCHOR = "bench_pass"
CACHE_DIR = os.path.join(CHECKOUT, ".jax_cache")
PEAKS_JSON = os.path.join(cells.BENCH_DIR, "peaks.json")
TOP = 10


class CompileCounter:
    """Counts XLA compile requests and the persistent-cache hits among
    them through ``jax.monitoring``; ``compiled`` is the requests that
    the persistent cache did not serve."""

    def __init__(self) -> None:
        from jax import monitoring

        self.requests = 0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **kwargs) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1

    def _on_event(self, event, **kwargs) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    @property
    def compiled(self) -> int:
        return self.requests - self.cache_hits


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def enable_compile_cache() -> str:
    """JAX's persistent cache: ``$JAX_COMPILATION_CACHE_DIR`` where set,
    else ``<checkout>/.jax_cache`` (a fixed path: it is part of the key)."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def load_peaks(device_kind: str) -> dict:
    with open(PEAKS_JSON) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_JSON} (have: {sorted(table)})")
    return table[device_kind]


@dataclasses.dataclass
class Cell:
    """A cell's inputs as made from the seed, and the program built on
    them."""

    cfg: dict
    mix: dict
    seed: int
    indptr: np.ndarray
    indices: np.ndarray
    feats: np.ndarray
    weights: list
    store: object
    specs: list
    session: object

    @property
    def num_vertices(self) -> int:
        return len(self.indptr) - 1

    @property
    def num_edges(self) -> int:
        return len(self.indices)


def build(cfg: dict, mix: dict, seed: int, workdir: str, backend: str,
          tracer=None, chips: int = 1) -> Cell:
    """Inputs from the seed, the program's store, specs and session.

    Each layer's spec takes every weight of the layer as ``params`` and
    any further ``GNNLayerSpec`` fields from the configuration's
    ``"spec"``.  A mix with an ``"exchange"`` runs the sharded engine:
    one shard per chip, thread workers, that exchange between them."""
    from repro.core.atlas import AtlasConfig
    from repro.dist.session import DistSession
    from repro.graphs.csr import CSRGraph
    from repro.models.gnn import GNNLayerSpec
    from repro.session import AtlasSession
    from repro.storage.layout import GraphStore

    t0 = time.perf_counter()
    indptr, indices = graphs.make_graph(cfg, seed)
    feats = graphs.make_features(cfg["num_vertices"], cfg["dims"][0], seed)
    weights = graphs.make_weights(cfg["kind"], cfg["dims"], seed)
    t1 = time.perf_counter()
    store = GraphStore.create(
        os.path.join(workdir, "store"), CSRGraph(indptr, indices), feats,
        num_partitions=mix["partitions"], order=mix["order"],
    )
    t2 = time.perf_counter()
    log(f"set-up: inputs {t1 - t0:.3f}s, store ({mix['order']}) "
        f"{t2 - t1:.3f}s; V={len(indptr) - 1} E={len(indices)}")
    dims = cfg["dims"]
    specs = [
        GNNLayerSpec(kind=cfg["kind"], in_dim=d_in, out_dim=d_out,
                     activation=i < len(dims) - 2, params=dict(w),
                     **cfg.get("spec", {}))
        for i, (d_in, d_out, w) in enumerate(zip(dims[:-1], dims[1:],
                                                 weights))
    ]
    config = AtlasConfig(backend=backend, chunk_bytes=mix["chunk_bytes"],
                         hot_bytes=cells.hot_bytes(cfg, mix),
                         eviction=mix["eviction"])
    run_dir = os.path.join(workdir, "run")
    trace = tracer if tracer is not None else False
    if "exchange" in mix:
        session = DistSession(store, shards=chips, config=config,
                              workdir=run_dir, exchange=mix["exchange"],
                              workers="thread", trace=trace)
    else:
        session = AtlasSession(store, config=config, workdir=run_dir,
                               trace=trace)
    return Cell(cfg, mix, seed, indptr, indices, feats, weights, store,
                specs, session)


def read_final(cell: Cell, result, since_ns: int) -> tuple[np.ndarray, int]:
    """The final layer's rows in external-id order (NaN where a row is
    missing), and how many of its spill files were last written before
    ``since_ns`` (wall clock): stale answers, not this pass's."""
    final = result.final
    out = np.full((cell.num_vertices, final.dim), np.nan, dtype=np.float32)
    stale = 0
    for f in final.spills.files:
        ids, rows = f.read_all()
        out[ids.astype(np.int64)] = rows
        if os.stat(f.path).st_mtime_ns < since_ns:
            stale += 1
    ext = cell.store.to_internal(np.arange(cell.num_vertices))
    return out[ext], stale


def compare(cell: Cell, out: np.ndarray, stale: int) -> dict:
    """The numbers that decide ``correct``, each with its limit."""
    t0 = time.perf_counter()
    ref = reference.forward(cell.cfg["kind"], cell.indptr, cell.indices,
                            cell.feats, cell.weights)
    log(f"reference: {time.perf_counter() - t0:.3f}s")
    return {
        "max_gap": {"value": reference.max_gap(out, ref),
                    "limit": cell.cfg["limits"]["max_gap"]},
        "stale_spills": {"value": stale, "limit": 0},
    }


def device_info(devices) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def layer_records(result) -> list[dict]:
    """The pass's counters: one dict per layer, or, from the sharded
    engine, its shard reports, one per shard and layer."""
    if hasattr(result, "shard_reports"):
        return [info for layer in sorted(result.shard_reports)
                for info in result.shard_reports[layer]]
    return [m.as_dict() for m in result.metrics]


def layer_lines(result) -> None:
    for m in layer_records(result):
        where = f"layer {m['layer']}"
        if "shard" in m:
            where += f" shard {m['shard']}"
        times = " ".join(f"{k.removesuffix('_seconds')}={m[k]:.3f}"
                         for k in ("seconds", "aggregate_seconds",
                                   "h2d_seconds", "transform_seconds")
                         if k in m)
        log(f"  {where}: {times} chunks={m['chunks']} "
            f"evictions={m['evictions']} reloads={m['reloads']}")


def span_record(tracer, offset_ns: int, w0: int, w1: int) -> list[dict]:
    """The tracer's spans on the profiler's clock, clipped to the window."""
    out = []
    for sp in tracer.spans():
        s = tracer.t0_ns + int(sp["start_s"] * 1e9) + offset_ns
        e = s + int(sp["dur_s"] * 1e9)
        if e > w0 and s < w1:
            out.append({"thread": sp["thread"], "name": sp["name"],
                        "cat": sp["cat"], "start_ns": s, "end_ns": e})
    return out


def start_profiler(profile_dir: str) -> None:
    """The JAX profiler without its Python call tracer: a whole pass of
    the program's Python would swamp the trace and slow the host."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(profile_dir, profiler_options=options)


def traced_pass(cell: Cell, tracer, profile_dir: str, peaks: dict,
                chips: int):
    """One pass under the profiler; returns the pass, its start on the
    wall clock and the record the per-layer metric readers take."""
    import jax

    start_profiler(profile_dir)
    try:
        wall0 = time.time_ns()
        with jax.profiler.TraceAnnotation(ANCHOR):
            p0 = time.perf_counter_ns()
            result = cell.session.infer(cell.specs)
            p1 = time.perf_counter_ns()
    finally:
        jax.profiler.stop_trace()
    t0 = time.perf_counter()
    red = trace_reduce.reduce_trace(trace_reduce.find_xplane(profile_dir),
                                    ANCHOR)
    w0, w1 = red["window_ns"]
    spans = span_record(tracer, w0 - p0, w0, w1)
    log(f"trace: pass {(p1 - p0) / 1e9:.3f}s host, window "
        f"{(w1 - w0) / 1e9:.3f}s, busy {red['busy_ns'] / 1e9:.3f}s on "
        f"{red['devices']}; {len(spans)} spans; reduced in "
        f"{time.perf_counter() - t0:.3f}s")
    record = {
        "config": cell.cfg,
        "num_vertices": cell.num_vertices,
        "num_edges": cell.num_edges,
        "chips": chips,
        "layers": layer_records(result),
        "spans": spans,
        "trace": red,
        "peaks": peaks,
    }
    return result, wall0, record


def breakdown(record: dict) -> dict:
    red = record["trace"]
    ops = sorted(red["ops"].items(), key=lambda kv: -kv[1])[:TOP]
    # the gaps are the first device's: label them by the thread that
    # calls its kernels (the staging thread, or shard 0) and the main one
    threads = sorted({sp["thread"] for sp in record["spans"]
                      if sp["cat"] == "aggregate"})[:1] + ["MainThread"]
    gaps = trace_reduce.label_gaps(red["gaps"], record["spans"], threads)
    top_gaps = sorted(gaps.items(), key=lambda kv: -kv[1])[:TOP]
    return {"device_ops": [[n, ns / 1e9] for n, ns in ops],
            "idle_gaps": [[n, s] for n, s in top_gaps]}


def run_cell(cell_def: dict, bench: dict, cfg: dict, mix: dict, seed: int,
             seconds: float, trace: bool, devices, peaks: dict | None,
             backend: str = "pallas") -> dict:
    """Set-up, window and check of one run; returns the result object."""
    from repro.obs.trace import Tracer

    compiles = CompileCounter()
    with tempfile.TemporaryDirectory(prefix="atlas-bench-") as workdir:
        tracer = Tracer() if trace else None
        cell = build(cfg, mix, seed, workdir, backend, tracer,
                     chips=len(devices))
        t0 = time.perf_counter()
        warm = cell.session.infer(cell.specs)
        log(f"warm-up pass: {time.perf_counter() - t0:.3f}s, "
            f"{compiles.requests} compile requests, {compiles.cache_hits} "
            f"from the persistent cache")
        layer_lines(warm)
        setup_s = time.perf_counter() - T_START
        requests_before = compiles.requests
        compiled_before = compiles.compiled

        metrics: dict = {}
        extra: dict = {}
        if trace:
            result, wall0, record = traced_pass(
                cell, tracer, os.path.join(workdir, "profile"), peaks,
                len(devices))
            layer_lines(result)
            for m in cells.cell_metrics(cell_def, bench, "per_layer"):
                value = cells.metric_reader(m["name"])(record)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            red = record["trace"]
            w0, w1 = red["window_ns"]
            extra = {"busy_s": red["busy_ns"] / 1e9,
                     "window_s": (w1 - w0) / 1e9}
            passes = 1
            brk = breakdown(record)
        else:
            t_first = time.perf_counter()
            passes = 0
            while True:
                wall0 = time.time_ns()
                p0 = time.perf_counter()
                result = cell.session.infer(cell.specs)
                t_last = time.perf_counter()
                passes += 1
                log(f"pass {passes}: {t_last - p0:.3f}s")
                if t_last - t_first >= seconds:
                    break
            layer_lines(result)
            rate = (cell.num_edges * len(cell.specs) * passes
                    / (t_last - t_first))
            values = {"edges_per_s": rate, "setup_s": setup_s}
            for m in cells.cell_metrics(cell_def, bench, "end_to_end"):
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
        window_compiles = compiles.compiled - compiled_before
        log(f"compiles inside the window: {window_compiles} (compile "
            f"requests {compiles.requests - requests_before}, the others "
            f"served by the persistent cache)")
        if window_compiles:
            raise RuntimeError(
                f"{window_compiles} compile(s) inside the measured window: "
                "the warm-up pass missed a shape")

        device = device_info(devices)
        device.update(extra)
        out, stale = read_final(cell, result, wall0)
        cell.session.close()
        cell.session = cell.store = None
    checks = compare(cell, out, stale)
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    line = {"correct": correct, "attempted": passes, "failed": 0,
            "metrics": metrics, "device": device}
    if trace:
        line["breakdown"] = brk
    line["checks"] = checks
    try:
        with open("/proc/self/io") as f:
            io = dict(ln.split(": ") for ln in f.read().splitlines())
        log(f"bytes written by this process: {int(io['write_bytes'])}")
    except OSError:
        pass
    log(f"setup_s {setup_s:.3f}")
    for name, c in checks.items():
        log(f"check {name} = {c['value']!r} (limit {c['limit']!r})")
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)

    bench = cells.load_benchmark()
    cell_def = cells.find_cell(args.workload, bench)
    mix = cells.load_traffic(cell_def["traffic"])
    cfg = cells.sized_config(cells.load_config(cell_def["config"]), mix,
                             cell_def["chips"])
    cache = enable_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"bench: no TPU; JAX found {len(devices)} {devices[0].platform} "
            f"device(s). The benchmark runs only on the chip.")
        return 2
    if len(devices) < cell_def["chips"]:
        log(f"bench: {args.workload} needs {cell_def['chips']} chips, JAX "
            f"found {len(devices)}")
        return 2
    peaks = load_peaks(devices[0].device_kind)
    log(f"device: {devices[0].device_kind} x{len(devices)}; compile cache "
        f"{cache}")
    line = run_cell(cell_def, bench, cfg, mix, args.seed, args.seconds,
                    bool(args.trace), devices[:cell_def["chips"]], peaks)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
