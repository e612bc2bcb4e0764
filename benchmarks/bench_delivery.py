"""Delivery-core microbenchmark: array vs python eviction bookkeeping.

Measures the engine's innermost loop — ``AtlasEngine._deliver`` routing
pre-aggregated per-chunk records through the memory manager, eviction
policy, and orchestrator — with everything else (disk, feature I/O,
dense transforms) stubbed out, so the number isolates the bookkeeping
cost the array-native refactor targets.  ``--mode engine`` additionally
times a full ``run_layer`` on a real on-disk store for an end-to-end
view; ``--mode io`` compares the spill-durability impls (synchronous
fsync-per-spill vs the write-back scheduler's group commit) across a
hot-store-fraction sweep, asserting bit-identical dense spills.

``--mmap-features`` generates the synthetic feature matrix straight
into an on-disk ``.npy`` and feeds the store from a read-only memmap,
so multi-M-vertex graphs (ROADMAP item) never materialise V×d floats
in RAM; it turns itself on automatically at --vertices >= 1M.

Usage:
    PYTHONPATH=src python benchmarks/bench_delivery.py
    PYTHONPATH=src python benchmarks/bench_delivery.py --vertices 250000 \
        --policies at,lru --mode both
    PYTHONPATH=src python benchmarks/bench_delivery.py --mode io \
        --vertices 2000000 --mmap-features --hot-fracs 0.05,0.125,0.25

Acceptance targets: >= 3x delivery throughput for
``policy_impl='array'`` over ``'python'`` at >= 100k vertices (ISSUE 1);
``io_impl='writeback'`` cuts layer-critical-path spill seconds vs
``'sync'`` with barrier time reported separately (ISSUE 5).
"""

from __future__ import annotations

import argparse
import json
import os
import tempfile
import time

import numpy as np

from repro.core import orchestrator as ost
from repro.core.atlas import AtlasConfig, AtlasEngine, spills_to_dense
from repro.core.eviction import make_policy
from repro.core.memory_manager import MemoryManager
from repro.core.orchestrator import Orchestrator
from repro.graphs.csr import degrees_from_csr
from repro.graphs.synth import make_features, make_features_mmap, powerlaw_graph
from repro.models.gnn import init_gnn_params
from repro.session import AtlasSession
from repro.storage.layout import GraphStore
from repro.util.compile_cache import enable_compile_cache


class RamColdStore:
    """In-memory cold tier so the microbench times bookkeeping, not disk."""

    def __init__(self, num_vertices: int, dim: int, dtype=np.float32):
        self._rows = np.zeros((num_vertices, dim), dtype=dtype)
        self.peak_resident = 0
        self._resident = 0

    def put(self, vertex_ids, rows):
        self._rows[vertex_ids] = rows
        self._resident += len(vertex_ids)
        self.peak_resident = max(self.peak_resident, self._resident)

    def take(self, vertex_ids):
        self._resident -= len(vertex_ids)
        return self._rows[vertex_ids].copy()


class SinkGrad:
    """Graduation stub: count rows, drop them."""

    def __init__(self):
        self.graduated = 0

    def add(self, vertex_ids, rows):
        self.graduated += len(vertex_ids)

    def add_gather(self, vertex_ids, source, rows_index):
        self.graduated += len(vertex_ids)


def build_chunks(csr, chunk_vertices: int):
    """Per-chunk (unique destinations, message counts) from the topology."""
    chunks = []
    for start in range(0, csr.num_vertices, chunk_vertices):
        end = min(start + chunk_vertices, csr.num_vertices)
        _, dst = csr.edges_for_range(start, end)
        u_dst, counts = np.unique(np.asarray(dst, dtype=np.int64), return_counts=True)
        chunks.append((u_dst, counts.astype(np.int64)))
    return chunks


def run_micro(csr, chunks, impl: str, hot_slots: int, dim: int, seed: int):
    in_deg, _ = degrees_from_csr(csr)
    required = in_deg.astype(np.int64)
    num_vertices = csr.num_vertices
    orch = Orchestrator(required)
    policy = make_policy(
        "at", seed=seed, impl=impl,
        num_vertices=num_vertices, max_pending=int(required.max()),
    )
    cold = RamColdStore(num_vertices, dim)
    mm = MemoryManager(
        num_slots=hot_slots, dim=dim, dtype=np.float32,
        orchestrator=orch, policy=policy, cold=cold,
    )
    grad = SinkGrad()
    shield = np.zeros(num_vertices, dtype=bool)
    delivered = 0
    reloads = 0
    t0 = time.perf_counter()
    for index, (u_dst, counts) in enumerate(chunks):
        shield[u_dst] = True
        partial = np.ones((len(u_dst), dim), dtype=np.float32)
        reloads += AtlasEngine._deliver(
            mm, orch, grad, u_dst, partial, counts,
            col_offset=0, shield=shield, chunk_index=index,
        )
        delivered += len(u_dst)
        shield[u_dst] = False
    seconds = time.perf_counter() - t0
    assert grad.graduated == int(np.sum(required > 0))
    return {
        "impl": impl,
        "seconds": seconds,
        "chunks": len(chunks),
        "chunks_per_s": len(chunks) / seconds,
        "delivered_vertices": delivered,
        "vertices_per_s": delivered / seconds,
        "evictions": mm.eviction_count,
        "reloads": mm.reload_count,
    }


def run_engine(
    csr,
    feats,
    impl: str,
    hot_slots: int,
    chunk_vertices: int,
    seed: int,
    backend: str = "numpy",
    io_impl: str = "writeback",
    pipeline: str = "auto",
    trace=None,
):
    """Full run_layer on a real on-disk store.  ``impl`` selects BOTH the
    eviction-policy impl and the layer-tail impl (python = full scalar
    oracle baseline, array = the vectorized engine); ``io_impl`` selects
    the spill durability path (sync fsync-per-spill oracle vs async
    write-back + group commit); ``pipeline`` selects serial vs the
    double-buffered staging ring for device aggregation.  ``trace`` is a
    ``repro.obs.trace.Tracer`` to record the run's per-thread timeline
    into (plus the background RSS/disk sampler)."""
    d = feats.shape[1]
    specs = init_gnn_params("gcn", [d, 8], seed=seed)
    cfg = AtlasConfig(
        chunk_bytes=chunk_vertices * d * 4,
        hot_slots=hot_slots,
        eviction="at",
        policy_impl=impl,
        tail_impl=impl,
        backend=backend,
        io_impl=io_impl,
        pipeline=pipeline,
        seed=seed,
        sample_interval_s=0.05 if trace is not None else 0.0,
    )
    with tempfile.TemporaryDirectory() as td:
        store = GraphStore.create(td + "/store", csr, feats, num_partitions=4)
        session = AtlasSession(store, config=cfg, workdir=td + "/work",
                               trace=trace)
        t0 = time.perf_counter()
        result = session.infer(specs)
        seconds = time.perf_counter() - t0
        spills, metrics = result.final.spills, result.metrics
        out = spills_to_dense(spills, csr.num_vertices, specs[-1].out_dim)
    m = metrics[0]
    rec = {
        "impl": impl,
        "backend": backend,
        "io_impl": io_impl,
        "seconds": seconds,
        "chunks": m.chunks,
        "chunks_per_s": m.chunks / seconds,
        "vertices_per_s": csr.num_vertices / seconds,
        "evictions": m.evictions,
        "reloads": m.reloads,
        "reload_pct_mean": m.reload_pct_mean,
        "tail_seconds": m.tail_seconds,
        "tail_rows_per_s": m.tail_rows_per_s,
        "transform_seconds": m.transform_seconds,
        "spill_seconds": m.spill_seconds,
        "barrier_seconds": m.barrier_seconds,
        "bytes_inflight": m.bytes_inflight,
        "aggregate_seconds": m.aggregate_seconds,
        "h2d_seconds": m.h2d_seconds,
        "pipeline_stall_seconds": m.pipeline_stall_seconds,
        # run-wide I/O queue stats, captured by the session before the
        # scheduler closed (None under io_impl="sync": no queue exists)
        "queue_stats": result.queue_stats,
        "output": out,
    }
    if trace is not None:
        rec["telemetry"] = result.telemetry
    return rec


def capture_graduation_stream(csr, feats, hot_slots, chunk_vertices, seed):
    """One engine run with ``GraduationProcessor.add_gather`` shimmed to
    record the exact per-call id batches the delivery loop produces — the
    real layer-tail workload, replayed below under both tail impls."""
    from repro.core.graduation import GraduationProcessor

    batches: list[np.ndarray] = []
    orig = GraduationProcessor.add_gather

    def recording(self, ids, source, rows_index):
        batches.append(np.asarray(ids).copy())
        return orig(self, ids, source, rows_index)

    GraduationProcessor.add_gather = recording
    try:
        run_engine(csr, feats, "array", hot_slots, chunk_vertices, seed)
    finally:
        GraduationProcessor.add_gather = orig
    return batches


def run_tail_replay(batches, num_vertices: int, dim: int, hot_slots: int, seed: int):
    """Replay the captured graduation stream through both tail impls,
    single-threaded (no GIL cross-talk), and isolate the bookkeeping cost:
    total minus the dense transform and the physical spill write, which
    are identical work under either impl.  Asserts bit-identical output."""
    from repro.core.graduation import make_graduation
    from repro.storage.writer import EmbeddingWriter

    rng = np.random.default_rng(seed)
    hot = rng.standard_normal((hot_slots, dim)).astype(np.float32)
    slot_batches = [
        rng.integers(0, hot_slots, len(b)).astype(np.int64) for b in batches
    ]
    spec = init_gnn_params("gcn", [dim, 8], seed=seed)[0]
    from repro.models.gnn import layer_update

    results, outputs = {}, {}
    for impl in ("python", "array"):
        best = None
        for _ in range(3):
            with tempfile.TemporaryDirectory() as td:
                w = EmbeddingWriter(
                    td, num_vertices=num_vertices, dim=8, dtype=np.float32,
                    num_partitions=8, buffer_rows=4096,
                    threaded=False, ingest_impl=impl,
                )
                g = make_graduation(
                    impl, transform=lambda r: layer_update(spec, r),
                    sink=w.write, dim=dim, dtype=np.float32,
                    buffer_rows=8192, threaded=False,
                )
                t0 = time.perf_counter()
                for ids, slots in zip(batches, slot_batches):
                    g.add_gather(ids, hot, slots)
                g.close()
                spills = w.close()
                total = time.perf_counter() - t0
                book = total - g.transform_seconds - w.spill_seconds
                if best is None or book < best["tail_seconds"]:
                    best = {
                        "impl": impl,
                        "tail_seconds": book,
                        "tail_rows_per_s": num_vertices / book,
                        "total_seconds": total,
                        "transform_seconds": g.transform_seconds,
                        "spill_seconds": w.spill_seconds,
                    }
                if impl not in outputs:
                    outputs[impl] = spills_to_dense(spills, num_vertices, 8)
        results[impl] = best
    assert np.array_equal(outputs["python"], outputs["array"]), (
        "tail impls diverged (spill contents)"
    )
    return results


def report(title: str, results: dict) -> float:
    py, ar = results["python"], results["array"]
    assert py["evictions"] == ar["evictions"], "impls diverged (evictions)"
    assert py["reloads"] == ar["reloads"], "impls diverged (reloads)"
    speedup = py["seconds"] / ar["seconds"]
    print(f"\n== {title} ==")
    for r in (py, ar):
        print(
            f"  {r['impl']:<7} {r['seconds']:8.3f}s   "
            f"{r['chunks_per_s']:10.1f} chunks/s   "
            f"{r['vertices_per_s']:12.0f} vertices/s   "
            f"evictions={r['evictions']} reloads={r['reloads']}"
        )
    print(f"  speedup (array over python): {speedup:.2f}x")
    return speedup


def report_tail(results: dict) -> float:
    """Layer-tail (graduation bookkeeping + writer scatter) throughput
    from the single-threaded stream replay, excluding the dense transform
    and physical spill write that are identical work under either impl."""
    py, ar = results["python"], results["array"]
    tail_speedup = ar["tail_rows_per_s"] / py["tail_rows_per_s"]
    print("  -- layer tail (graduation + spill scatter), stream replay --")
    for r in (py, ar):
        print(
            f"  {r['impl']:<7} {r['tail_seconds']*1000:8.1f}ms tail   "
            f"{r['tail_rows_per_s']:12.0f} rows/s   "
            f"(transform {r['transform_seconds']:.3f}s, "
            f"spill {r['spill_seconds']:.3f}s)"
        )
    print(f"  tail speedup (array over python): {tail_speedup:.2f}x")
    return tail_speedup


def run_io_sweep(csr, feats, hot_fracs, chunk_vertices, seed, repeats):
    """sync-vs-writeback spill durability across a hot-store sweep.

    Per hot fraction: run the full engine under both io impls, assert the
    dense spill outputs are bit-identical, and report the spill cost left
    on the layer critical path (spill_seconds) with the group-commit
    barrier broken out separately — plus reload% so the sweep charts
    reload churn vs hot-store fraction like paper Fig 8."""
    best = lambda runs: min(runs, key=lambda r: r["seconds"])
    sweep = []
    for hf in hot_fracs:
        hot_slots = max(16, int(csr.num_vertices * hf))
        res = {}
        for io_impl in ("sync", "writeback"):
            res[io_impl] = best([
                run_engine(csr, feats, "array", hot_slots, chunk_vertices,
                           seed, io_impl=io_impl)
                for _ in range(repeats)
            ])
        out_s = res["sync"].pop("output")
        out_w = res["writeback"].pop("output")
        if not np.array_equal(out_s, out_w):
            raise AssertionError(
                f"io impls diverged (dense spill contents) at hot_frac={hf}"
            )
        assert res["sync"]["evictions"] == res["writeback"]["evictions"]
        sweep.append({"hot_frac": hf, "hot_slots": hot_slots, **res})
    return sweep


def report_io(sweep) -> None:
    print("\n== io (sync fsync-per-spill vs write-back group commit) ==")
    print(
        f"  {'hot_frac':>8} {'impl':>10} {'total':>9} {'spill(cp)':>10} "
        f"{'barrier':>9} {'inflight':>10} {'reload%':>8}"
    )
    for row in sweep:
        for impl in ("sync", "writeback"):
            r = row[impl]
            print(
                f"  {row['hot_frac']:>8.3f} {impl:>10} {r['seconds']:>8.3f}s "
                f"{r['spill_seconds']:>9.4f}s {r['barrier_seconds']:>8.4f}s "
                f"{r['bytes_inflight']:>10} {r['reload_pct_mean']:>7.1f}%"
            )
        sy, wb = row["sync"], row["writeback"]
        if wb["spill_seconds"] > 0:
            print(
                f"  {'':>8} critical-path spill time: "
                f"{sy['spill_seconds'] / wb['spill_seconds']:.1f}x lower "
                f"(writeback), spill contents bit-identical"
            )


def run_dist_check(v, d, kind, shards, workers, chunk_vertices, seed,
                   backend="numpy", trace_path=None):
    """``--mode dist``: the shard-parallel engine vs the single-machine
    session on an exact-arithmetic graph (``repro.exact``) — every fp32
    sum exactly representable, so the N-shard run with cross-shard
    message routing must reproduce the single-machine spills **and** the
    rows served by the unmodified reader bit for bit.  Any tolerance here
    would hide a routing/namespace bug, so the comparison is
    ``np.array_equal``, not allclose."""
    import shutil

    from repro.dist import DistSession
    from repro.exact import exact_graph_and_specs

    csr, feats, specs = exact_graph_and_specs(v, d, kind=kind, seed=seed)
    hot_slots = max(16, v // 8)
    cfg = AtlasConfig(
        chunk_bytes=chunk_vertices * d * 4, hot_slots=hot_slots,
        backend=backend, trace=trace_path is not None,
    )
    with tempfile.TemporaryDirectory() as td:
        store = GraphStore.create(td + "/store", csr, feats, num_partitions=4)
        # opened first: its constructor refuses process workers under a
        # JAX backend before anything runs
        with DistSession(
            store, shards=shards, config=cfg, workers=workers,
            workdir=td + "/dist", trace=trace_path is not None,
        ) as dist:
            t0 = time.perf_counter()
            with AtlasSession(
                store,
                config=AtlasConfig(
                    chunk_bytes=cfg.chunk_bytes, hot_slots=hot_slots,
                    backend=backend,
                ),
                workdir=td + "/single",
            ) as single:
                ref = single.infer(specs)
                dense_ref = spills_to_dense(ref.final.spills, v, ref.final.dim)
            single_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            result = dist.infer(specs)
            dense_dist = spills_to_dense(result.final.spills, v, result.final.dim)
            version = dist.publish(result.final)
            probe = np.arange(0, v, 97)
            with dist.reader(result.final.layer) as reader:
                served = reader.lookup(probe)
            dist_s = time.perf_counter() - t0
            if trace_path and result.trace_path:
                shutil.copyfile(result.trace_path, trace_path)
    if not np.array_equal(dense_dist, dense_ref):
        raise AssertionError(
            f"dist spills diverged from single-machine ({kind}, "
            f"shards={shards}, workers={workers})"
        )
    if not np.array_equal(served, dense_ref[probe]):
        raise AssertionError(
            f"served rows diverged from single-machine ({kind}, "
            f"shards={shards}, workers={workers})"
        )
    exchange_bytes = sum(
        r["exchange"]["sent_bytes"]
        for reports in result.shard_reports.values()
        for r in reports
    )
    rec = {
        "kind": kind,
        "vertices": v,
        "shards": shards,
        "workers": workers,
        "layers": len(specs),
        "epoch": version.epoch,
        "single_seconds": single_s,
        "dist_seconds": dist_s,
        "exchange_sent_bytes": exchange_bytes,
        "bit_identical": True,
        "served_identical": True,
    }
    print(
        f"  {kind:<5} shards={shards} workers={workers}: "
        f"single {single_s:6.2f}s  dist {dist_s:6.2f}s  "
        f"exchange {exchange_bytes} B  spills+served bit-identical"
    )
    return rec


def build_features(args, workdir: str):
    """Dense in-RAM features, or an on-disk memmap for multi-M graphs."""
    if args.mmap_features or args.vertices >= 1_000_000:
        path = os.path.join(workdir, "features.npy")
        print(f"features: memory-mapped {path}")
        return make_features_mmap(
            args.vertices, args.dim, path, seed=args.seed
        )
    return make_features(args.vertices, args.dim, seed=args.seed)


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--vertices", type=int, default=120_000)
    ap.add_argument("--avg-degree", type=int, default=8)
    ap.add_argument("--dim", type=int, default=16)
    ap.add_argument("--hot-frac", type=float, default=0.125,
                    help="hot slots as a fraction of vertices")
    ap.add_argument("--hot-fracs", default=None,
                    help="comma list of hot fractions for --mode io "
                         "(default: just --hot-frac)")
    ap.add_argument("--chunk-vertices", type=int, default=4096)
    ap.add_argument("--mode",
                    choices=["micro", "engine", "both", "backend", "io",
                             "dist"],
                    default="micro")
    ap.add_argument("--shards", type=int, default=2,
                    help="shard count for --mode dist")
    ap.add_argument("--dist-workers", default="process",
                    choices=["thread", "process"],
                    help="worker harness for --mode dist")
    ap.add_argument("--dist-kinds", default="gcn,sage",
                    help="comma list of GNN kinds for --mode dist")
    ap.add_argument("--backend", default="numpy",
                    choices=["numpy", "jax", "pallas", "pallas-interpret"],
                    help="chunk-aggregation backend for --mode engine, "
                         "--mode dist (thread workers only) and the "
                         "non-numpy leg of --mode backend")
    ap.add_argument("--pipeline", default="auto",
                    choices=["auto", "staged", "serial"],
                    help="aggregation pipeline for --mode engine runs "
                         "(auto = staged when threaded and backend != numpy)")
    ap.add_argument("--io-impl", default="writeback",
                    choices=["writeback", "sync"],
                    help="spill durability impl for --mode engine runs")
    ap.add_argument("--mmap-features", action="store_true",
                    help="generate features into an on-disk .npy memmap "
                         "(auto at --vertices >= 1M)")
    ap.add_argument("--repeats", type=int, default=3,
                    help="repetitions per impl; best (min-time) run is reported")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="write raw results as JSON to PATH ('-' for stdout)")
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="run one extra traced engine pass and export its "
                         "Perfetto timeline (Chrome trace-event JSON) to "
                         "PATH; inspect with repro.launch.obs_report")
    args = ap.parse_args()
    enable_compile_cache()

    hot_slots = max(16, int(args.vertices * args.hot_frac))
    print(
        f"graph: V={args.vertices} avg_deg={args.avg_degree} d={args.dim} "
        f"hot_slots={hot_slots} chunk_vertices={args.chunk_vertices}"
    )
    csr = powerlaw_graph(args.vertices, args.avg_degree, seed=args.seed,
                         self_loops=True)
    all_results = {}
    best = lambda runs: min(runs, key=lambda r: r["seconds"])
    reps = max(1, args.repeats)
    feat_td = tempfile.TemporaryDirectory(prefix="bench_delivery_feats_")
    feats_cache: list = []  # built once, shared by every mode leg

    def get_feats():
        if not feats_cache:
            feats_cache.append(build_features(args, feat_td.name))
        return feats_cache[0]

    if args.mode in ("micro", "both"):
        chunks = build_chunks(csr, args.chunk_vertices)
        res = {
            impl: best([
                run_micro(csr, chunks, impl, hot_slots, args.dim, args.seed)
                for _ in range(reps)
            ])
            for impl in ("python", "array")
        }
        all_results["micro"] = {**res, "speedup": report("micro (_deliver only)", res)}
    if args.mode in ("engine", "both"):
        feats = get_feats()
        res = {
            impl: best([
                run_engine(csr, feats, impl, hot_slots, args.chunk_vertices,
                           args.seed, backend=args.backend,
                           io_impl=args.io_impl, pipeline=args.pipeline)
                for _ in range(reps)
            ])
            for impl in ("python", "array")
        }
        # the array tail must reproduce the python-oracle spills bit for bit
        out_py, out_ar = res["python"].pop("output"), res["array"].pop("output")
        if not np.array_equal(out_py, out_ar):
            raise AssertionError("impls diverged (spill contents)")
        speedup = report("engine (full run_layer)", res)
        print("  spill contents: bit-identical across impls")
        ar = res["array"]
        print(
            f"  pipeline: aggregate {ar['aggregate_seconds']:.4f}s   "
            f"h2d {ar['h2d_seconds']:.4f}s   "
            f"stall {ar['pipeline_stall_seconds']:.4f}s"
        )
        # the staging ring must reproduce the serial spills bit for bit
        if args.backend != "numpy":
            out_st = run_engine(
                csr, feats, "array", hot_slots, args.chunk_vertices,
                args.seed, backend=args.backend, io_impl=args.io_impl,
                pipeline="staged",
            ).pop("output")
            out_se = run_engine(
                csr, feats, "array", hot_slots, args.chunk_vertices,
                args.seed, backend=args.backend, io_impl=args.io_impl,
                pipeline="serial",
            ).pop("output")
            if not np.array_equal(out_st, out_se):
                raise AssertionError(
                    "pipeline impls diverged (spill contents)"
                )
            print("  spill contents: bit-identical staged vs serial pipeline")
        # layer-tail throughput: replay the engine's real graduation
        # stream through both tail impls, single-threaded and isolated
        batches = capture_graduation_stream(
            csr, feats, hot_slots, args.chunk_vertices, args.seed
        )
        tail = run_tail_replay(
            batches, args.vertices, args.dim, hot_slots, args.seed
        )
        tail_speedup = report_tail(tail)
        print("  tail replay spill contents: bit-identical across impls")
        all_results["engine"] = {
            **res, "speedup": speedup,
            "tail": tail, "tail_speedup": tail_speedup,
        }
    if args.mode == "io":
        # ISSUE 5: spill durability impls across a hot-store sweep, with
        # the vectorized engine fixed so only io_impl varies
        feats = get_feats()
        hot_fracs = (
            [float(x) for x in args.hot_fracs.split(",")]
            if args.hot_fracs
            else [args.hot_frac]
        )
        sweep = run_io_sweep(
            csr, feats, hot_fracs, args.chunk_vertices, args.seed, reps
        )
        report_io(sweep)
        print("  spill contents: bit-identical across io impls")
        all_results["io"] = sweep
    if args.mode == "backend":
        # ROADMAP item: numpy vs device chunk aggregation end-to-end, with
        # the array policy impl fixed so only the aggregation backend varies
        feats = get_feats()
        other = args.backend if args.backend != "numpy" else "jax"
        res = {
            backend: best([
                run_engine(csr, feats, "array", hot_slots, args.chunk_vertices,
                           args.seed, backend=backend)
                for _ in range(reps)
            ])
            for backend in ("numpy", other)
        }
        ny, dv = res["numpy"], res[other]
        # backends differ in float op order: same bookkeeping, not bitwise
        ny.pop("output"), dv.pop("output")
        assert ny["evictions"] == dv["evictions"], "backends diverged (evictions)"
        speedup = ny["seconds"] / dv["seconds"]
        print("\n== backend (full run_layer, policy_impl=array) ==")
        for r in (ny, dv):
            print(
                f"  {r['backend']:<16} {r['seconds']:8.3f}s   "
                f"{r['chunks_per_s']:10.1f} chunks/s   "
                f"{r['vertices_per_s']:12.0f} vertices/s   "
                f"evictions={r['evictions']} reloads={r['reloads']}"
            )
        print(
            f"  device leg: aggregate {dv['aggregate_seconds']:.4f}s   "
            f"h2d {dv['h2d_seconds']:.4f}s   "
            f"stall {dv['pipeline_stall_seconds']:.4f}s"
        )
        print(f"  speedup ({other} over numpy): {speedup:.2f}x")
        all_results["backend"] = {**res, f"{other}_speedup": speedup}
    if args.mode == "dist":
        # ISSUE 9: shard-parallel engine vs single-machine, exact
        # arithmetic, bitwise assertion on spills AND served rows; the
        # merged per-shard trace (when --trace) lands at args.trace
        print(f"\n== dist (shard-parallel vs single-machine, "
              f"shards={args.shards}) ==")
        kinds = [k for k in args.dist_kinds.split(",") if k]
        dist_rows = []
        for i, kind in enumerate(kinds):
            dist_rows.append(run_dist_check(
                args.vertices, args.dim, kind, args.shards,
                args.dist_workers, args.chunk_vertices, args.seed,
                backend=args.backend,
                trace_path=args.trace if i == 0 else None,
            ))
        print("  spills + served rows: bit-identical across all kinds")
        all_results["dist"] = dist_rows
        if args.trace:
            print(f"merged per-shard trace -> {args.trace}")
    if args.trace and args.mode != "dist":
        # one extra traced pass of the full engine (vectorized impl):
        # per-thread timeline + telemetry, Perfetto-loadable, analysable
        # with `python -m repro.launch.obs_report <trace> --check`
        from repro.obs.trace import Tracer

        tracer = Tracer()
        traced = run_engine(
            csr, get_feats(), "array", hot_slots, args.chunk_vertices,
            args.seed, backend=args.backend, io_impl=args.io_impl,
            pipeline=args.pipeline, trace=tracer,
        )
        traced.pop("output")
        path = tracer.export(args.trace)
        print(
            f"\ntraced engine pass: {traced['seconds']:.3f}s, "
            f"{tracer.num_spans} spans -> {path}"
        )
        all_results["traced"] = traced
    feat_td.cleanup()
    if args.json == "-":
        print(json.dumps(all_results, indent=2))
    elif args.json:
        with open(args.json, "w") as f:
            json.dump(all_results, f, indent=2)
        print(f"\nwrote {args.json}")


if __name__ == "__main__":
    main()
