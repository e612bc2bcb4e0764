#!/usr/bin/env python3
"""Chip smoke test: ATLAS infer -> publish -> serve on a TPU, compiled.

    python3 chip_smoke.py            # one chip: the main path
    python3 chip_smoke.py --chips 4  # four chips: 4-shard mesh run vs one chip

Default phase: a seeded heavy-tailed graph (V=200k, average in-degree
~15, 128-wide features like ogbn-papers100M) in an on-disk store in the
paper's vertex order, GraphSAGE 128 -> 256 -> 256 (the hidden width 256
is assumed: the repository holds no published one) through
``AtlasSession`` with ``backend="pallas"`` — the ``edge_block_spmm``
kernel compiled for the chip — under a hot-store budget of a quarter of
the feature bytes, so eviction and reload really happen.  The final
layer is checked against ``dense_reference`` (mean-max-abs <= 1e-4),
published, and served rows are checked bit for bit against the spills.

``--chips 4``: only the multi-chip path and what it is compared with —
``DistSession(shards=4, exchange="mesh", workers="thread")`` on an
exact-arithmetic store, each shard's kernels on its own chip, against a
one-chip ``AtlasSession`` run, by ``np.array_equal``.

Exits non-zero, printing no result, when JAX finds no TPU or any check
fails.  On success the last line of stdout is one JSON object:
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

MAIN_VERTICES = 200_000
MAIN_DEGREE = 15  # the paper's average in-degree
MAIN_DIMS = [128, 256, 256]  # ogbn-papers100M width; hidden 256 assumed
# The kernel visits every (dst tile x edge block x src tile), so its
# work per chunk grows with the cube of the chunk's rows; 1 MiB (2048
# rows at d=128) keeps a layer within a minute on one v5e chip.
CHUNK_BYTES = 1 << 20
SERVE_BATCHES = 8
SERVE_BATCH = 1024

MESH_VERTICES = 40_000
MESH_DIM = 128
MESH_SHARDS = 4


class CompileCounter:
    """Counts XLA compile requests and the persistent-cache hits among
    them, through ``jax.monitoring`` (a compile request that hits the
    cache loads the executable instead of compiling it)."""

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

    def line(self) -> str:
        return (
            f"compiles: {self.requests} requests, {self.cache_hits} loaded "
            f"from the persistent cache, {self.requests - self.cache_hits} "
            f"compiled"
        )


def check(ok: bool, message: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {message}")


def run_main_path(workdir: str, vertices: int, backend: str, chunk_bytes: int,
                  seed: int = 0) -> None:
    """infer -> reference check -> publish -> serve, printing metrics."""
    import numpy as np

    from repro.core.atlas import AtlasConfig, spills_to_dense
    from repro.graphs.synth import make_features, powerlaw_graph
    from repro.models.gnn import dense_reference, init_gnn_params
    from repro.session import AtlasSession
    from repro.storage.layout import GraphStore

    t0 = time.perf_counter()
    csr = powerlaw_graph(vertices, MAIN_DEGREE, seed=seed + 1, self_loops=False)
    feats = make_features(vertices, MAIN_DIMS[0], seed=seed + 2)
    specs = init_gnn_params("sage", MAIN_DIMS, seed=seed + 3)
    store = GraphStore.create(
        os.path.join(workdir, "store"), csr, feats, num_partitions=8, order="at"
    )
    hot_bytes = feats.nbytes // 4
    print(f"graph: V={vertices} E={csr.num_edges} widths={MAIN_DIMS} "
          f"(sage) order={store.ordering_name}; features "
          f"{feats.nbytes} B, hot budget {hot_bytes} B, chunk {chunk_bytes} "
          f"B; store built in {time.perf_counter() - t0:.3f}s")

    cfg = AtlasConfig(backend=backend, chunk_bytes=chunk_bytes,
                      hot_bytes=hot_bytes)
    print(f"backend={cfg.backend} (compiled: {cfg.backend == 'pallas'})")
    with AtlasSession(store, config=cfg,
                      workdir=os.path.join(workdir, "work")) as session:
        t0 = time.perf_counter()
        result = session.infer(specs)
        print(f"infer: {time.perf_counter() - t0:.3f}s for "
              f"{len(specs)} layers")
        for m in result.metrics:
            print(f"  layer {m.layer}: seconds={m.seconds:.3f} "
                  f"aggregate_seconds={m.aggregate_seconds:.3f} "
                  f"h2d_seconds={m.h2d_seconds:.3f} chunks={m.chunks} "
                  f"evictions={m.evictions} reloads={m.reloads}")
            check(m.evictions > 0 and m.reloads > 0,
                  f"layer {m.layer} never evicted and reloaded")
        final = result.final
        # engine rows are in storage order; index by external id
        out = spills_to_dense(final.spills, vertices, final.dim)
        out = out[store.to_internal(np.arange(vertices))]

        t0 = time.perf_counter()
        ref = dense_reference(csr, feats, specs)
        err = float(np.abs(out - ref).max(axis=1).mean())
        print(f"mean-max-abs error vs dense_reference: {err!r} (bound 1e-4; "
              f"reference {time.perf_counter() - t0:.3f}s)")
        check(err <= 1e-4, f"error {err} above 1e-4")

        published = session.publish(final)
        rng = np.random.default_rng(seed + 4)
        with session.reader(final.layer, cache_bytes=8 << 20) as reader:
            for _ in range(SERVE_BATCHES):
                ids = rng.integers(0, vertices, size=SERVE_BATCH)
                rows = reader.lookup(ids)
                check(np.array_equal(rows, out[ids]),
                      "served rows differ from the spills")
        print(f"served {SERVE_BATCHES}x{SERVE_BATCH} lookups from version "
              f"v{published.epoch}: equal to the spills")


def run_mesh_path(workdir: str, vertices: int, backend: str, chunk_bytes: int,
                  shards: int = MESH_SHARDS, seed: int = 7) -> None:
    """4-shard mesh-exchange run vs a one-device run, bitwise."""
    import numpy as np

    from repro.core.atlas import AtlasConfig, spills_to_dense
    from repro.dist import DistSession
    from repro.exact import exact_graph_and_specs
    from repro.session import AtlasSession
    from repro.storage.layout import GraphStore

    csr, feats, specs = exact_graph_and_specs(
        vertices, MESH_DIM, kind="sage", seed=seed
    )
    store = GraphStore.create(
        os.path.join(workdir, "store"), csr, feats, num_partitions=4
    )
    dims = [MESH_DIM] + [s.out_dim for s in specs]
    cfg = AtlasConfig(backend=backend, chunk_bytes=chunk_bytes,
                      hot_slots=max(16, vertices // 8))
    print(f"exact graph: V={vertices} E={csr.num_edges} widths={dims} (sage), "
          f"chunk {chunk_bytes} B, hot slots {cfg.hot_slots}")

    t0 = time.perf_counter()
    with AtlasSession(store, config=cfg,
                      workdir=os.path.join(workdir, "single")) as single:
        ref = single.infer(specs)
        dense_ref = spills_to_dense(ref.final.spills, vertices, ref.final.dim)
    print(f"one device: {time.perf_counter() - t0:.3f}s")

    t0 = time.perf_counter()
    with DistSession(store, shards=shards, config=cfg, exchange="mesh",
                     workers="thread",
                     workdir=os.path.join(workdir, "dist")) as dist:
        result = dist.infer(specs)
        seconds = time.perf_counter() - t0
        dense = spills_to_dense(result.final.spills, vertices, result.final.dim)
        dist.publish(result.final)
        probe = np.arange(0, vertices, 97)
        with dist.reader(result.final.layer) as reader:
            served = reader.lookup(probe)
    print(f"{shards} shards, mesh exchange: {seconds:.3f}s")
    for layer, reports in sorted(result.shard_reports.items()):
        for r in reports:
            print(f"  layer {layer} shard {r['shard']}: device={r['device']} "
                  f"seconds={r['seconds']:.3f} chunks={r['chunks']} "
                  f"evictions={r['evictions']} reloads={r['reloads']} "
                  f"sent_bytes={r['exchange']['sent_bytes']}")
        devices = {r["device"] for r in reports}
        check(len(devices) == shards,
              f"layer {layer}: shards shared devices {sorted(devices)}")
    check(np.array_equal(dense, dense_ref),
          "sharded spills differ from the one-device run")
    check(np.array_equal(served, dense_ref[probe]),
          "served rows differ from the one-device run")
    print("sharded spills and served rows: bitwise equal to one device")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=[1, 4], default=1,
                    help="4: run only the 4-shard mesh-exchange phase")
    args = ap.parse_args(argv)

    from repro.util.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU — JAX found {len(devices)} "
              f"{dev.platform} device(s) ({dev.device_kind}); this test runs "
              f"only on the chip", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"chips, JAX found {len(devices)}", file=sys.stderr)
        return 1
    compiles = CompileCounter()
    print(f"device: {dev.device_kind} x{len(devices)} (platform "
          f"{dev.platform}); compile cache: {cache_dir}")

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as td:
        if args.chips == 4:
            run_mesh_path(td, MESH_VERTICES, "pallas", CHUNK_BYTES)
        else:
            run_main_path(td, MAIN_VERTICES, "pallas", CHUNK_BYTES)
    print(compiles.line())
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
