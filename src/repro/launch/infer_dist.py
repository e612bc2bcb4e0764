"""Sharded out-of-core inference driver (and shard-worker entry point).

Coordinator mode (default): build or open a store, run a
``repro.dist.DistSession`` over it, publish the final layer, spot-check
served rows, and — unless ``--no-check`` — verify bit-identity against
the single-machine ``AtlasSession`` on the same graph::

    PYTHONPATH=src python -m repro.launch.infer_dist \
        --vertices 20000 --shards 2 --workers process --kind sage

With a JAX ``--backend`` (thread workers only: a chip belongs to one
process), shard ``s`` aggregates on ``jax.devices()[s]`` and the
single-machine check runs the same backend on one device.

Worker mode (``--worker``): one shard of one layer, spawned per layer by
the process-mode coordinator.  Streams the shard's source range, routes
cross-shard buckets through the file-backed ``LocalExchange``, barriers
its own write-back scheduler, and reports a JSON result file; any
failure exits nonzero after flagging the exchange abort marker so peers
fail fast instead of timing out.
"""

from __future__ import annotations

import argparse
import json
import os
import pickle
import tempfile
import traceback


def _worker_main(args) -> int:
    import numpy as np  # noqa: F401 — keep imports inside worker for fast --help

    from repro.core.atlas import AtlasConfig
    from repro.dist.exchange import LocalExchange
    from repro.dist.partition import ShardPlan
    from repro.dist.session import DistRunManifest
    from repro.dist.worker import run_shard_layer
    from repro.graphs.csr import degrees_from_csr
    from repro.obs.trace import Tracer
    from repro.storage.layout import GraphStore
    from repro.storage.spill import SpillFile, SpillSet
    from repro.util.compile_cache import enable_compile_cache

    enable_compile_cache()
    exchange = LocalExchange(
        args.exchange_root, args.shards, timeout_s=args.exchange_timeout
    )
    try:
        store = GraphStore.open(args.store)
        manifest = DistRunManifest.load(args.manifest)
        with open(args.specs, "rb") as f:
            specs = pickle.load(f)
        cfg = AtlasConfig(**json.loads(args.config_json))
        plan = ShardPlan(
            store.num_vertices, args.shards,
            store_digest=store.ordering_digest,
        )
        plan.validate_store(store)
        csr = store.topology()
        in_deg, _ = degrees_from_csr(csr)
        layer = args.layer
        if layer == 0:
            spills = store.layer0_spills()
        else:
            spills = SpillSet()
            for p in manifest.spills[layer][args.shard]:
                spills.add(SpillFile.open(p))
        tracer = Tracer() if args.trace else None
        layer_spills, info = run_shard_layer(
            csr, in_deg, spills, specs[layer], args.out_dir, layer,
            args.shard, plan, exchange, config=cfg, tracer=tracer,
        )
        if args.trace:
            tracer.export(args.trace)
        tmp = args.result + ".tmp"
        with open(tmp, "w") as f:
            json.dump(info, f, indent=2)
        os.replace(tmp, args.result)
        return 0
    except BaseException as e:  # noqa: BLE001 — worker boundary
        # flag the abort before dying so peer collect() polls fail fast
        try:
            exchange.abort(
                f"shard {args.shard} layer {args.layer}: "
                f"{type(e).__name__}: {e}"
            )
        except BaseException:
            pass
        traceback.print_exc()
        return 1


def _coordinator_main(args) -> int:
    import numpy as np

    from repro.core.atlas import AtlasConfig, spills_to_dense
    from repro.dist.session import DistSession
    from repro.exact import exact_graph_and_specs
    from repro.session import AtlasSession
    from repro.storage.layout import GraphStore
    from repro.util.compile_cache import enable_compile_cache

    enable_compile_cache()
    with tempfile.TemporaryDirectory() as td:
        workdir = args.workdir or td
        csr, feats, specs = exact_graph_and_specs(
            args.vertices, args.feat_dim, kind=args.kind, seed=args.seed
        )
        store = GraphStore.create(
            os.path.join(workdir, "store"), csr, feats, num_partitions=4
        )
        cfg = AtlasConfig(
            chunk_bytes=args.chunk_bytes, hot_slots=args.hot_slots,
            backend=args.backend, trace=args.trace,
        )
        with DistSession(
            store, shards=args.shards, config=cfg, exchange=args.exchange,
            workers=args.workers, workdir=os.path.join(workdir, "dist"),
        ) as dist:
            result = dist.infer(specs)
            dense_dist = spills_to_dense(
                result.final.spills, store.num_vertices, result.final.dim
            )
            version = dist.publish(result.final)
            with dist.reader(result.final.layer) as reader:
                probe = np.arange(0, store.num_vertices, 97)
                served = reader.lookup(probe)
        report = {
            "vertices": store.num_vertices,
            "shards": args.shards,
            "workers": args.workers,
            "exchange": args.exchange,
            "layers": len(specs),
            "epoch": version.epoch,
            "served_rows": int(len(served)),
            "shard_reports": result.shard_reports,
        }
        if not args.no_check:
            with AtlasSession(
                store, config=AtlasConfig(
                    chunk_bytes=args.chunk_bytes, hot_slots=args.hot_slots,
                    backend=args.backend,
                ),
                workdir=os.path.join(workdir, "single"),
            ) as single:
                ref = single.infer(specs)
                dense_ref = spills_to_dense(
                    ref.final.spills, store.num_vertices, ref.final.dim
                )
            identical = bool(np.array_equal(dense_dist, dense_ref))
            served_ok = bool(np.array_equal(served, dense_ref[probe]))
            report["bit_identical"] = identical
            report["served_identical"] = served_ok
            if not (identical and served_ok):
                print(json.dumps(report, indent=2))
                print("FAIL: dist output differs from single-machine run")
                return 1
        print(json.dumps(report, indent=2))
        return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--worker", action="store_true", help="shard-worker mode")
    # worker-mode arguments (supplied by the coordinator)
    ap.add_argument("--store", help="graph store root")
    ap.add_argument("--manifest", help="dist run manifest path")
    ap.add_argument("--specs", help="pickled layer-spec stack")
    ap.add_argument("--config-json", help="AtlasConfig as JSON")
    ap.add_argument("--layer", type=int, default=0)
    ap.add_argument("--shard", type=int, default=0)
    ap.add_argument("--shards", type=int, default=2)
    ap.add_argument("--out-dir", help="shard output directory")
    ap.add_argument("--exchange-root", help="LocalExchange directory")
    ap.add_argument("--exchange-timeout", type=float, default=120.0)
    ap.add_argument("--result", help="worker result JSON path")
    # coordinator-mode arguments
    ap.add_argument("--vertices", type=int, default=20_000)
    ap.add_argument("--feat-dim", type=int, default=16)
    ap.add_argument("--kind", choices=["gcn", "sage"], default="gcn")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--exchange", choices=["local", "mesh"], default="local")
    ap.add_argument("--workers", choices=["thread", "process"], default="process")
    ap.add_argument("--backend", default="numpy",
                    choices=["numpy", "jax", "pallas", "pallas-interpret"],
                    help="chunk aggregation; JAX backends need --workers thread")
    ap.add_argument("--chunk-bytes", type=int, default=1 << 20)
    ap.add_argument("--hot-slots", type=int, default=None)
    ap.add_argument("--workdir", default=None, help="keep run state here")
    ap.add_argument("--trace", default=None,
                    help="worker: trace output path; coordinator: any value enables tracing")
    ap.add_argument("--no-check", action="store_true",
                    help="skip the single-machine bit-identity check")
    args = ap.parse_args(argv)
    if args.worker:
        return _worker_main(args)
    return _coordinator_main(args)


if __name__ == "__main__":
    raise SystemExit(main())
