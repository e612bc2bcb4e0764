"""Sharded out-of-core inference (repro.dist) == single-machine session.

Every comparison here is ``np.array_equal`` on an exact-arithmetic graph
(``repro.exact``): power-of-four in-degrees make each edge weight a power
of two, integer features/weights keep every partial sum inside fp32's
mantissa — so the N-shard run with cross-shard message routing must
reproduce the single-machine spills and served rows **bitwise**.  A
tolerance would hide routing/namespace bugs; equality cannot.

Mesh-exchange runs need the placeholder device count set before jax
init, so they execute in a subprocess via the infer_dist CLI.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core.atlas import AtlasConfig, spills_to_dense
from repro.dist import DistRunManifest, DistSession, DistWorkerError
from repro.exact import exact_graph_and_specs
from repro.session import AtlasSession, StaleManifestError
from repro.storage.layout import GraphStore

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def exact_store(tmp_path, v=1500, d=8, kind="gcn", seed=7):
    csr, feats, specs = exact_graph_and_specs(v, d, kind=kind, seed=seed)
    store = GraphStore.create(
        str(tmp_path / "store"), csr, feats, num_partitions=4
    )
    return store, specs


def dist_cfg(**kw):
    # small chunks + tight hot store so shards really stream, evict,
    # and reload instead of resolving everything in RAM
    kw.setdefault("chunk_bytes", 1 << 14)
    kw.setdefault("hot_slots", 96)
    return AtlasConfig(**kw)


def single_machine_dense(tmp_path, store, specs, tag="single"):
    with AtlasSession(
        store, config=dist_cfg(), workdir=str(tmp_path / tag)
    ) as session:
        res = session.infer(specs)
        return spills_to_dense(
            res.final.spills, store.num_vertices, res.final.dim
        )


# --------------------------------------------------------------------------
# shard-count sweep: spills and served rows bitwise equal to single-machine
# --------------------------------------------------------------------------


@pytest.mark.parametrize("kind", ["gcn", "sage"])
def test_shard_sweep_bit_identity(tmp_path, kind):
    """1-, 2- and 4-shard thread-mode runs reproduce the single-machine
    spills bit for bit, and an unmodified session reader serves the
    published merged result by external id."""
    store, specs = exact_store(tmp_path, kind=kind)
    v = store.num_vertices
    ref = single_machine_dense(tmp_path, store, specs)
    probe = np.arange(0, v, 61)
    for shards in (1, 2, 4):
        with DistSession(
            store, shards=shards, config=dist_cfg(),
            workdir=str(tmp_path / f"dist{shards}"), workers="thread",
        ) as dist:
            result = dist.infer(specs)
            dense = spills_to_dense(result.final.spills, v, result.final.dim)
            assert np.array_equal(dense, ref), (
                f"{kind} shards={shards}: spills diverged"
            )
            # per-layer reports: one per shard, rows summing to V
            for l, reports in result.shard_reports.items():
                assert len(reports) == shards
                assert sum(r["rows"] for r in reports) == v
            version = dist.publish(result.final)
            with dist.reader(result.final.layer) as reader:
                assert np.array_equal(reader.lookup(probe), ref[probe])
            assert version.epoch in store.servable_versions(result.final.layer)


def test_dist_session_refuses_sagepool_by_name(tmp_path):
    """The shards add remote partials, so a max aggregation is refused,
    naming the kind, before any layer work — not returned as a sum."""
    from repro.graphs.synth import make_features, powerlaw_graph
    from repro.models.gnn import init_gnn_params

    csr = powerlaw_graph(200, 4, seed=3)
    store = GraphStore.create(str(tmp_path / "store"), csr,
                              make_features(200, 8, seed=3), num_partitions=2)
    specs = init_gnn_params("sagepool", [8, 4], seed=1)
    with DistSession(store, shards=2, config=dist_cfg(),
                     workdir=str(tmp_path / "dist"), workers="thread") as dist:
        with pytest.raises(ValueError, match="layer kind 'sagepool'"):
            dist.infer(specs)
    assert not os.path.exists(tmp_path / "dist" / "layer_1")


def test_multi_shard_runs_exchange_real_traffic(tmp_path):
    """The ring-offset exact graph has cross-boundary edges, so 2-shard
    runs must route real bytes through the exchange — guards against a
    'bit-identical because nothing was distributed' false pass."""
    store, specs = exact_store(tmp_path)
    with DistSession(
        store, shards=2, config=dist_cfg(),
        workdir=str(tmp_path / "dist"), workers="thread",
    ) as dist:
        result = dist.infer(specs)
    sent = sum(
        r["exchange"]["sent_bytes"]
        for reports in result.shard_reports.values()
        for r in reports
    )
    recv = sum(
        r["exchange"]["recv_bytes"]
        for reports in result.shard_reports.values()
        for r in reports
    )
    assert sent > 0 and recv > 0
    assert sent == recv  # every posted bucket collected exactly once


# --------------------------------------------------------------------------
# failure model: death mid-layer leaves the manifest un-advanced
# --------------------------------------------------------------------------


def test_worker_death_keeps_manifest_unadvanced_and_resume_replays(tmp_path):
    """Kill shard 1 between its exchange post and collect in layer 2:
    every worker fails fast (abort marker), the dist manifest still
    records only layer 1, and a fresh session's ``infer(resume=True)``
    replays from layer 2 to a bit-identical result."""
    store, specs = exact_store(tmp_path, kind="sage")
    v = store.num_vertices
    ref = single_machine_dense(tmp_path, store, specs)
    workdir = str(tmp_path / "dist")

    def die_in_layer_1(shard, layer, phase):
        if shard == 1 and layer == 1 and phase == "post":
            raise RuntimeError("injected worker death")

    with DistSession(
        store, shards=2, config=dist_cfg(), workdir=workdir,
        workers="thread", exchange_timeout_s=30.0,
    ) as dist:
        with pytest.raises(DistWorkerError) as ei:
            dist.infer(specs, fault=die_in_layer_1)
        assert ei.value.shard == 1 and ei.value.layer == 1
        manifest = DistRunManifest.load(dist.run_manifest_path)
        assert manifest.completed_layers == 1  # layer 2 never committed
        for p in (
            path for by in manifest.spills.values()
            for paths in by.values() for path in paths
        ):
            assert os.path.exists(p)  # committed layer's spills durable
    # crash recovery: a brand-new session over the same workdir
    with DistSession(
        store, shards=2, config=dist_cfg(), workdir=workdir,
        workers="thread",
    ) as dist:
        result = dist.infer(specs, resume=True)
        dense = spills_to_dense(result.final.spills, v, result.final.dim)
        assert np.array_equal(dense, ref)
        # only the incomplete layers re-ran
        assert sorted(result.shard_reports) == [2]


def test_resume_validation_rejects_stale_manifests(tmp_path):
    store, specs = exact_store(tmp_path, v=600)
    workdir = str(tmp_path / "dist")
    with DistSession(
        store, shards=2, config=dist_cfg(), workdir=workdir, workers="thread"
    ) as dist:
        dist.infer(specs)
        path = dist.run_manifest_path
    dims = [s.out_dim for s in specs]

    def reload():
        return DistRunManifest.load(path)

    ok = reload()
    ok.validate_resume(path, store.num_vertices, 2, dims,
                       store_digest=store.ordering_digest)
    with pytest.raises(StaleManifestError, match="shard count|shards"):
        reload().validate_resume(path, store.num_vertices, 4, dims,
                                 store_digest=store.ordering_digest)
    with pytest.raises(StaleManifestError, match="vertices"):
        reload().validate_resume(path, store.num_vertices + 1, 2, dims,
                                 store_digest=store.ordering_digest)
    with pytest.raises(StaleManifestError, match="digest"):
        reload().validate_resume(path, store.num_vertices, 2, dims,
                                 store_ordering="at", store_digest="bogus")
    with pytest.raises(StaleManifestError, match="layer dims"):
        reload().validate_resume(path, store.num_vertices, 2, dims[:-1],
                                 store_digest=store.ordering_digest)
    # a completed layer whose spill files vanished is not resumable
    m = reload()
    victim = m.spills[m.completed_layers][0][0]
    os.remove(victim)
    with pytest.raises(StaleManifestError, match="missing"):
        reload().validate_resume(path, store.num_vertices, 2, dims,
                                 store_digest=store.ordering_digest)
    # resuming under a different shard count from the session API
    with DistSession(
        store, shards=4, config=dist_cfg(), workdir=workdir, workers="thread"
    ) as dist:
        with pytest.raises(StaleManifestError):
            dist.infer(specs, resume=True)


def test_manifest_schema_version_gate(tmp_path):
    path = str(tmp_path / "m.json")
    m = DistRunManifest(num_vertices=10, num_layers=2, num_shards=2)
    m.save(path)
    data = json.load(open(path))
    data["schema_version"] = 999
    json.dump(data, open(path, "w"))
    with pytest.raises(StaleManifestError, match="schema_version"):
        DistRunManifest.load(path)


# --------------------------------------------------------------------------
# process workers + mesh exchange (subprocess: jax device count env)
# --------------------------------------------------------------------------


def run_cli(extra, env_extra=None, timeout=600):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.update(env_extra or {})
    cmd = [sys.executable, "-m", "repro.launch.infer_dist", *extra]
    r = subprocess.run(cmd, capture_output=True, text=True, env=env,
                       cwd=REPO, timeout=timeout)
    assert r.returncode == 0, f"\nstdout:{r.stdout}\nstderr:{r.stderr[-3000:]}"
    return json.loads(r.stdout[r.stdout.index("{"):])


@pytest.mark.parametrize("kind", ["gcn", "sage"])
def test_process_mode_2proc_smoke(kind):
    """2 shard worker processes per layer, file-backed exchange, full
    driver: infer -> publish -> serve -> bitwise check vs single-machine
    (the CLI exits nonzero on any mismatch)."""
    report = run_cli([
        "--vertices", "1200", "--feat-dim", "8", "--kind", kind,
        "--shards", "2", "--workers", "process",
        "--chunk-bytes", str(1 << 14), "--hot-slots", "96",
    ])
    assert report["bit_identical"] and report["served_identical"]
    assert report["shards"] == 2


def test_mesh_exchange_bit_identity():
    """Cross-shard routing through jax.lax.all_to_all under shard_map
    (2 host-platform devices) is pure data movement: still bitwise equal
    to the single-machine run."""
    report = run_cli(
        [
            "--vertices", "1000", "--feat-dim", "8", "--kind", "gcn",
            "--shards", "2", "--workers", "thread", "--exchange", "mesh",
            "--chunk-bytes", str(1 << 14), "--hot-slots", "96",
        ],
        env_extra={"XLA_FLAGS": "--xla_force_host_platform_device_count=2"},
    )
    assert report["bit_identical"] and report["served_identical"]


def test_mesh_exchange_places_each_shard_on_its_own_device():
    """4 shards on 4 host-platform devices with a JAX backend: shard s
    aggregates on device s, and the result still equals the one-device
    run of the same backend bitwise."""
    report = run_cli(
        [
            "--vertices", "1000", "--feat-dim", "8", "--kind", "sage",
            "--shards", "4", "--workers", "thread", "--exchange", "mesh",
            "--backend", "pallas-interpret",
            "--chunk-bytes", str(1 << 14), "--hot-slots", "96",
        ],
        env_extra={"XLA_FLAGS": "--xla_force_host_platform_device_count=4"},
    )
    assert report["bit_identical"] and report["served_identical"]
    for reports in report["shard_reports"].values():
        assert len({r["device"] for r in reports}) == 4


@pytest.mark.parametrize("backend", ["jax", "pallas", "pallas-interpret"])
def test_process_workers_refuse_jax_backends_before_spawning(
    tmp_path, monkeypatch, backend
):
    """A chip belongs to one process: process workers under a JAX backend
    are refused in the constructor, before any worker starts."""
    store, _ = exact_store(tmp_path, v=200)

    def no_spawn(*a, **kw):
        raise AssertionError("a worker process was spawned")

    monkeypatch.setattr(subprocess, "Popen", no_spawn)
    with pytest.raises(ValueError, match="chip belongs to one process"):
        DistSession(store, shards=2, workers="process",
                    config=dist_cfg(backend=backend))


def test_bench_delivery_dist_refuses_process_workers_with_jax_backend():
    """``bench_delivery --mode dist --backend pallas`` with process workers
    fails the same way, before its single-machine leg runs."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_delivery", os.path.join(REPO, "benchmarks", "bench_delivery.py")
    )
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    with pytest.raises(ValueError, match="chip belongs to one process"):
        bench.run_dist_check(400, 8, "gcn", 2, "process", 64, seed=7,
                             backend="pallas")
