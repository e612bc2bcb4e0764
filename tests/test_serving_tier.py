"""Multi-process zero-copy serving tier (ISSUE 10): the mmap fast path
vs the page-cache bit-identity oracle, cross-process pin leases
(publish/GC honoring leases from other processes, stale-lease reaping),
the weakref reader backstop, cache-counter metrics export, and the
batching ``ServingFrontend``."""

import gc
import multiprocessing
import os
import threading
import time

import numpy as np
import pytest

from repro.graphs.synth import make_features, powerlaw_graph
from repro.obs.metrics import Histogram, MetricsRegistry
from repro.serve_gnn.leases import (
    PinLease,
    lease_dir,
    list_leases,
    live_leases,
    pid_alive,
    reap_stale,
)
from repro.serve_gnn.page_cache import ShardedPageCache
from repro.serving.frontend import ServingFrontend
from repro.session import AtlasSession
from repro.storage.layout import GraphStore

from tests.test_session import scattered_spillset, serving_session

SERVE_LAYER = 1


# --------------------------------------------------------------------------
# Zero-copy fast path: bit identity against the page-cache oracle
# --------------------------------------------------------------------------


@pytest.mark.parametrize(
    "block_rows,rows_per_file", [(64, None), (32, 100), (128, 333)]
)
def test_fast_path_bit_identity_grid(tmp_path, block_rows, rows_per_file):
    """Every layout: mmap-gathered rows == page-cache-decoded rows ==
    the source rows, for duplicated/unsorted/full-scan requests."""
    v, d = 700, 12
    rng = np.random.default_rng(block_rows)
    with serving_session(tmp_path, v) as session:
        ss, rows = scattered_spillset(tmp_path, rng, v, d, 4)
        session.publish(SERVE_LAYER, spills=ss, block_rows=block_rows,
                        rows_per_file=rows_per_file)
        with session.reader(SERVE_LAYER, fast_path=True) as fast, \
                session.reader(
                    SERVE_LAYER, fast_path=False, cache_bytes=1 << 20
                ) as oracle:
            assert fast.fast_path and fast.cache is None
            assert not oracle.fast_path
            for size in (1, 7, 64, 300):
                q = rng.integers(0, v, size=size)
                q[::3] = q[0]  # duplicates
                got, ref = fast.lookup(q), oracle.lookup(q)
                assert got.tobytes() == ref.tobytes()
                assert np.array_equal(got, rows[q])
            full = np.arange(v, dtype=np.uint64)
            assert np.array_equal(fast.lookup(full), rows)
            assert fast.mmap_gathers > 0 and fast.blocks_read == 0
            assert fast.snapshot()["fast_path"] is True


def test_fast_path_missing_ids_raise(tmp_path):
    v = 200
    rng = np.random.default_rng(0)
    with serving_session(tmp_path, v) as session:
        # only even ids present: in-range gaps + beyond-range misses
        ids = np.arange(0, v, 2, dtype=np.uint64)
        rows = rng.standard_normal((len(ids), 4)).astype(np.float32)
        from repro.storage.spill import SpillSet, write_spill
        ss = SpillSet()
        ss.add(write_spill(str(tmp_path / "even.spill"), ids, rows,
                           block_rows=16))
        session.publish(SERVE_LAYER, spills=ss, block_rows=16)
        with session.reader(SERVE_LAYER, fast_path=True) as fast:
            assert np.array_equal(fast.lookup(ids[:10]), rows[:10])
            with pytest.raises(KeyError):
                fast.lookup(np.array([1], dtype=np.uint64))  # gap
            with pytest.raises(KeyError):
                fast.lookup(np.array([v + 5], dtype=np.uint64))  # beyond


def test_fast_path_external_ids(tmp_path):
    """Reordered store: the mmap path translates external ids through
    the permutation sidecar exactly like the oracle."""
    v, d = 400, 8
    csr = powerlaw_graph(v, 6, seed=3)
    feats = make_features(v, d, seed=3)
    store = GraphStore.create(
        str(tmp_path / "store"), csr, feats, num_partitions=2,
        order="rnd", order_seed=1,
    )
    with AtlasSession(store, workdir=str(tmp_path / "run")) as session:
        session.publish(SERVE_LAYER, spills=store.layer0_spills(),
                        block_rows=64)
        q = np.random.default_rng(4).integers(0, v, size=150)
        with session.reader(SERVE_LAYER, fast_path=True) as fast, \
                session.reader(SERVE_LAYER, fast_path=False) as oracle:
            assert np.array_equal(fast.lookup(q), oracle.lookup(q))
            assert np.array_equal(fast.lookup(q), feats[q])


def test_reader_fast_path_auto_selection(tmp_path):
    """"auto" serves from mmaps iff the version's rows fit the budget
    and no explicit cache object was handed in."""
    v, d = 300, 8
    rng = np.random.default_rng(1)
    with serving_session(tmp_path, v) as session:
        ss, _ = scattered_spillset(tmp_path, rng, v, d, 3)
        session.publish(SERVE_LAYER, spills=ss, block_rows=64)
        data = v * d * 4
        with session.reader(SERVE_LAYER, cache_bytes=data + 1024) as r:
            assert r.fast_path and r.cache is None  # fits: mmap path
        with session.reader(SERVE_LAYER, cache_bytes=data // 4) as r:
            assert not r.fast_path and r.cache is not None  # too big
        with session.reader(SERVE_LAYER) as r:
            assert not r.fast_path  # no budget given: stay on the oracle
        with session.reader(
            SERVE_LAYER, cache_bytes=data * 2, fast_path=False
        ) as r:
            assert not r.fast_path and r.cache is not None  # explicit wins
        shared = ShardedPageCache(64, 1 << 20)
        with session.reader(SERVE_LAYER, cache=shared) as r:
            assert not r.fast_path  # explicit cache object: page-cache path
        with pytest.raises(ValueError):
            session.reader(SERVE_LAYER, cache=shared, fast_path=True)


def test_cache_metrics_registry_export(tmp_path):
    v, d = 400, 8
    rng = np.random.default_rng(2)
    registry = MetricsRegistry()
    with serving_session(tmp_path, v) as session:
        ss, rows = scattered_spillset(tmp_path, rng, v, d, 3)
        session.publish(SERVE_LAYER, spills=ss, block_rows=64)
        with session.reader(
            SERVE_LAYER, cache_bytes=1 << 20, fast_path=False,
            metrics=registry,
        ) as r:
            q = rng.integers(0, v, size=128)
            r.lookup(q)  # cold: misses
            r.lookup(q)  # warm: hits
            assert np.array_equal(r.lookup(q), rows[q])
        snap = registry.snapshot()["serve"]["cache"]
        assert snap["misses"] > 0 and snap["hits"] > 0
        assert snap["resident_bytes"]["value"] > 0
        assert snap["resident_blocks"]["value"] > 0
        # registry counters mirror the cache's own
        assert snap["hits"] == r.cache.hits
        assert snap["misses"] == r.cache.misses


# --------------------------------------------------------------------------
# Cross-process pin leases
# --------------------------------------------------------------------------


def _pin_worker(store_root, ready, release, conn):
    """Child process: pin the current version via its own session, hold
    it across the parent's re-publish + GC, verify the pinned rows never
    change, then release."""
    out = {"error": None}
    try:
        with AtlasSession(store_root, lease_ttl=60.0) as session:
            with session.reader(SERVE_LAYER, fast_path=True) as reader:
                q = np.arange(0, 50, dtype=np.uint64)
                before = reader.lookup(q)
                out["version"] = int(reader.version)
                ready.set()
                if not release.wait(timeout=60):
                    raise TimeoutError("parent never released")
                after = reader.lookup(q)
                out["stable"] = bool(np.array_equal(before, after))
    except BaseException as e:  # noqa: BLE001 - report to parent
        out["error"] = f"{type(e).__name__}: {e}"
    conn.send(out)
    conn.close()


def test_child_process_pin_survives_publish_and_gc(tmp_path):
    """Acceptance: a version pinned by a reader in another process
    survives the parent's publish+GC, and is collected after release."""
    v, d = 300, 8
    rng = np.random.default_rng(7)
    with serving_session(tmp_path, v) as session:
        ss1, _ = scattered_spillset(tmp_path, rng, v, d, 3, tag="a")
        pub1 = session.publish(SERVE_LAYER, spills=ss1, block_rows=64)

        ctx = multiprocessing.get_context("fork")
        ready, release = ctx.Event(), ctx.Event()
        parent_conn, child_conn = ctx.Pipe(duplex=False)
        p = ctx.Process(
            target=_pin_worker,
            args=(session.store.root, ready, release, child_conn),
            daemon=True,
        )
        p.start()
        child_conn.close()
        assert ready.wait(timeout=60), "child never pinned"

        # re-publish: GC must skip v1 — it is pinned only by the CHILD
        # process's lease (this session holds no pin on it)
        ss2, _ = scattered_spillset(tmp_path, rng, v, d, 3, tag="b",
                                    shift=1.0)
        pub2 = session.publish(SERVE_LAYER, spills=ss2, block_rows=64)
        assert pub1.epoch not in pub2.gc_removed
        assert os.path.isdir(pub1.dir)
        assert pub1.epoch in session.store.servable_versions(SERVE_LAYER)
        assert live_leases(pub1.dir, ttl=60.0)

        release.set()
        report = parent_conn.recv()
        p.join(timeout=60)
        assert report["error"] is None, report["error"]
        assert report["version"] == pub1.epoch
        assert report["stable"], "pinned rows changed under the child"

        # child released its lease: v1 is collectable now
        assert session.gc(SERVE_LAYER) == [pub1.epoch]
        assert not os.path.exists(pub1.dir)


def test_dead_pid_lease_reaped_after_ttl(tmp_path):
    """A lease from a dead process protects its version until the TTL
    expires, then is reaped and the version collected."""
    v, d = 200, 8
    rng = np.random.default_rng(8)
    # a genuinely dead pid: a forked child that already exited
    ctx = multiprocessing.get_context("fork")
    proc = ctx.Process(target=os.getpid, daemon=True)
    proc.start()
    proc.join()
    dead_pid = proc.pid
    assert not pid_alive(dead_pid)

    ttl = 30.0
    with serving_session(tmp_path, v, lease_ttl=ttl) as session:
        ss1, _ = scattered_spillset(tmp_path, rng, v, d, 2, tag="a")
        pub1 = session.publish(SERVE_LAYER, spills=ss1, block_rows=64)
        lease = PinLease(pub1.dir, ttl=ttl, heartbeat=False, pid=dead_pid)

        ss2, _ = scattered_spillset(tmp_path, rng, v, d, 2, tag="b",
                                    shift=1.0)
        # fresh mtime + dead pid: NOT stale yet (TTL guards pid-observed-
        # mid-exit races) — publish-time GC keeps v1
        pub2 = session.publish(SERVE_LAYER, spills=ss2, block_rows=64)
        assert pub1.epoch not in pub2.gc_removed
        assert os.path.isdir(pub1.dir)

        # age the heartbeat past the TTL: stale (old mtime AND dead pid)
        old = time.time() - ttl - 5.0
        os.utime(lease.path, (old, old))
        assert session.gc(SERVE_LAYER) == [pub1.epoch]
        assert not os.path.exists(pub1.dir)


def test_live_pid_lease_never_reaped(tmp_path):
    """A stale heartbeat alone never loses the lease while its process
    is alive — only mtime+dead-pid does."""
    v, d = 150, 4
    rng = np.random.default_rng(9)
    ttl = 30.0
    with serving_session(tmp_path, v, lease_ttl=ttl) as session:
        ss1, _ = scattered_spillset(tmp_path, rng, v, d, 2, tag="a")
        pub1 = session.publish(SERVE_LAYER, spills=ss1, block_rows=64)
        # our own (live) pid, no heartbeat, mtime aged way past the TTL
        lease = PinLease(pub1.dir, ttl=ttl, heartbeat=False)
        old = time.time() - ttl * 10
        os.utime(lease.path, (old, old))

        ss2, _ = scattered_spillset(tmp_path, rng, v, d, 2, tag="b",
                                    shift=1.0)
        pub2 = session.publish(SERVE_LAYER, spills=ss2, block_rows=64)
        assert pub1.epoch not in pub2.gc_removed
        assert reap_stale(pub1.dir, ttl=ttl) == []
        assert len(list_leases(pub1.dir)) == 1

        lease.release()
        assert session.gc(SERVE_LAYER) == [pub1.epoch]


def test_reader_lease_lifecycle(tmp_path):
    """Opening a reader drops a heartbeated lease file in the version
    dir; close removes it."""
    v, d = 150, 4
    rng = np.random.default_rng(10)
    with serving_session(tmp_path, v) as session:
        ss, _ = scattered_spillset(tmp_path, rng, v, d, 2)
        pub = session.publish(SERVE_LAYER, spills=ss, block_rows=64)
        r = session.reader(SERVE_LAYER)
        leases = list_leases(pub.dir)
        assert len(leases) == 1 and leases[0].pid == os.getpid()
        assert os.path.dirname(leases[0].path) == lease_dir(pub.dir)
        r.close()
        assert list_leases(pub.dir) == []
        r.close()  # idempotent


def test_leaked_reader_unpinned_by_finalizer(tmp_path):
    """A reader dropped without close() releases its pin and lease when
    the garbage collector reclaims it — it cannot pin a version forever."""
    v, d = 200, 8
    rng = np.random.default_rng(11)
    with serving_session(tmp_path, v) as session:
        ss1, _ = scattered_spillset(tmp_path, rng, v, d, 2, tag="a")
        pub1 = session.publish(SERVE_LAYER, spills=ss1, block_rows=64)
        r = session.reader(SERVE_LAYER, fast_path=True)
        lease_path = r._lease.path
        assert session.pinned_versions(SERVE_LAYER) == {pub1.epoch: 1}

        del r  # leaked: no close()
        gc.collect()
        assert not os.path.exists(lease_path)
        assert session.pinned_versions(SERVE_LAYER) == {}

        ss2, _ = scattered_spillset(tmp_path, rng, v, d, 2, tag="b",
                                    shift=1.0)
        pub2 = session.publish(SERVE_LAYER, spills=ss2, block_rows=64)
        assert pub1.epoch in pub2.gc_removed


def test_reload_manifest_never_clobbers_inflight_publish(tmp_path):
    """Regression: ``reader()`` re-reads the store manifest from disk
    (cross-process publish visibility) while a same-process publish is
    mutating it under only the publish lock.  An unserialized reload used
    to swap ``store.manifest`` mid-commit, stranding the commit's version
    entry on the orphaned dict — the saved manifest then lost the epoch,
    ``next_epoch`` regressed, and a later publish *reused* the epoch
    number, re-landing different rows under pinned readers' mmaps.
    Epoch monotonicity + per-version row stability must hold under a
    reader-churn/publish race."""
    v, d = 500, 8
    rng = np.random.default_rng(12)
    with serving_session(tmp_path, v) as session:
        sets, refs = [], []
        for k in range(2):
            ss, rows = scattered_spillset(
                tmp_path, rng, v, d, 3, tag=f"m{k}", shift=float(k)
            )
            sets.append(ss)
            refs.append(rows)
        session.publish(SERVE_LAYER, spills=sets[0], block_rows=64,
                        rows_per_file=128)
        stop = threading.Event()
        errors: list[str] = []

        def churn(ti):
            lrng = np.random.default_rng(100 + ti)
            try:
                while not stop.is_set():
                    # every open runs reload_manifest against the
                    # publisher's commit section
                    with session.reader(
                        SERVE_LAYER, cache_bytes=64 << 20
                    ) as r:
                        q = lrng.integers(0, v, size=32)
                        exp = refs[(r.version - 1) % 2][q]
                        if not np.array_equal(r.lookup(q), exp):
                            errors.append(f"diverged at v{r.version}")
                            stop.set()
            except Exception as e:  # noqa: BLE001 - surfaced below
                errors.append(f"reader {ti}: {type(e).__name__}: {e}")
                stop.set()

        threads = [
            threading.Thread(target=churn, args=(ti,)) for ti in range(4)
        ]
        for t in threads:
            t.start()
        last = 1
        try:
            for i in range(1, 80):
                if stop.is_set():
                    break
                pub = session.publish(
                    SERVE_LAYER, spills=sets[i % 2], block_rows=64,
                    rows_per_file=128,
                )
                assert pub.epoch > last, (
                    f"epoch reuse: v{pub.epoch} published after v{last}"
                )
                last = pub.epoch
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=60)
        assert not errors, errors
        assert last == 80


# --------------------------------------------------------------------------
# Histogram cross-process state
# --------------------------------------------------------------------------


def test_histogram_state_roundtrip_and_merge():
    rng = np.random.default_rng(12)
    a, b = Histogram(), Histogram()
    for x in rng.exponential(0.01, size=200):
        a.observe(float(x))
    for x in rng.exponential(0.10, size=100):
        b.observe(float(x))
    restored = Histogram.from_state(a.to_state())
    assert restored.snapshot() == a.snapshot()
    merged = Histogram.from_state(a.to_state()).merge(
        Histogram.from_state(b.to_state())
    )
    ref = Histogram()
    ref.merge(a).merge(b)
    assert merged.snapshot() == ref.snapshot()
    assert merged.count == 300


# --------------------------------------------------------------------------
# Batching front-end
# --------------------------------------------------------------------------


class _ArrayReader:
    """Minimal lookup target: rows by index, KeyError past the end."""

    def __init__(self, rows: np.ndarray, delay_s: float = 0.0):
        self.rows = rows
        self.delay_s = delay_s
        self.calls = 0

    def lookup(self, ids):
        self.calls += 1
        if self.delay_s:
            time.sleep(self.delay_s)
        ids = np.asarray(ids, dtype=np.int64)
        if np.any(ids >= len(self.rows)):
            raise KeyError("missing ids")
        return self.rows[ids]


def test_frontend_correctness_across_threads():
    rng = np.random.default_rng(13)
    rows = rng.standard_normal((500, 8)).astype(np.float32)
    reader = _ArrayReader(rows)
    failures: list[str] = []

    with ServingFrontend(reader, max_batch=256, max_delay_s=0.002) as fe:
        def client(seed: int) -> None:
            r = np.random.default_rng(seed)
            for _ in range(25):
                q = r.integers(0, 500, size=int(r.integers(1, 40)))
                got = fe.lookup(q, timeout=30)
                if not np.array_equal(got, rows[q]):
                    failures.append(f"client {seed}: rows diverged")
                    return

        threads = [threading.Thread(target=client, args=(s,))
                   for s in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    assert not failures
    assert fe.requests == 150
    snap = fe.snapshot()
    assert snap["waves"] == reader.calls
    assert snap["errors"] == 0


def test_frontend_coalesces_waves():
    """While one (slow) wave is in flight, later submits pile up and are
    served together — far fewer reader calls than requests."""
    rng = np.random.default_rng(14)
    rows = rng.standard_normal((300, 4)).astype(np.float32)
    reader = _ArrayReader(rows, delay_s=0.02)
    with ServingFrontend(reader, max_batch=10_000, max_delay_s=0.5) as fe:
        futs = [fe.submit(rng.integers(0, 300, size=16)) for _ in range(12)]
        for f in futs:
            assert np.array_equal(f.result(30), rows[f.ids])
    assert fe.waves < fe.requests  # coalescing actually happened
    assert fe.batched_ids == 12 * 16
    assert fe.unique_ids <= fe.batched_ids


def test_frontend_error_isolation():
    """A request with missing ids fails alone; wave-mates still get rows."""
    rng = np.random.default_rng(15)
    rows = rng.standard_normal((100, 4)).astype(np.float32)
    reader = _ArrayReader(rows, delay_s=0.02)
    with ServingFrontend(reader, max_batch=10_000, max_delay_s=0.5) as fe:
        good1 = fe.submit(np.arange(10))
        bad = fe.submit(np.array([5, 999]))  # 999 is missing
        good2 = fe.submit(np.arange(20, 30))
        assert np.array_equal(good1.result(30), rows[:10])
        with pytest.raises(KeyError):
            bad.result(30)
        assert np.array_equal(good2.result(30), rows[20:30])
    assert fe.errors == 1


def test_frontend_deadline_flushes_sparse_traffic():
    """A single tiny request is served within ~max_delay_s even though
    max_batch is never reached."""
    rows = np.arange(40, dtype=np.float32).reshape(10, 4)
    reader = _ArrayReader(rows)
    with ServingFrontend(reader, max_batch=10_000, max_delay_s=0.02) as fe:
        t0 = time.perf_counter()
        got = fe.lookup(np.array([3]), timeout=10)
        assert time.perf_counter() - t0 < 5.0
        assert np.array_equal(got, rows[[3]])


def test_frontend_stop_drains_and_refuses():
    rng = np.random.default_rng(16)
    rows = rng.standard_normal((200, 4)).astype(np.float32)
    reader = _ArrayReader(rows, delay_s=0.005)
    fe = ServingFrontend(reader, max_batch=32, max_delay_s=0.5).start()
    futs = [fe.submit(rng.integers(0, 200, size=8)) for _ in range(10)]
    fe.stop()
    for f in futs:  # stop() drained everything already queued
        assert f.done
        assert np.array_equal(f.result(0), rows[f.ids])
    with pytest.raises(RuntimeError):
        fe.submit(np.array([1]))


def test_frontend_over_session_reader(tmp_path):
    """End to end: frontend waves against a pinned fast-path reader are
    bit-identical to direct lookups."""
    v, d = 300, 8
    rng = np.random.default_rng(17)
    with serving_session(tmp_path, v) as session:
        ss, rows = scattered_spillset(tmp_path, rng, v, d, 3)
        session.publish(SERVE_LAYER, spills=ss, block_rows=64)
        with session.reader(SERVE_LAYER, fast_path=True) as reader, \
                ServingFrontend(reader, max_batch=128,
                                max_delay_s=0.002) as fe:
            futs = [fe.submit(rng.integers(0, v, size=24))
                    for _ in range(20)]
            for f in futs:
                assert np.array_equal(f.result(30), rows[f.ids])
        assert fe.waves >= 1


def test_bench_serve_processes_parent_never_starts_a_jax_backend():
    """``bench_serve --processes`` forks its readers: a parent that had
    started a JAX backend would hold the chip its children need, so the
    parent must reach the end of the sweep without ever starting one."""
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = (
        "import runpy, sys\n"
        "sys.argv = ['bench_serve.py', '--processes', '2', '--vertices', "
        "'3000', '--dim', '8', '--batches', '10', '--warm-batches', '2', "
        "'--raw-files', '2', '--rows-per-file', '2048']\n"
        "runpy.run_path('benchmarks/bench_serve.py', run_name='__main__')\n"
        "from jax._src import xla_bridge\n"
        "print('backend_started', xla_bridge.backends_are_initialized())\n"
    )
    env = dict(os.environ, PYTHONPATH=os.path.join(repo, "src"))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, cwd=repo, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip().splitlines()[-1] == "backend_started False"
