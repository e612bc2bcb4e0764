"""ISSUE 6: pallas aggregation backend + staged device pipeline.

Three contracts under test:

1. Backend equivalence — numpy / jax / pallas-interpret agree (allclose
   partials, identical ``(u_dst, counts)``) over a grid of chunk shapes
   including empty chunks, single-edge chunks, non-multiple-of-block
   dims, and all three ``spec.kind`` weightings.
2. Pipeline semantics — the staging ring delivers chunks in index
   order, so the engine's output (and spill bytes) are identical to the
   serial loop; the engine end-to-end matches the dense oracle under the
   pallas backend.
3. Run-shared scheduler + overlapped barrier — ``AtlasSession.infer``
   creates exactly one ``WritebackIOScheduler`` for the whole run
   (QueueStats global across layers), and the deferred group commit
   still strictly precedes the manifest advance (kill-between test in
   the style of tests/test_io_scheduler.py).
"""

import json
import os
import threading

import numpy as np
import pytest

from repro.core.atlas import AtlasConfig, AtlasEngine, spills_to_dense
from repro.core.broadcast import (
    JaxChunkAggregator,
    PallasChunkAggregator,
    chunk_aggregate,
    chunk_aggregate_numpy,
)
from repro.core.staging import (
    SerialAggregation,
    StagedAggregation,
    make_aggregation_pipeline,
)
from repro.graphs.synth import make_features, powerlaw_graph
from repro.models.gnn import dense_reference, edge_weights, init_gnn_params
from repro.session import AtlasSession
from repro.storage.io_scheduler import WritebackIOScheduler

from tests.conftest import build_store

BACKENDS = ["numpy", "jax", "pallas-interpret"]


# --------------------------------------------------------------------------
# 1. Backend equivalence grid
# --------------------------------------------------------------------------


def _chunk(rng, n, d, m, num_dst):
    feats = rng.normal(size=(n, d)).astype(np.float32)
    src_local = rng.integers(0, n, m).astype(np.int64)
    dst = rng.integers(0, num_dst, m).astype(np.int64)
    return feats, src_local, dst


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize(
    "n,d,m,num_dst",
    [
        (64, 16, 300, 500),  # typical
        (100, 32, 0, 500),  # empty chunk (E=0)
        (5, 8, 1, 9),  # single edge
        (33, 130, 257, 77),  # nothing a multiple of any block
        (1, 1, 1, 1),  # degenerate minimum
        (300, 24, 2000, 40),  # heavy fan-in (many edges per dst)
        (64, 16, 1500, 1),  # one hub destination over several edge blocks
        # ~690 segments in a 1024-row pow-2 bucket: 3 tiles hold
        # segments, the last only padding; 12 edge blocks in 16
        (200, 24, 3000, 700),
    ],
)
@pytest.mark.parametrize("kind", ["gcn", "sage", "sagepool", "gin"])
def test_backend_equivalence_grid(backend, n, d, m, num_dst, kind):
    """Every backend returns the numpy oracle's (u_dst, counts) exactly
    and its partials to fp32 tolerance, for every weighting and for the
    pooling aggregator's projected max."""
    rng = np.random.default_rng(n * 7 + m + d)
    feats, src_local, dst = _chunk(rng, n, d, m, num_dst)
    # realistic per-kind edge weights (gcn: symmetric norm, sage: 1/deg,
    # gin: ones; none for the max) computed from a synthetic degree vector
    in_deg = rng.integers(1, 9, num_dst).astype(np.int64)
    src_g = rng.integers(0, num_dst, m).astype(np.int64)  # fake global ids
    w = edge_weights(kind, src_g, dst, in_deg)
    (spec,) = init_gnn_params(kind, [d, 4], seed=n + m)
    if kind == "sagepool":
        # a bias that leaves some projected columns 0 and others not
        spec.params["b_pool"][:] = rng.normal(0, 0.3, d)
    projection = spec.source_projection
    ref_u, ref_p, ref_c = chunk_aggregate_numpy(feats, src_local, dst, w,
                                                projection)
    agg = chunk_aggregate(backend, projection=projection)
    u, p, c = agg(feats, src_local, dst, w)
    assert u.dtype == np.int64 and c.dtype == np.int64
    np.testing.assert_array_equal(u, ref_u)
    np.testing.assert_array_equal(c, ref_c)
    assert p.shape == ref_p.shape and p.dtype == np.float32
    np.testing.assert_allclose(p, ref_p, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("backend", ["jax", "pallas-interpret"])
def test_aggregator_scratch_reuse_across_chunks(backend):
    """One aggregator instance over many differently-shaped chunks (the
    per-layer usage pattern) must stay correct while its scratch buffers
    are recycled, and must account h2d transfer time."""
    rng = np.random.default_rng(3)
    agg = chunk_aggregate(backend)
    n, d = 96, 20
    feats = rng.normal(size=(n, d)).astype(np.float32)
    for m in [500, 3, 0, 257, 1, 64, 1000]:
        src_local = rng.integers(0, n, m).astype(np.int64)
        dst = rng.integers(0, 400, m).astype(np.int64)
        w = rng.uniform(-1, 1, m).astype(np.float32)
        ref = chunk_aggregate_numpy(feats, src_local, dst, w)
        got = agg(feats, src_local, dst, w)
        np.testing.assert_array_equal(got[0], ref[0])
        np.testing.assert_array_equal(got[2], ref[2])
        np.testing.assert_allclose(got[1], ref[1], rtol=1e-4, atol=1e-5)
    assert agg.h2d_seconds > 0.0


def test_chunk_aggregate_dispatcher():
    assert chunk_aggregate("numpy") is chunk_aggregate_numpy
    assert isinstance(chunk_aggregate("jax"), JaxChunkAggregator)
    p = chunk_aggregate("pallas-interpret")
    assert isinstance(p, PallasChunkAggregator) and p.interpret
    # 'pallas' is the compiled kernel: off-TPU it refuses, pointing at
    # the interpret backend, instead of quietly interpreting
    with pytest.raises(RuntimeError, match="no TPU.*pallas-interpret"):
        chunk_aggregate("pallas")
    with pytest.raises(ValueError, match="unknown broadcast backend"):
        chunk_aggregate("cuda")


@pytest.mark.parametrize("backend", ["numpy", "jax", "pallas-interpret"])
def test_chunk_aggregate_takes_the_max_given_a_projection(backend):
    """A layer's projection alone makes an aggregator the pooling max:
    per destination the elementwise max of ``ReLU(h W_pool + b_pool)``
    over its edges, edge weights ignored; without one, the weighted sum."""
    rng = np.random.default_rng(11)
    (spec,) = init_gnn_params("sagepool", [6, 2], seed=1)
    spec.params["b_pool"][:] = rng.normal(0, 0.3, 6)
    feats = rng.normal(size=(9, 6)).astype(np.float32)
    src_local = rng.integers(0, 9, 40).astype(np.int64)
    dst = rng.integers(0, 7, 40).astype(np.int64)
    w = rng.uniform(-1, 1, 40).astype(np.float32)
    w_pool, b_pool = spec.source_projection
    rows = np.maximum(feats @ w_pool + b_pool, 0.0)
    u, p, _ = chunk_aggregate(backend, projection=spec.source_projection)(
        feats, src_local, dst, w)
    for k, v in enumerate(u):
        np.testing.assert_allclose(p[k], rows[src_local[dst == v]].max(0),
                                   rtol=1e-5, atol=1e-6)
    u, p, _ = chunk_aggregate(backend)(feats, src_local, dst, w)
    for k, v in enumerate(u):
        e = dst == v
        np.testing.assert_allclose(p[k], (feats[src_local[e]] * w[e, None])
                                   .sum(0), rtol=1e-4, atol=1e-5)


def test_pallas_backend_session_fails_without_tpu(tmp_path):
    """The whole entry point fails on a host without a TPU instead of
    running the kernel in interpret mode."""
    v, d = 200, 8
    csr = powerlaw_graph(v, 4, seed=41)
    store = build_store(tmp_path, csr, make_features(v, d, seed=41))
    specs = init_gnn_params("sage", [d, 4], seed=5)
    cfg = AtlasConfig(chunk_bytes=64 * d * 4, backend="pallas")
    with AtlasSession(store, config=cfg, workdir=str(tmp_path / "w")) as s:
        with pytest.raises(RuntimeError, match="no TPU"):
            s.infer(specs)


def test_device_aggregators_place_operands_on_their_device():
    import jax

    devices = jax.devices()
    for backend in ("jax", "pallas-interpret"):
        for index in range(3):
            agg = chunk_aggregate(backend, device_index=index)
            assert agg.device == devices[index % len(devices)]


# --------------------------------------------------------------------------
# 1b. edge_block_spmm corners (run here, not in test_kernels.py, because
#     that module is skipped wholesale when hypothesis is absent — these
#     must collect in tier-1 on a bare CPU container)
# --------------------------------------------------------------------------


def _spmm_ref(feats, src, dst, w, num_dst):
    out = np.zeros((num_dst, feats.shape[1]), np.float32)
    np.add.at(out, np.asarray(dst), np.asarray(w)[:, None] * np.asarray(feats)[np.asarray(src)])
    return out


def test_spmm_empty_edge_list_short_circuits():
    """E=0 must return zeros without a pallas_call (no grid of size 0)."""
    import jax.numpy as jnp

    from repro.kernels.edge_block_spmm import edge_block_spmm

    feats = jnp.ones((10, 6), jnp.float32)
    e = jnp.zeros(0, jnp.int32)
    out = edge_block_spmm(feats, e, e, jnp.zeros(0, jnp.float32), 7,
                          interpret=True)
    assert out.shape == (7, 6)
    assert not np.any(np.asarray(out))


def test_spmm_sentinel_padding_edges_contribute_nothing():
    """-1 src/dst rows (the padding convention) have all-zero one-hots;
    mixing them into a real edge list must not change the result — even
    with poisonous weights on the padding."""
    import jax.numpy as jnp

    from repro.kernels.edge_block_spmm import edge_block_spmm

    rng = np.random.default_rng(7)
    feats = jnp.asarray(rng.normal(size=(40, 12)), jnp.float32)
    src = jnp.asarray(rng.integers(0, 40, 100), jnp.int32)
    dst = jnp.asarray(rng.integers(0, 30, 100), jnp.int32)
    w = jnp.asarray(rng.uniform(0.1, 1.0, 100), jnp.float32)
    want = edge_block_spmm(feats, src, dst, w, 30, interpret=True)
    pad = jnp.full(28, -1, jnp.int32)
    out = edge_block_spmm(
        feats,
        jnp.concatenate([src, pad]),
        jnp.concatenate([dst, pad]),
        jnp.concatenate([w, jnp.full(28, 1e6, jnp.float32)]),
        30,
        interpret=True,
    )
    np.testing.assert_allclose(out, want, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize(
    "v_src,num_dst,e,d",
    [(3, 5, 7, 2),  # everything smaller than any block
     (16, 16, 50, 200),  # d spans more than one interpret tile
     (9, 1, 4, 1),  # single destination / single feature
     # 2 src tiles of 1024 rows; dst tile boundary inside an edge block
     (2000, 300, 600, 8)],
)
def test_spmm_auto_blocks_small_and_ragged(v_src, num_dst, e, d):
    """No explicit block sizes: auto_blocks must pick valid tiles for
    shapes far below the TPU defaults (the d < block_d corner)."""
    import jax.numpy as jnp

    from repro.kernels.edge_block_spmm import edge_block_spmm

    rng = np.random.default_rng(d * 31 + e)
    feats = jnp.asarray(rng.normal(size=(v_src, d)), jnp.float32)
    src = jnp.asarray(rng.integers(0, v_src, e), jnp.int32)
    dst = jnp.asarray(rng.integers(0, num_dst, e), jnp.int32)
    w = jnp.asarray(rng.uniform(-1, 1, e), jnp.float32)
    out = edge_block_spmm(feats, src, dst, w, num_dst, interpret=True)
    want = _spmm_ref(feats, src, dst, w, num_dst)
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-4)


def test_spmm_auto_blocks_divide_padded_shapes():
    from repro.kernels.edge_block_spmm import auto_blocks

    for args in [(1, 1, 1, 1), (1000, 130, 5000, 700), (8, 256, 64, 8)]:
        be, bv, bdst, bd = auto_blocks(*args, interpret=True)
        assert all(b >= 1 for b in (be, bv, bdst, bd))
        assert be % 8 == 0 and bv % 8 == 0
        assert be * bv <= 256 * 1024  # src-onehot VMEM cap
    # TPU mode keeps MXU-lane-aligned tiles regardless of operand size
    be, bv, bdst, bd = auto_blocks(10, 3, 10, 10, interpret=False)
    assert bd == 128 and bdst == 256 and be == 256


def test_spmm_aligned_call_matches_padded_path():
    """Block-aligned features skip the device-side pad and still match."""
    import jax.numpy as jnp

    from repro.kernels.edge_block_spmm import edge_block_spmm

    rng = np.random.default_rng(11)
    feats = jnp.asarray(rng.normal(size=(64, 16)), jnp.float32)
    src = jnp.asarray(rng.integers(0, 64, 128), jnp.int32)
    dst = jnp.asarray(rng.integers(0, 32, 128), jnp.int32)
    w = jnp.asarray(rng.uniform(0.1, 1.0, 128), jnp.float32)
    out = edge_block_spmm(feats, src, dst, w, 32, block_e=64, block_v=64,
                          block_dst=32, block_d=16, interpret=True)
    want = _spmm_ref(feats, src, dst, w, 32)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)


def _sorted_dst(kind, rng, m, num_dst):
    if kind == "uniform":
        dst = rng.integers(0, num_dst, m)
    elif kind == "hub":  # most edges into one destination
        dst = np.where(rng.random(m) < 0.8, num_dst // 2,
                       rng.integers(0, num_dst, m))
    elif kind == "dense":  # every destination once, in order
        dst = np.arange(m) % num_dst
    else:  # "gaps": whole 32-row tiles with no edge
        dst = rng.integers(0, max(1, num_dst // 64), m) * 64
    return np.sort(dst)


@pytest.mark.parametrize("kind", ["uniform", "hub", "dense", "gaps"])
@pytest.mark.parametrize("m,num_dst", [(1, 1), (300, 40), (2000, 700)])
def test_work_items_cover_each_edge_pair_once(kind, m, num_dst):
    """The item tables list every (tile, block) pair that holds an edge
    exactly once, a tile's items consecutively and no more than the
    static bound; their length depends on the padded shapes alone."""
    from repro.kernels.edge_block_spmm import max_work_items, work_items

    be, bdst = 64, 32
    rng = np.random.default_rng(m + num_dst)
    dst = _sorted_dst(kind, rng, m, num_dst)
    ep = -(-m // be) * be * 2  # a pow-2-style bucket with spare blocks
    jp = -(-num_dst // bdst) * bdst * 2
    bound = max_work_items(ep, jp, be, bdst)
    items = work_items(dst, num_dst, block_e=be, block_dst=bdst, ep=ep,
                       jp=jp)
    assert items.dtype == np.int32 and len(items) == 1 + 3 * bound
    n = int(items[0])
    tile, block, first = (items[1 + k * bound:1 + (k + 1) * bound]
                          for k in range(3))
    assert 1 <= n <= bound
    pairs = list(zip(tile[:n].tolist(), block[:n].tolist()))
    assert len(set(pairs)) == n
    held = {(int(t) // bdst, e // be) for e, t in enumerate(dst)}
    assert held <= set(pairs)
    # the extra items are one per tile with no edge, which zeroes it
    n_tiles = -(-num_dst // bdst)
    empty = set(range(n_tiles)) - {t for t, _ in held}
    assert len(pairs) == len(held) + len(empty)
    assert {t for t, _ in set(pairs) - held} == empty
    # sorted by tile then block; first flags each tile's first item
    assert pairs == sorted(pairs)
    assert set(tile[:n].tolist()) == set(range(n_tiles))
    starts = np.r_[True, tile[1:n] != tile[:n - 1]]
    np.testing.assert_array_equal(first[:n], starts.astype(np.int32))
    # padding repeats the last real item and starts no tile
    assert (tile[n:] == tile[n - 1]).all()
    assert (block[n:] == block[n - 1]).all()
    assert not first[n:].any()
    assert (block[:n] < ep // be).all()


def test_aggregator_compiles_the_kernel_once_per_bucket():
    """Chunks whose edges and segments land in one padded bucket, with
    any distribution of destinations, run one compiled program; the
    aggregator counts fewer steps than a dense grid would run."""
    import jax

    from repro.kernels.edge_block_spmm import _spmm_jit

    # block sizes no other test uses, so this bucket compiles here:
    # 289..576 edges pad to 8 blocks of 72, 81..160 segments to 4 tiles
    # of 40, 112 rows are 2 src tiles of 56 and d=48 is 2 d tiles of 24
    agg = PallasChunkAggregator(jax.devices()[0], interpret=True,
                                block_e=72, block_v=56, block_dst=40,
                                block_d=24)
    rng = np.random.default_rng(5)
    n, d = 112, 48
    feats = rng.normal(size=(n, d)).astype(np.float32)
    chunks = [
        rng.integers(0, 160, 500),  # uniform, ~153 segments
        np.r_[np.full(300, 7), np.arange(100) * 3 + 8],  # a hub
        np.arange(300) % 150,  # two edges per destination
        np.repeat(np.arange(90) * 1000, 5),  # sparse global ids
        np.r_[np.full(200, 1), np.arange(90) + 10],  # hub over 3 blocks
    ]
    before = _spmm_jit._cache_size()
    for dst in chunks:
        dst = rng.permutation(dst).astype(np.int64)
        m = len(dst)
        src_local = rng.integers(0, n, m).astype(np.int64)
        w = rng.uniform(-1, 1, m).astype(np.float32)
        ref = chunk_aggregate_numpy(feats, src_local, dst, w)
        got = agg(feats, src_local, dst, w)
        np.testing.assert_array_equal(got[0], ref[0])
        np.testing.assert_array_equal(got[2], ref[2])
        np.testing.assert_allclose(got[1], ref[1], rtol=1e-4, atol=1e-5)
    assert _spmm_jit._cache_size() - before == 1
    # 5 chunks x 4 tiles x 8 blocks x 2 d tiles x 2 src tiles
    assert agg.spmm_dense_steps == 5 * 4 * 8 * 2 * 2
    assert 0 < agg.spmm_steps < agg.spmm_dense_steps


# --------------------------------------------------------------------------
# 2. Pipeline semantics
# --------------------------------------------------------------------------


class _FakeChunk:
    def __init__(self, index):
        self.index = index
        self.feats = np.full((4, 2), float(index), np.float32)


def _fake_prep(chunk):
    return (
        np.zeros(2, np.int64),
        np.array([chunk.index, 0], np.int64),
        np.ones(2, np.float32),
    )


def test_staged_pipeline_preserves_index_order():
    """FIFO ring: chunks come out in exactly the index order they went
    in, so delivery-order-dependent state (eviction scores, graduation
    order, spills) matches the serial loop."""
    chunks = [_FakeChunk(i) for i in range(32)]
    pipe = StagedAggregation(
        iter(chunks), _fake_prep, chunk_aggregate_numpy, depth=2
    )
    seen = [chunk.index for chunk, _ in pipe]
    assert seen == list(range(32))
    assert pipe.aggregate_seconds > 0.0


def test_staged_pipeline_propagates_worker_errors():
    def bad_prep(chunk):
        if chunk.index == 3:
            raise RuntimeError("prep exploded")
        return _fake_prep(chunk)

    pipe = StagedAggregation(
        iter([_FakeChunk(i) for i in range(8)]), bad_prep,
        chunk_aggregate_numpy, depth=2,
    )
    with pytest.raises(RuntimeError, match="prep exploded"):
        list(pipe)


def test_staged_pipeline_close_unblocks_producer():
    """Abandoning iteration mid-stream (engine error path) must not
    deadlock on a full ring; close() also closes the source iterator."""
    closed = {"v": False}

    def source():
        try:
            for i in range(10_000):
                yield _FakeChunk(i)
        finally:
            closed["v"] = True

    pipe = StagedAggregation(source(), _fake_prep, chunk_aggregate_numpy, depth=2)
    it = iter(pipe)
    next(it)
    it.close()  # generator close -> finally -> pipe.close()
    assert closed["v"]
    assert "atlas-staging" not in {
        t.name for t in threading.enumerate() if t.is_alive()
    }


def test_make_aggregation_pipeline_modes():
    mk = lambda mode, backend, threaded: make_aggregation_pipeline(  # noqa: E731
        mode, backend, threaded, iter(()), _fake_prep, chunk_aggregate_numpy
    )
    assert isinstance(mk("auto", "numpy", True), SerialAggregation)
    assert isinstance(mk("auto", "pallas-interpret", True), StagedAggregation)
    assert isinstance(mk("auto", "jax", False), SerialAggregation)
    assert isinstance(mk("serial", "jax", True), SerialAggregation)
    assert isinstance(mk("staged", "numpy", True), StagedAggregation)
    with pytest.raises(ValueError, match="unknown pipeline mode"):
        mk("ring", "numpy", True)
    with pytest.raises(ValueError, match="staging depth"):
        StagedAggregation(iter(()), _fake_prep, chunk_aggregate_numpy, depth=0)


@pytest.mark.parametrize("backend", ["jax", "pallas-interpret"])
def test_engine_backend_matches_dense(tmp_path, backend):
    """End-to-end: device backends through the staged pipeline match the
    dense in-memory oracle (paper §4.1 error scale) under eviction."""
    v, d = 500, 16
    csr = powerlaw_graph(v, 5, seed=43)
    feats = make_features(v, d, seed=43)
    specs = init_gnn_params("sage", [d, 8], seed=9)
    ref = dense_reference(csr, feats, specs)
    store = build_store(tmp_path, csr, feats)
    cfg = AtlasConfig(
        chunk_bytes=64 * d * 4, hot_slots=v // 4, backend=backend
    )
    spills, metrics = AtlasEngine(cfg).run(store, specs, str(tmp_path / "w"))
    out = spills_to_dense(spills, v, 8)
    assert np.abs(out - ref).max() < 1e-4
    m = metrics[0]
    assert m.evictions > 0
    assert m.aggregate_seconds > 0.0
    assert m.h2d_seconds > 0.0


def test_staged_and_serial_engine_outputs_identical(tmp_path):
    """Same backend, staged vs serial pipeline: spills must be
    bit-identical per file — the ring only moves *where* aggregation
    runs, never what is computed or in which order it is delivered."""
    v, d = 600, 12
    csr = powerlaw_graph(v, 6, seed=47)
    feats = make_features(v, d, seed=47)
    specs = init_gnn_params("gcn", [d, 6], seed=11)
    raw = {}
    for mode in ("staged", "serial"):
        store = build_store(tmp_path / mode, csr, feats)
        cfg = AtlasConfig(
            chunk_bytes=48 * d * 4, hot_slots=v // 4, backend="jax",
            pipeline=mode,
        )
        with AtlasSession(store, config=cfg) as session:
            result = session.infer(specs)
            raw[mode] = {
                os.path.basename(f.path): open(f.path, "rb").read()
                for f in result.final.spills.files
            }
    assert raw["staged"].keys() == raw["serial"].keys()
    for name in raw["staged"]:
        assert raw["staged"][name] == raw["serial"][name], name


# --------------------------------------------------------------------------
# 3. Run-shared scheduler + overlapped barrier
# --------------------------------------------------------------------------


def _run_session(tmp, csr, feats, specs, **cfg_kw):
    store = build_store(tmp, csr, feats, num_partitions=2)
    d = feats.shape[1]
    cfg = AtlasConfig(
        chunk_bytes=64 * d * 4,
        hot_slots=csr.num_vertices // 4,
        spill_buffer_rows=64,
        **cfg_kw,
    )
    session = AtlasSession(store, config=cfg, workdir=str(tmp / "work"))
    return session, store


def test_one_scheduler_per_infer_run_shared_qstats(tmp_path, monkeypatch):
    """A multi-layer run creates exactly one WritebackIOScheduler, whose
    QueueStats span every layer: one barrier per layer on the same stats
    object, enqueue accounting across the whole run."""
    v, d = 700, 10
    csr = powerlaw_graph(v, 5, seed=51)
    feats = make_features(v, d, seed=51)
    specs = init_gnn_params("gcn", [d, 8, 6], seed=13)

    created = []
    real_init = WritebackIOScheduler.__init__

    def counting_init(self, *a, **kw):
        real_init(self, *a, **kw)
        created.append(self)

    monkeypatch.setattr(WritebackIOScheduler, "__init__", counting_init)
    session, _ = _run_session(tmp_path, csr, feats, specs)
    result = session.infer(specs)
    assert len(result.metrics) == 2
    assert len(created) == 1, "infer must share one scheduler across layers"
    qstats = created[0].qstats
    # one group commit per layer, plus the run-end barrier that makes
    # the final layer's deferred manifest fsync durable
    assert qstats.barriers == len(specs) + 1
    assert qstats.completed == qstats.enqueued > 0
    assert qstats.dropped == 0
    # the run reclaimed its scheduler; nothing for close() to leak
    assert created[0].closed
    for m in result.metrics:
        assert m.barrier_seconds > 0.0
    session.close()


def test_overlapped_barrier_still_precedes_manifest_advance(tmp_path, monkeypatch):
    """Kill-between test: the deferred (overlapped) group commit of layer
    l must complete before the manifest records layer l.  Crash the
    barrier helper for layer 2: the manifest stays at layer 1, and
    resume replays only layer 2, bit-identically."""
    v, d = 800, 12
    csr = powerlaw_graph(v, 5, seed=53)
    feats = make_features(v, d, seed=53)
    specs = init_gnn_params("gcn", [d, 10, 6], seed=17)

    ref_session, ref_store = _run_session(tmp_path / "ref", csr, feats, specs)
    ref_out = spills_to_dense(ref_session.infer(specs).final.spills, v, 6)
    ref_session.close()

    real_barrier = WritebackIOScheduler.barrier
    state = {"barriers": 0}

    def crashing_barrier(self):
        state["barriers"] += 1
        if state["barriers"] == 2:  # layer 1 commits; layer 2 dies
            raise KeyboardInterrupt("preempted during overlapped commit")
        return real_barrier(self)

    monkeypatch.setattr(WritebackIOScheduler, "barrier", crashing_barrier)
    session, _ = _run_session(tmp_path / "crash", csr, feats, specs)
    with pytest.raises(KeyboardInterrupt):
        session.infer(specs)
    manifest = json.load(open(session.run_manifest_path))
    assert manifest["completed_layers"] == 1

    monkeypatch.setattr(WritebackIOScheduler, "barrier", real_barrier)
    result = session.infer(specs, resume=True)
    assert [m.layer for m in result.metrics] == [1]
    assert np.array_equal(spills_to_dense(result.final.spills, v, 6), ref_out)
    session.close()


def test_crash_between_layers_commits_finished_layer(tmp_path):
    """A crash at the very start of layer l+1 (before its pipeline runs
    the deferred commit) must still land layer l's manifest advance —
    infer's error path runs the pending commit so resume does not replay
    completed work."""
    v, d = 500, 8
    csr = powerlaw_graph(v, 5, seed=59)
    feats = make_features(v, d, seed=59)
    specs = init_gnn_params("gcn", [d, 6, 4], seed=19)

    class CrashAtLayer1(AtlasEngine):
        def run_layer(self, *a, **kw):
            if kw.get("layer_index") == 1:
                raise KeyboardInterrupt("simulated preemption")
            return super().run_layer(*a, **kw)

    store = build_store(tmp_path, csr, feats, num_partitions=2)
    cfg = AtlasConfig(chunk_bytes=64 * d * 4, hot_slots=v, spill_buffer_rows=64)
    session = AtlasSession(
        store, engine=CrashAtLayer1(cfg), workdir=str(tmp_path / "work")
    )
    with pytest.raises(KeyboardInterrupt):
        session.infer(specs)
    manifest = json.load(open(session.run_manifest_path))
    assert manifest["completed_layers"] == 1  # layer 0 committed on the way out
    session.close()

    resumed = AtlasSession(
        store, config=cfg, workdir=str(tmp_path / "work")
    )
    result = resumed.infer(specs, resume=True)
    assert [m.layer for m in result.metrics] == [1]
    resumed.close()


def test_engine_pipeline_metrics_in_sync_io_mode(tmp_path):
    """io_impl='sync' (oracle) composes with the staged pipeline: no
    scheduler is created, barrier metrics stay zero, outputs correct."""
    v, d = 400, 8
    csr = powerlaw_graph(v, 4, seed=61)
    feats = make_features(v, d, seed=61)
    specs = init_gnn_params("gin", [d, 4], seed=23)
    ref = dense_reference(csr, feats, specs)
    session, _ = _run_session(
        tmp_path, csr, feats, specs, io_impl="sync", backend="jax"
    )
    result = session.infer(specs)
    m = result.metrics[0]
    assert m.barrier_seconds == 0.0 and m.bytes_inflight == 0
    assert m.aggregate_seconds > 0.0
    out = spills_to_dense(result.final.spills, v, 4)
    assert np.abs(out - ref).max() < 1e-4
    session.close()
