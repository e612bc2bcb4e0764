"""The GraphSAGE pooling kind (``kinds/sagepool.py``) and the cells that
came with it: ``sagepool-papers100m`` and ``gcn-igb-large-inmem``.  The
reference against a per-vertex loop, the work counts, the readers of
the kind's two per-layer metrics, small runs of both cells, faults the
comparison has to catch and the control."""

from __future__ import annotations

import numpy as np
import pytest

from conftest import SEED, SMALL_VERTICES, run_small, small_cell
from bench import cells, graphs, reference, work

POOL = "sagepool-papers100m"
NEW_CELLS = [POOL, "gcn-igb-large-inmem"]


def _pool_layer_loop(indptr, indices, h, layer, activation):
    """One layer, vertex by vertex, in float64."""
    v = len(indptr) - 1
    src = np.repeat(np.arange(v), np.diff(indptr))
    p = np.maximum(h @ layer["w_pool"].astype(np.float64)
                   + layer["b_pool"], 0.0)
    out = np.empty((v, layer["w"].shape[1]))
    for u in range(v):
        nbrs = src[indices == u]
        a = p[nbrs].max(axis=0) if len(nbrs) else np.zeros(h.shape[1])
        out[u] = np.concatenate([h[u], a]) @ layer["w"] + layer["b"]
    return np.maximum(out, 0.0) if activation else out


def test_reference_matches_a_per_vertex_loop():
    cfg = cells.load_config("graphsage-pool-papers100m")
    cfg["num_vertices"] = 2000
    indptr, indices = graphs.make_graph(cfg, 3)
    feats = graphs.make_features(2000, cfg["dims"][0], 3)
    weights = graphs.make_weights("sagepool", cfg["dims"], 3)
    assert [sorted(w) for w in weights] == [["b", "b_pool", "w",
                                             "w_pool"]] * 2
    h = feats.astype(np.float64)
    for i, layer in enumerate(weights):
        h = _pool_layer_loop(indptr, indices, h, layer, i == 0)
    got = reference.forward("sagepool", indptr, indices, feats, weights)
    np.testing.assert_allclose(got, h, rtol=1e-10, atol=1e-12)
    # some vertex has no in-edge: its neighbour half reads 0
    assert (np.bincount(indices, minlength=2000) == 0).any()


def test_the_reference_max_spans_edge_blocks():
    """Hubs whose edges straddle the reference's edge blocks still get
    the max over all of them."""
    kind = cells.layer_kind("sagepool")
    cfg = cells.load_config("graphsage-pool-papers100m")
    cfg["num_vertices"] = 500
    indptr, indices = graphs.make_graph(cfg, 4)
    graph = kind.reference_graph(indptr, indices, np.float64)
    p = np.abs(np.random.default_rng(4).normal(size=(500, 16)))
    whole = kind.neighbour_max(graph, p, block=len(indices))
    assert np.array_equal(kind.neighbour_max(graph, p, block=7), whole)
    want = np.zeros_like(p)
    np.maximum.at(want, graph[1], p[graph[0]])
    assert np.array_equal(whole, want)


def test_work_counts():
    assert work.messages("sagepool", 10, 30) == 40
    assert work.hot_width("sagepool", 4) == 8
    # 30 edges x 4 columns compare-selects; rows in and out, 8 B per edge
    assert work.aggregation_work("sagepool", 10, 30, 4) == (
        30 * 4, 4 * 10 * 4 + 8 * 30 + 4 * 10 * 4)
    # projection 2 V d^2 and the product over [self ; max]
    assert work.update_flops("sagepool", 10, 4, 3) == \
        2 * 10 * 4 * 4 + 2 * 10 * 8 * 3
    cfg = cells.load_config("graphsage-pool-papers100m")
    v, dims = cfg["num_vertices"], cfg["dims"]
    e = int(v * cfg["avg_degree"])
    least = work.aggregation_least_seconds("sagepool", v, e, dims,
                                           197e12, 819e9)
    assert least["bounds"] == ["bandwidth", "bandwidth"]
    nbytes = sum(8 * v * d + 8 * e for d in dims[:-1])
    assert least["seconds"] == pytest.approx(nbytes / 819e9)
    assert cells.hot_bytes(cfg, cells.load_traffic("ooc-quarter")) == \
        v * 128 * 4 // 4


def _record(layers, ops=None):
    cfg = cells.load_config("graphsage-pool-papers100m")
    return {"config": cfg, "num_vertices": 1000, "num_edges": 14500,
            "layers": layers, "spans": [], "chips": 1,
            "trace": {"ops": ops or {}, "window_ns": [0, 10**9]},
            "peaks": {"flops_per_s": 197e12, "bytes_per_s": 819e9}}


def test_max_merge_share_reader():
    read = cells.metric_reader("max_merge_share")
    assert read(_record([])) is None
    assert read(_record([{"seconds": 2.0}])) is None  # no such counter
    rec = _record([{"seconds": 2.0, "max_merge_seconds": 0.25},
                   {"seconds": 3.0, "max_merge_seconds": 1.0}])
    assert read(rec) == pytest.approx(25.0)


def test_segmax_roofline_reads_only_the_segmax_kernel():
    read = cells.metric_reader("edge_block_segmax_roofline")
    assert read(_record([], {"%edge_block_spmm.1 = f32[8]": 10**6})) is None
    rec = _record([], {"%edge_block_segmax.1 = f32[8]": 2 * 10**6,
                       "%fusion = f32[8]": 10**9})
    least = work.aggregation_least_seconds(
        "sagepool", 1000, 14500, [128, 256, 172], 197e12, 819e9)
    assert read(rec) == pytest.approx(100 * least["seconds"] / 2e-3)


@pytest.mark.parametrize("name", NEW_CELLS)
def test_new_cells_run_correct(name):
    line = run_small(name)
    assert line["correct"] is True
    assert set(line["metrics"]) == {"edges_per_s", "setup_s"}


def test_sagepool_traced_run_reads_its_metrics():
    """The cell reports every per-layer metric of the layers it runs: all
    but the sum kernel's roofline and the mesh exchange.  On the CPU the
    trace holds no device plane, so the device-trace metrics have nothing
    to read; every counter share does."""
    bench = cells.load_benchmark()
    cell = cells.find_cell(POOL, bench)
    metrics = cells.cell_metrics(cell, bench, "per_layer")
    assert {m["name"] for m in bench["per_layer"]} - {
        m["name"] for m in metrics} == {"edge_block_spmm_roofline",
                                         "exchange_share"}
    line = run_small(POOL, trace=True)
    assert line["correct"] is True
    counters = {m["name"] for m in metrics
                if m["source"] == "program_counter"}
    assert counters <= set(line["metrics"])
    assert line["metrics"]["max_merge_share"]["value"] > 0.0
    assert line["metrics"]["evict_reload_share"]["value"] > 0.0


def test_a_sum_in_place_of_the_max_merge_fails(monkeypatch):
    """The hot store adding the chunks' maxima of a vertex (``+=``) in
    place of their max: the count of messages is right, the answer not."""
    from repro.core.memory_manager import MemoryManager

    accumulate = MemoryManager.accumulate

    def summed(self, vertices, partial, col_offset=0, slots=None,
               combine="sum"):
        return accumulate(self, vertices, partial, col_offset, slots, "sum")

    monkeypatch.setattr(MemoryManager, "accumulate", summed)
    line = run_small(POOL)
    assert line["correct"] is False
    assert line["checks"]["max_gap"]["value"] > 1e-2


def test_the_projection_left_out_fails(monkeypatch):
    """The max of the raw rows, without ``ReLU(h W_pool + b_pool)``."""
    from repro.core import broadcast

    def raw_rows(items, src_p, dst_p, feats_p, w_pool, b_pool, **blocks):
        return broadcast._segmax_call(items, src_p, dst_p, feats_p,
                                      **blocks)

    monkeypatch.setattr(broadcast, "_pool_segmax", raw_rows)
    line = run_small(POOL)
    assert line["correct"] is False


def test_the_control_reads_far_above_the_program():
    """As in test_faults: the control (``"high"``) has to read at least
    three times what the program reads on the same seeds."""
    _, _, cfg, _ = small_cell(POOL)
    program, control = [], []
    for seed in (SEED, 1, 2):
        program.append(run_small(POOL, seed=seed)["checks"]["max_gap"]
                       ["value"])
        indptr, indices = graphs.make_graph(cfg, seed)
        feats = graphs.make_features(SMALL_VERTICES, cfg["dims"][0], seed)
        weights = graphs.make_weights("sagepool", cfg["dims"], seed)
        args = ("sagepool", indptr, indices, feats, weights)
        control.append(reference.max_gap(
            reference.forward(*args, precision="high"),
            reference.forward(*args)))
    assert min(control) > 3 * max(program)
