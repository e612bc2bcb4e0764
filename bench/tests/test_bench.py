"""CPU tests of the benchmark harness: lookup by name, the work
functions, the trace reduction, the reference, the refusal without a
TPU and the shape of the result line."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from conftest import CHECKOUT, run_small
from bench import cells, graphs, reference, trace_reduce, work
from bench import run as bench_run

SMALL_TRACE = os.path.join(CHECKOUT, "bench", "testdata", "small.xplane.pb")


# ------------------------------------------------------------ by name


def test_every_piece_of_every_cell_is_found_by_name():
    bench = cells.load_benchmark()
    for cell in bench["workloads"]:
        assert cells.find_cell(cell["name"], bench) is cell
        cfg = cells.load_config(cell["config"])
        mix = cells.load_traffic(cell["traffic"])
        assert cells.hot_bytes(cfg, mix) > 0
        assert callable(cells.layer_kind(cfg["kind"]).reference_layer)
        for section in ("end_to_end", "per_layer"):
            assert cells.cell_metrics(cell, bench, section)
    for m in bench["per_layer"]:
        assert callable(cells.metric_reader(m["name"]))
    for c in bench["configs"]:
        assert os.path.exists(os.path.join(CHECKOUT, c["file"]))


@pytest.mark.parametrize("lookup, name", [
    (cells.find_cell, "no-such-cell"),
    (cells.load_config, "no-such-config"),
    (cells.load_traffic, "no-such-mix"),
    (cells.metric_reader, "no_such_metric"),
])
def test_a_missing_piece_is_an_error(lookup, name):
    with pytest.raises((KeyError, FileNotFoundError), match=name):
        lookup(name)


def test_unknown_device_kind_is_an_error():
    assert bench_run.load_peaks("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="TPU v9"):
        bench_run.load_peaks("TPU v9")


def test_hot_budget_rules():
    sage = cells.load_config("graphsage-papers100m")
    v = sage["num_vertices"]
    assert cells.hot_bytes(sage, cells.load_traffic("ooc-quarter")) == \
        v * 128 * 4 // 4
    assert cells.hot_bytes(sage, cells.load_traffic("in-memory")) == \
        v * 512 * 4
    gcn = cells.load_config("gcn-igb-large")
    assert cells.hot_bytes(gcn, cells.load_traffic("in-memory")) == \
        gcn["num_vertices"] * 1024 * 4


# ------------------------------------------------------- work counted


def test_work_functions_against_hand_counts():
    # SAGE, V=10, E=30, d=4: M = 40 messages
    assert work.messages("sage", 10, 30) == 40
    assert work.aggregation_work("sage", 10, 30, 4) == (
        2 * 40 * 4, 4 * 10 * 4 + 12 * 40 + 4 * 10 * 4)
    assert work.update_flops("sage", 10, 4, 3) == 2 * 10 * 8 * 3
    # GCN: self-loops are edges, no self message
    assert work.messages("gcn", 10, 30) == 30
    assert work.update_flops("gcn", 10, 4, 3) == 2 * 10 * 4 * 3
    assert work.pass_model_flops("gcn", 10, 30, [4, 3, 2]) == (
        2 * 30 * 4 + 2 * 10 * 4 * 3 + 2 * 30 * 3 + 2 * 10 * 3 * 2)
    least = work.aggregation_least_seconds("sage", 10, 30, [4, 3, 2],
                                           peak_flops=1.0,
                                           peak_bytes_per_s=1e9)
    assert least["bounds"] == ["compute", "compute"]
    assert least["seconds"] == 2 * 40 * 4 + 2 * 40 * 3


def _record(layers, ops, window_ns=(0, 10**9)):
    for i, m in enumerate(layers):
        m.setdefault("layer", i)
    return {
        "config": {"kind": "sage", "dims": [128, 256, 172]},
        "num_vertices": 1000, "num_edges": 5000, "chips": 1,
        "layers": layers, "peaks": {"flops_per_s": 197e12,
                                    "bytes_per_s": 819e9},
        "trace": {"window_ns": window_ns, "devices": ["/device:TPU:0"],
                  "busy_ns": 4e8, "ops": ops, "gaps": np.zeros((0, 2))},
        "spans": [],
    }


def _layer(chunks, **kw):
    m = {"seconds": 1.0, "chunks": chunks, "bytes_read": 512000,
         "cold_bytes_read": 0, "cold_bytes_written": 0,
         "aggregate_seconds": 0.5, "h2d_seconds": 0.1,
         "transform_seconds": 0.2}
    m.update(kw)
    return m


def test_roofline_and_mfu_read_the_configuration_not_the_chunking():
    ops = {"_spmm_kernel": 200_000_000, "copy": 100}
    few = _record([_layer(1), _layer(2)], ops)
    many = _record([_layer(97), _layer(301)], ops)
    roof = cells.metric_reader("edge_block_spmm_roofline")
    mfu = cells.metric_reader("pass_mfu")
    assert roof(few) == roof(many)
    assert mfu(few) == mfu(many)
    least = work.aggregation_least_seconds("sage", 1000, 5000,
                                           [128, 256, 172], 197e12, 819e9)
    assert roof(few) == pytest.approx(100 * least["seconds"] / 0.2)
    flops = work.pass_model_flops("sage", 1000, 5000, [128, 256, 172])
    assert mfu(few) == pytest.approx(100 * flops / 197e12)
    # no kernel in the trace: nothing to read, never 0
    assert roof(_record([_layer(1)], {"copy": 5})) is None


def test_counter_metrics():
    rec = _record([_layer(3, cold_bytes_read=100, cold_bytes_written=60),
                   _layer(5, bytes_read=1_024_000)], {})
    assert cells.metric_reader("h2d_share")(rec) == pytest.approx(10.0)
    assert cells.metric_reader("aggregate_share")(rec) == pytest.approx(50.0)
    assert cells.metric_reader("transform_share")(rec) == pytest.approx(20.0)
    assert cells.metric_reader("cold_bytes_per_edge")(rec) == \
        pytest.approx(160 / (2 * 6000))
    assert cells.metric_reader("read_amplification")(rec) == \
        pytest.approx((512000 + 1024000) / (1000 * 128 * 4 + 1000 * 256 * 4))
    assert cells.metric_reader("device_idle_share")(rec) == \
        pytest.approx(60.0)


# ------------------------------------------------------ trace reduction


def test_union_of_intervals():
    iv = np.asarray([[5, 7], [0, 2], [1, 3], [7, 8], [10, 11]])
    assert trace_reduce.union(iv).tolist() == [[0, 3], [5, 8], [10, 11]]
    assert trace_reduce.union(np.zeros((0, 2))).shape == (0, 2)


def test_trace_reduce_on_the_recorded_chip_trace():
    red = trace_reduce.reduce_trace(SMALL_TRACE, bench_run.ANCHOR)
    w0, w1 = red["window_ns"]
    assert w1 > w0
    assert red["devices"] == ["/device:TPU:0"]
    assert 0 < red["busy_ns"] <= w1 - w0
    gaps = red["gaps"]
    # busy and idle tile the window
    assert red["busy_ns"] + (gaps[:, 1] - gaps[:, 0]).sum() == w1 - w0
    assert any("spmm" in name for name in red["ops"])
    assert sum(red["ops"].values()) >= red["busy_ns"]
    with pytest.raises(KeyError):
        trace_reduce.reduce_trace(SMALL_TRACE, "no-such-anchor")


def test_gap_labels_name_the_open_spans():
    spans = [
        {"thread": "a", "name": "outer", "start_ns": 0, "end_ns": 100},
        {"thread": "a", "name": "inner", "start_ns": 10, "end_ns": 30},
        {"thread": "b", "name": "wait", "start_ns": 50, "end_ns": 90},
    ]
    gaps = np.asarray([[15, 25], [60, 70], [200, 210]])
    out = trace_reduce.label_gaps(gaps, spans, ["a", "b"])
    assert out == {"a: inner | b: -": 10e-9, "a: outer | b: wait": 10e-9,
                   "a: - | b: -": 10e-9}


# ---------------------------------------------------------- reference


@pytest.mark.parametrize("name", ["graphsage-papers100m", "gcn-igb-large"])
def test_reference_matches_the_programs_dense_reference(name):
    from repro.graphs.csr import CSRGraph
    from repro.models.gnn import GNNLayerSpec, dense_reference

    cfg = cells.load_config(name)
    cfg["num_vertices"] = 500
    indptr, indices = graphs.make_graph(cfg, 11)
    feats = graphs.make_features(500, cfg["dims"][0], 11)
    weights = graphs.make_weights(cfg["kind"], cfg["dims"], 11)
    dims = cfg["dims"]
    specs = [GNNLayerSpec(cfg["kind"], a, b, i < len(dims) - 2, dict(w))
             for i, (a, b, w) in enumerate(zip(dims[:-1], dims[1:],
                                               weights))]
    theirs = dense_reference(CSRGraph(indptr, indices), feats, specs)
    ours = reference.forward(cfg["kind"], indptr, indices, feats, weights)
    assert ours.shape == (500, dims[-1])
    # the program's reference sums in float32: GCN's 1024-long sums
    # round to about 1e-5 of the output's RMS
    assert reference.max_gap(theirs, ours) < 1e-4


def test_bf16_split():
    x = np.asarray([1.0, 1 + 2**-9, 3.14159265, -2.5e-3], np.float32)
    hi, lo = reference.split_bf16(x)
    assert np.all(reference.bf16_round(hi) == hi)
    assert np.all(reference.bf16_round(lo) == lo)
    assert np.all(np.abs((hi + lo) - x) <= np.abs(x) * 2**-16)
    assert reference.bf16_round(np.float32([1 + 2**-9]))[0] == 1.0


def test_max_gap_reads_inf_for_missing_rows():
    ref = np.ones((4, 2))
    out = ref.astype(np.float32).copy()
    assert reference.max_gap(out, ref) == 0.0
    out[2, 1] = np.nan
    assert reference.max_gap(out, ref) == float("inf")
    assert reference.max_gap(out[:3], ref) == float("inf")


def test_seeds_give_the_same_sizes_and_differ_in_values():
    cfg = cells.load_config("graphsage-papers100m")
    cfg["num_vertices"] = 2000
    a = graphs.make_graph(cfg, 2**40 + 1)
    b = graphs.make_graph(cfg, 2**40 + 1)
    c = graphs.make_graph(cfg, 7)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert abs(len(a[1]) - len(c[1])) < 0.01 * len(a[1])
    assert not np.array_equal(a[1], c[1])
    with pytest.raises(ValueError):
        graphs.rng_for(-1, 0)


# ------------------------------------------------------ the command


def test_the_command_refuses_a_host_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(CHECKOUT, "bench", "run.py"),
         "--workload", "sage-papers100m", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=CHECKOUT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_result_line_shape(cell_name):
    bench = cells.load_benchmark()
    cell = cells.find_cell(cell_name, bench)
    line = run_small(cell_name)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["metrics"]) == {
        m["name"] for m in cells.cell_metrics(cell, bench, "end_to_end")}
    for m in line["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= \
        set(line["device"])
    for c in line["checks"].values():
        assert set(c) == {"value", "limit"}
    json.dumps(line)


def test_traced_result_line_shape():
    line = run_small("sage-papers100m", trace=True)
    assert line["correct"] is True
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert list(line)[-2:] == ["breakdown", "checks"]
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    for name in ("h2d_share", "aggregate_share", "transform_share",
                 "cold_bytes_per_edge", "read_amplification", "pass_mfu"):
        assert name in line["metrics"]
