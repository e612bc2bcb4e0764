"""ISSUE 7: unified run telemetry (docs/observability.md).

Contracts under test:

1. Tracer — strictly nested B/E span pairs per thread track, Chrome
   trace-event export that ``obs_report.validate_trace`` accepts,
   self-time accounting that excludes nested children, and a
   ``NULL_TRACER`` that records nothing.
2. Metrics — log-bucket histogram quantiles (within one bucket's
   growth factor), histogram merge, and the registry's dotted-name
   snapshot tree.
3. Sampler — thread hygiene: idempotent start/stop, no leaked thread,
   samples recorded, /proc readers return sane values.
4. Engine integration — a traced ``AtlasSession.infer`` writes a valid
   trace.json next to the run manifest with >= 4 named thread tracks;
   ``RunResult`` carries queue_stats + telemetry; LayerMetrics keep
   their exact values with tracing on (staged vs serial spills stay
   bit-identical); ``h2d_seconds`` is populated under the staged
   pipeline (regression: the pipeline owns the aggregator whose
   counter must be read after the ring drains).
5. The aggregate call's round trip and delivery — under the Pallas
   kernel (interpret mode) and the staged pipeline, the dedup, h2d,
   kernel-wait, d2h, deliver and evict counters are positive and
   reconcile with their spans; tracing changes no count and no output
   bit; and every span lands on the JAX profiler's host plane as many
   times and as long as the tracer recorded it.
"""

import collections
import glob

import json
import os
import threading
import time

import jax
import numpy as np
import pytest

from repro.core.atlas import AtlasConfig, spills_to_dense
from repro.graphs.synth import make_features, powerlaw_graph
from repro.launch.obs_report import (
    RECONCILE,
    analyze,
    load_trace,
    reconcile,
    validate_trace,
)
from repro.models.gnn import init_gnn_params
from repro.obs import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    NULL_TRACER,
    ResourceSampler,
    Tracer,
    as_tracer,
)
from repro.session import AtlasSession

from tests.conftest import build_store


# --------------------------------------------------------------------------
# 1. Tracer
# --------------------------------------------------------------------------


def test_tracer_spans_nest_and_export_validates(tmp_path):
    tr = Tracer()
    with tr.span("outer", "layer"):
        with tr.span("inner", "aggregate"):
            pass
    tr.counter("rss_mb", 12.5)
    assert tr.num_spans == 2
    path = tr.export(str(tmp_path / "trace.json"))
    events = load_trace(path)
    assert validate_trace(events) == []
    phs = {e["ph"] for e in events}
    assert {"B", "E", "M", "C"} <= phs
    # every timed event carries a microsecond timestamp and a track
    for e in events:
        if e["ph"] != "M":
            assert isinstance(e["ts"], (int, float)) and e["ts"] >= 0
            assert "tid" in e and "pid" in e


def test_tracer_self_time_excludes_children():
    tr = Tracer()
    with tr.span("outer", "layer"):
        time.sleep(0.02)
        with tr.span("inner", "aggregate"):
            time.sleep(0.03)
    spans = {s["name"]: s for s in tr.spans()}
    assert spans["inner"]["dur_s"] >= 0.025
    assert spans["outer"]["dur_s"] >= spans["inner"]["dur_s"]
    # outer self time excludes the nested child
    assert spans["outer"]["self_s"] <= spans["outer"]["dur_s"] - 0.025
    cats = tr.category_seconds()
    assert cats["aggregate"] >= 0.025
    assert abs(
        cats["layer"] + cats["aggregate"]
        - (spans["outer"]["dur_s"])
    ) < 0.02


def test_tracer_per_thread_tracks():
    tr = Tracer()

    def work(n):
        with tr.span(f"job_{n}", "read"):
            time.sleep(0.01)

    threads = [threading.Thread(target=work, args=(i,)) for i in range(3)]
    with tr.span("main", "layer"):
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    tids = {s["tid"] for s in tr.spans()}
    assert len(tids) == 4  # main + 3 workers, distinct synthetic tracks
    events = tr.events()
    names = [e["args"]["name"] for e in events if e["ph"] == "M"]
    assert len(names) == 4


def test_null_tracer_records_nothing():
    tr = NULL_TRACER
    assert tr.enabled is False
    with tr.span("x", "read"):
        pass
    tr.begin("y", "spill")
    tr.end("y", "spill")
    tr.counter("c", 1.0)
    assert tr.num_spans == 0
    with pytest.raises(RuntimeError):
        tr.export("/tmp/should_not_exist.json")


def test_as_tracer_coercions():
    assert as_tracer(None) is NULL_TRACER
    assert as_tracer(False) is NULL_TRACER
    assert isinstance(as_tracer(True), Tracer)
    t = Tracer()
    assert as_tracer(t) is t


def test_validate_trace_catches_violations():
    ok = {"ph": "B", "ts": 1.0, "pid": 1, "tid": 1, "name": "a"}
    # unknown ph
    assert validate_trace([{**ok, "ph": "Q"}])
    # negative / missing ts
    assert validate_trace([{**ok, "ts": -5}])
    # E with no open B
    assert validate_trace([{"ph": "E", "ts": 1.0, "pid": 1, "tid": 1,
                            "name": "a"}])
    # improper nesting: E name does not match innermost B
    bad = [
        {"ph": "B", "ts": 1.0, "pid": 1, "tid": 1, "name": "a"},
        {"ph": "B", "ts": 2.0, "pid": 1, "tid": 1, "name": "b"},
        {"ph": "E", "ts": 3.0, "pid": 1, "tid": 1, "name": "a"},
        {"ph": "E", "ts": 4.0, "pid": 1, "tid": 1, "name": "b"},
    ]
    assert any("nesting" in v for v in validate_trace(bad))
    # unclosed B
    assert any("never closed" in v for v in validate_trace([ok]))
    # well-formed pair on two tracks passes
    good = [
        {"ph": "B", "ts": 1.0, "pid": 1, "tid": 1, "name": "a"},
        {"ph": "B", "ts": 1.5, "pid": 1, "tid": 2, "name": "c"},
        {"ph": "E", "ts": 2.0, "pid": 1, "tid": 1, "name": "a"},
        {"ph": "E", "ts": 2.5, "pid": 1, "tid": 2, "name": "c"},
    ]
    assert validate_trace(good) == []


# --------------------------------------------------------------------------
# 2. Metrics
# --------------------------------------------------------------------------


def test_histogram_quantiles_within_bucket_resolution():
    h = Histogram()
    for v in [0.001] * 50 + [0.010] * 45 + [0.100] * 5:
        h.observe(v)
    s = h.snapshot()
    assert s["count"] == 100
    assert s["min"] == pytest.approx(0.001)
    assert s["max"] == pytest.approx(0.100)
    # log-bucket quantiles are exact to within one growth factor (2x)
    assert 0.0005 <= s["p50"] <= 0.002
    assert 0.005 <= s["p95"] <= 0.020
    # p99 falls in the top bucket and clamps to the observed max
    assert 0.05 <= s["p99"] <= 0.100


def test_histogram_merge_accumulates():
    a, b = Histogram(), Histogram()
    for v in (0.001, 0.002, 0.004):
        a.observe(v)
    for v in (0.008, 0.016, 0.032):
        b.observe(v)
    a.merge(b)
    s = a.snapshot()
    assert s["count"] == 6
    assert s["min"] == pytest.approx(0.001)
    assert s["max"] == pytest.approx(0.032)
    assert s["sum"] == pytest.approx(0.063)


def test_registry_snapshot_tree():
    reg = MetricsRegistry()
    reg.counter("io.spills").inc(3)
    reg.gauge("resources.rss_bytes").set(1024)
    reg.histogram("serve.latency").observe(0.005)
    snap = reg.snapshot()
    assert snap["io"]["spills"] == 3
    assert snap["resources"]["rss_bytes"]["value"] == 1024
    assert snap["serve"]["latency"]["count"] == 1
    # type reuse is checked
    with pytest.raises(TypeError):
        reg.gauge("io.spills")


def test_counter_and_gauge_track_extremes():
    c = Counter()
    c.inc()
    c.inc(4)
    assert c.value == 5
    g = Gauge()
    for v in (5.0, 1.0, 9.0):
        g.set(v)
    s = g.snapshot()
    assert s["value"] == 9.0 and s["min"] == 1.0 and s["max"] == 9.0
    assert s["samples"] == 3


# --------------------------------------------------------------------------
# 3. Sampler
# --------------------------------------------------------------------------


def test_sampler_thread_hygiene():
    before = threading.active_count()
    reg = MetricsRegistry()
    s = ResourceSampler(interval_s=0.01, registry=reg)
    s.start()
    s.start()  # idempotent
    assert s.running
    time.sleep(0.06)
    s.stop()
    s.stop()  # idempotent
    assert not s.running
    assert threading.active_count() == before
    snap = s.snapshot()
    if os.path.exists("/proc/self/statm"):
        assert snap["rss_bytes"]["value"] > 0
        assert snap["rss_bytes"]["samples"] >= 2


def test_sampler_context_manager_and_tracer_counters():
    tr = Tracer()
    with ResourceSampler(interval_s=0.01, tracer=tr) as s:
        assert s.running
        time.sleep(0.04)
    assert not s.running
    if os.path.exists("/proc/self/statm"):
        counters = [e for e in tr.events() if e["ph"] == "C"]
        assert any(e["name"] == "rss_mb" for e in counters)


# --------------------------------------------------------------------------
# 4. Engine integration
# --------------------------------------------------------------------------


def _run(tmp_path, csr, feats, sub, *, trace=None, **cfg_kw):
    store = build_store(tmp_path / sub, csr, feats)
    cfg = AtlasConfig(hot_slots=512, chunk_bytes=1 << 16, seed=0, **cfg_kw)
    session = AtlasSession(store, cfg, workdir=str(tmp_path / sub / "run"),
                           trace=trace)
    specs = init_gnn_params("gcn", [feats.shape[1], 16, 8], seed=1)
    result = session.infer(specs)
    session.close()
    return result


def test_traced_run_writes_valid_trace(tmp_path, small_graph, small_features):
    res = _run(tmp_path, small_graph, small_features, "t",
               trace=True, sample_interval_s=0.01)
    # trace.json lands next to the run manifest
    assert res.trace_path is not None
    assert os.path.dirname(res.trace_path) == str(tmp_path / "t" / "run")
    assert os.path.exists(os.path.join(os.path.dirname(res.trace_path),
                                       "run_manifest.json"))
    events = load_trace(res.trace_path)
    assert validate_trace(events) == []
    report = analyze(events)
    # at least the delivery thread + reader + writer + io tracks
    assert len(set(report["threads"].values())) >= 4
    assert len(report["layers"]) == 2
    for layer in report["layers"]:
        assert layer["wall_seconds"] > 0
        assert layer["category_seconds"]
    # telemetry snapshot mirrors the run
    assert res.telemetry is not None
    assert len(res.telemetry["layers"]) == 2
    assert res.telemetry["trace"]["num_spans"] > 0
    assert res.telemetry["resources"]  # sampler ran
    # run-wide queue stats captured before the scheduler closed
    qs = res.queue_stats
    assert qs is not None
    assert qs["enqueued"] == qs["completed"] > 0
    assert qs["barriers"] >= 2


def test_untraced_run_has_no_trace(tmp_path, small_graph, small_features):
    res = _run(tmp_path, small_graph, small_features, "u")
    assert res.trace_path is None
    assert not os.path.exists(str(tmp_path / "u" / "run" / "trace.json"))
    # telemetry + queue stats are still populated (they are metrics-based)
    assert res.queue_stats is not None
    assert res.telemetry is not None and "trace" not in res.telemetry


def test_phase_metrics_bounded_by_layer_wall(
    tmp_path, small_graph, small_features
):
    res = _run(tmp_path, small_graph, small_features, "w",
               trace=True)
    for m in res.metrics:
        wall = m.seconds
        # phases timed on the delivery critical path cannot exceed the
        # layer wall (lenient epsilon for clock granularity)
        for field in ("aggregate_seconds", "h2d_seconds",
                      "pipeline_stall_seconds", "transform_seconds",
                      "spill_seconds"):
            assert getattr(m, field) <= wall + 0.05, field


def test_tracing_keeps_staged_and_serial_bit_identical(
    tmp_path, small_graph, small_features
):
    out = {}
    for pipeline in ("staged", "serial"):
        res = _run(tmp_path, small_graph, small_features, pipeline,
                   trace=True, backend="jax", pipeline=pipeline)
        out[pipeline] = spills_to_dense(
            res.final.spills, small_graph.num_vertices, 8
        )
    assert np.array_equal(out["staged"], out["serial"])


def test_h2d_seconds_populated_under_staged_pipeline(
    tmp_path, small_graph, small_features
):
    # regression (ISSUE 7 satellite): the staged pipeline owns the device
    # aggregator; h2d_seconds must be read from it after the ring drains,
    # not from the engine-local aggregator instance
    res = _run(tmp_path, small_graph, small_features, "h2d",
               backend="jax", pipeline="staged")
    for m in res.metrics:
        assert m.h2d_seconds > 0.0
        assert m.h2d_seconds <= m.aggregate_seconds + 0.05


def test_traced_category_totals_reconcile(
    tmp_path, small_graph, small_features
):
    res = _run(tmp_path, small_graph, small_features, "r", trace=True)
    cats = res.telemetry["trace"]["category_seconds"]
    agg_metric = sum(m.aggregate_seconds for m in res.metrics)
    agg_trace = sum(cats.get(c, 0.0) for c in RECONCILE["aggregate_seconds"])
    # span totals track the LayerMetrics scalars (generous tolerance at
    # unit-test scale where runs are a few ms; the 5% acceptance check
    # runs at bench scale via obs_report --check in CI)
    assert agg_trace == pytest.approx(agg_metric, rel=0.25, abs=0.02)
    stall_metric = sum(m.pipeline_stall_seconds for m in res.metrics)
    assert cats.get("stall", 0.0) == pytest.approx(
        stall_metric, rel=0.25, abs=0.02
    )


# --------------------------------------------------------------------------
# 5. Round trip and delivery under the Pallas kernel
# --------------------------------------------------------------------------

# the counters this layer of tracing adds, each behind its own span
ROUND_TRIP = ("dedup_seconds", "h2d_seconds", "kernel_wait_seconds",
              "d2h_seconds", "deliver_seconds", "evict_seconds")


def _run_pallas(root, trace, v=400, kind="sage"):
    """Two SAGE layers (or ``kind``'s) through the Pallas kernel
    (interpret mode) and the staged pipeline, with a hot store a quarter
    of the vertices, so that eviction and reload run in both layers."""
    d = 16
    csr = powerlaw_graph(v, 5, seed=43)
    feats = make_features(v, d, seed=43)
    store = build_store(root, csr, feats)
    cfg = AtlasConfig(chunk_bytes=64 * d * 4, hot_slots=v // 4,
                      backend="pallas-interpret", pipeline="staged")
    session = AtlasSession(store, cfg, workdir=str(root / "run"),
                           trace=trace)
    result = session.infer(init_gnn_params(kind, [d, 8, 8], seed=9))
    session.close()
    return result


def test_round_trip_counters_reconcile_with_spans(tmp_path):
    res = _run_pallas(tmp_path, trace=True)
    for m in res.metrics:
        assert m.evictions > 0 and m.reloads > 0
        for field in ROUND_TRIP:
            assert getattr(m, field) > 0.0, field
        # the aggregate call's parts, timed inside it
        parts = (m.dedup_seconds + m.h2d_seconds + m.kernel_wait_seconds
                 + m.d2h_seconds)
        assert parts <= m.aggregate_seconds
        assert m.evict_seconds <= m.deliver_seconds
        assert 0 < m.spmm_steps <= m.spmm_dense_steps
    events = load_trace(res.trace_path)
    assert validate_trace(events) == []
    report = analyze(events)
    for field, cats in RECONCILE.items():
        if field == "max_merge_seconds":
            continue  # the max's: test_max_merge_reconciles_with_its_spans
        for cat in cats:
            assert report["category_seconds"].get(cat, 0.0) > 0.0, cat
    # the fields this test is about; the others carry run-level spans (the
    # session's closing group commit) that unit-test scale magnifies
    fields = ROUND_TRIP + ("aggregate_seconds",)
    problems = reconcile(report, [m.as_dict() for m in res.metrics])
    assert [p for p in problems if p.split(":")[0] in fields] == []


def test_obs_report_prints_spmm_step_ratio(tmp_path, capsys):
    """With telemetry, obs_report prints each layer's kernel grid steps
    against the dense grid's, and carries them in its JSON."""
    from repro.launch.obs_report import main

    res = _run_pallas(tmp_path, trace=True)
    telemetry = tmp_path / "telemetry.json"
    telemetry.write_text(json.dumps(res.telemetry))
    out_json = tmp_path / "report.json"
    assert main([res.trace_path, "--telemetry", str(telemetry),
                 "--json", str(out_json)]) == 0
    printed = capsys.readouterr().out
    rows = json.loads(out_json.read_text())["spmm_steps"]
    assert [r["layer"] for r in rows] == [m.layer for m in res.metrics]
    for r, m in zip(rows, res.metrics):
        assert (r["steps"], r["dense_steps"]) == (
            m.spmm_steps, m.spmm_dense_steps)
        assert r["ratio"] == pytest.approx(m.spmm_steps / m.spmm_dense_steps)
        assert (f"layer {m.layer}: {m.spmm_steps:,} of "
                f"{m.spmm_dense_steps:,} ({r['ratio']:.2%})") in printed


def test_max_merge_reconciles_with_its_spans(tmp_path, capsys):
    """The pooling aggregator's max combine is timed into
    ``max_merge_seconds`` and traced as ``max_merge`` (category
    ``merge``) inside ``deliver``; the two agree within obs_report's 5%,
    and obs_report prints the segmax kernel's grid steps."""
    from repro.launch.obs_report import main

    res = _run_pallas(tmp_path, trace=True, kind="sagepool")
    for m in res.metrics:
        assert m.evictions > 0 and m.reloads > 0
        assert 0.0 < m.max_merge_seconds < m.deliver_seconds
        assert 0 < m.segmax_steps <= m.segmax_dense_steps
        assert m.spmm_steps == m.spmm_dense_steps == 0
    events = load_trace(res.trace_path)
    report = analyze(events)
    assert report["category_seconds"]["merge"] > 0.0
    # every max_merge span lies inside a deliver span
    merge_regions = [set(k.split("+")) for k in report["region_seconds"]
                     if "merge" in k.split("+")]
    assert merge_regions and all("deliver" in r for r in merge_regions)
    problems = reconcile(report, [m.as_dict() for m in res.metrics])
    assert [p for p in problems
            if p.split(":")[0] in ("max_merge_seconds",
                                   "deliver_seconds")] == []

    telemetry = tmp_path / "telemetry.json"
    telemetry.write_text(json.dumps(res.telemetry))
    out_json = tmp_path / "report.json"
    assert main([res.trace_path, "--telemetry", str(telemetry),
                 "--json", str(out_json)]) == 0
    printed = capsys.readouterr().out
    rows = json.loads(out_json.read_text())["segmax_steps"]
    assert "spmm_steps" not in json.loads(out_json.read_text())
    assert "edge_block_segmax grid steps" in printed
    for r, m in zip(rows, res.metrics):
        assert (r["steps"], r["dense_steps"]) == (
            m.segmax_steps, m.segmax_dense_steps)


def test_tracing_changes_no_count_and_no_output(tmp_path):
    on = _run_pallas(tmp_path / "on", trace=True)
    off = _run_pallas(tmp_path / "off", trace=False)

    def counts(m):
        # timings vary run to run; so does the write-back queue's
        # high-water mark, which depends on how fast the I/O thread drains
        return {k: v for k, v in m.as_dict().items()
                if not k.endswith("seconds")
                and k not in ("tail_rows_per_s", "bytes_inflight")}

    assert [counts(m) for m in on.metrics] == [counts(m) for m in off.metrics]
    for a, b in zip(on.final.spills.files, off.final.spills.files):
        ids_a, rows_a = a.read_all()
        ids_b, rows_b = b.read_all()
        assert np.array_equal(ids_a, ids_b)
        assert rows_a.tobytes() == rows_b.tobytes()
    assert len(on.final.spills.files) == len(off.final.spills.files)


def _profiled_pass(root):
    """One traced pass under the JAX profiler: the tracer's span
    durations and the host plane's event durations (ns), by name."""
    from jax.profiler import ProfileData

    tracer = Tracer()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with jax.profiler.trace(str(root / "profile"), profiler_options=options):
        _run_pallas(root, trace=tracer, v=200)
    recorded = collections.defaultdict(list)
    for sp in tracer.spans():
        recorded[sp["name"]].append(sp["dur_s"] * 1e9)
    (path,) = glob.glob(str(root / "profile" / "**" / "*.xplane.pb"),
                        recursive=True)
    on_host = collections.defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in recorded:
                    on_host[ev.name].append(ev.duration_ns)
    return recorded, on_host


def test_spans_land_on_the_profiler_host_plane(tmp_path):
    # A span's clock reads and its annotation's lie a microsecond apart.
    # A host thread descheduled in between reads as a gap of however long
    # it waited, which on a loaded host befalls about one span in a few
    # hundred: so a pass is tried up to five times, and one pass must
    # match in full.  A missing, extra or shifted annotation fails all.
    for attempt in range(5):
        recorded, on_host = _profiled_pass(tmp_path / f"pass_{attempt}")
        assert {"deliver", "d2h", "kernel_wait", "evict"} <= set(recorded)
        for name, durs in recorded.items():
            assert len(on_host[name]) == len(durs), name
        gaps = [(name, h, t)
                for name, durs in recorded.items()
                for h, t in zip(sorted(on_host[name]), sorted(durs))
                if abs(h - t) > max(0.01 * t, 50e3)]
        if not gaps:
            return
    pytest.fail(f"spans and their annotations differ: {gaps[:5]}")
