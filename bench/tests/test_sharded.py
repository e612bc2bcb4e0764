"""The four-chip cell on the CPU: the sharded engine on four host
devices, with the mesh exchange and the kernel in interpret mode, is
within the configuration's limit of the reference; the comparison fails
the exchange between chips left out (the cell's other faults are in
``test_faults.py``); and ``exchange_share`` reads the exchange's
spans."""

from __future__ import annotations

import numpy as np
import pytest

from conftest import run_small, small_cell
from bench import cells

CELL = "sage-papers100m-4chip"


def test_the_sharded_engine_runs_within_the_limit():
    cell, _, cfg, mix = small_cell(CELL)
    assert cell["chips"] == 4 and mix["exchange"] == "mesh"
    # every chip holds the configuration's vertex count
    one = cells.load_config(cell["config"])
    assert cells.sized_config(one, mix, 4)["num_vertices"] == \
        4 * one["num_vertices"]
    line = run_small(CELL)
    assert line["device"]["count"] == 4
    assert line["correct"] is True
    gap = line["checks"]["max_gap"]
    assert gap["limit"] == cfg["limits"]["max_gap"]
    assert 0 < gap["value"] <= gap["limit"]


def test_the_sharded_traced_run_reads_its_metrics():
    bench = cells.load_benchmark()
    cell = cells.find_cell(CELL, bench)
    line = run_small(CELL, trace=True)
    assert line["correct"] is True
    # on the CPU there is no device plane: the trace's readers find
    # nothing, the counters' and the spans' readers do
    want = {m["name"] for m in cells.cell_metrics(cell, bench, "per_layer")
            if m["source"] != "device_trace"} | {"pass_mfu"}
    assert set(line["metrics"]) == want
    assert 0 < line["metrics"]["exchange_share"]["value"] < 100


def test_the_exchange_between_chips_left_out_fails(monkeypatch):
    from repro.dist.exchange import MeshExchange

    route = MeshExchange._all_to_all

    def no_rows(self, ids, cnt, rows):
        # the ids and message counts arrive, so every vertex completes,
        # but no row crosses a chip
        r_ids, r_cnt, r_rows = route(self, ids, cnt, rows)
        return r_ids, r_cnt, np.zeros_like(r_rows)

    monkeypatch.setattr(MeshExchange, "_all_to_all", no_rows)
    line = run_small(CELL)
    assert line["correct"] is False
    assert line["checks"]["max_gap"]["value"] > 1e-2


def _record(spans, seconds):
    return {"layers": [{"layer": i // 4, "shard": i % 4, "seconds": s}
                       for i, s in enumerate(seconds)],
            "spans": spans}


def _span(name, start, end, thread="dist-shard-0"):
    return {"thread": thread, "name": name, "cat": "sink",
            "start_ns": start, "end_ns": end}


def test_exchange_share_from_a_synthetic_record():
    read = cells.metric_reader("exchange_share")
    # 8 shard reports of 2 s each: 16 s; 1 s of post, 3 s of collect
    spans = [_span("exchange_post", 0, 250_000_000),
             _span("exchange_post", 0, 750_000_000, "dist-shard-1"),
             _span("exchange_collect", 10**9, 4 * 10**9),
             _span("aggregate", 0, 10**10)]
    assert read(_record(spans, [2.0] * 8)) == pytest.approx(25.0)
    # a run on one chip has no exchange: nothing to read, never 0
    assert read(_record([_span("aggregate", 0, 10**9)], [2.0])) is None
    assert read(_record(spans, [])) is None
