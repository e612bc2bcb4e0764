"""CPU tests of the readers of the aggregate call's and delivery's
counters: nothing to read on an empty pass or from a program that does
not count them, and the hand-counted share on a constructed record."""

from __future__ import annotations

import pytest

from bench import cells

READERS = ["d2h_share", "dedup_share", "deliver_share",
           "evict_reload_share"]
FIELDS = {"d2h_share": "d2h_seconds", "dedup_share": "dedup_seconds",
          "deliver_share": "deliver_seconds",
          "evict_reload_share": "evict_seconds"}


def _record(layers):
    return {"layers": layers, "spans": [], "config": {}, "trace": {}}


def _layer(seconds, **counters):
    m = {"seconds": seconds, "aggregate_seconds": 0.5 * seconds,
         "h2d_seconds": 0.1 * seconds}
    m.update(counters)
    return m


@pytest.mark.parametrize("name", READERS)
def test_nothing_to_read_on_an_empty_pass(name):
    assert cells.metric_reader(name)(_record([])) is None


@pytest.mark.parametrize("name", READERS)
def test_nothing_to_read_from_a_program_without_the_counter(name):
    rec = _record([_layer(2.0), _layer(3.0)])
    assert cells.metric_reader(name)(rec) is None


@pytest.mark.parametrize("name", READERS)
def test_hand_counted_share(name):
    field = FIELDS[name]
    # 0.25 s of 2 s in layer 0 and 1.0 s of 3 s in layer 1: 1.25 / 5
    rec = _record([_layer(2.0, **{field: 0.25}), _layer(3.0, **{field: 1.0})])
    assert cells.metric_reader(name)(rec) == pytest.approx(25.0)


def test_no_eviction_reads_zero_not_nothing():
    rec = _record([_layer(2.0, evict_seconds=0.0),
                   _layer(3.0, evict_seconds=0.0)])
    assert cells.metric_reader("evict_reload_share")(rec) == 0.0
