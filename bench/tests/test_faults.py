"""The comparison that decides ``correct`` fails what it must.

Each test drives a whole run of a small cell, with the harness's look
for a chip skipped and the kernel in interpret mode, and breaks the
timed path underneath in one way.  The faults are the ones a cell of
this benchmark can have: a pass that returns its state unchanged, half
of each chunk's edges left out with the mean taken over the rest, and
an answer altered where it is produced, each in a one-chip cell and in
the four-chip cell, which runs the sharded engine; that cell's exchange
between chips left out is in ``test_sharded.py``.  The control, the
reference computed at ``Precision.HIGH``, has to read far above the
program.
"""

from __future__ import annotations

import numpy as np
import pytest

from conftest import SEED, SMALL_VERTICES, run_small, small_cell
from bench import graphs, reference

FOUR = "sage-papers100m-4chip"


def sharded(name: str) -> bool:
    return "exchange" in small_cell(name)[3]


def test_sound_run_is_correct(cell_name):
    assert run_small(cell_name)["correct"] is True


@pytest.mark.parametrize("name", ["sage-papers100m", FOUR])
def test_a_pass_that_returns_its_state_unchanged_fails(monkeypatch, name):
    from repro.dist.session import DistSession
    from repro.session import AtlasSession

    session = DistSession if sharded(name) else AtlasSession
    infer = session.infer
    first = {}

    def stale_infer(self, specs, resume=False, **kwargs):
        if "result" not in first:
            first["result"] = infer(self, specs, resume=resume)
        return first["result"]

    monkeypatch.setattr(session, "infer", stale_infer)
    line = run_small(name)
    assert line["correct"] is False
    assert line["checks"]["stale_spills"]["value"] > 0


@pytest.mark.parametrize("name", ["gcn-igb-large", FOUR])
def test_half_of_each_batch_left_out_fails(monkeypatch, name):
    from repro.core.broadcast import PallasChunkAggregator

    call = PallasChunkAggregator.__call__

    def half(self, feats, src_local, dst, weights):
        # every other edge, with doubled weights for the mean over the
        # rest; the message counts still say every edge arrived, so the
        # program's own bookkeeping cannot see it
        keep = np.arange(len(dst)) % 2 == 0
        u_all, counts = np.unique(dst, return_counts=True)
        u_half, part_half, _ = call(self, feats, src_local[keep], dst[keep],
                                    weights[keep] * 2)
        part = np.zeros((len(u_all), feats.shape[1]), np.float32)
        part[np.searchsorted(u_all, u_half)] = part_half
        return u_all.astype(np.int64), part, counts.astype(np.int64)

    monkeypatch.setattr(PallasChunkAggregator, "__call__", half)
    line = run_small(name)
    assert line["correct"] is False
    assert line["checks"]["max_gap"]["value"] > 1e-2


@pytest.mark.parametrize("name", ["sage-papers100m", FOUR])
def test_an_answer_altered_where_it_is_produced_fails(monkeypatch, name):
    import repro.core.atlas as atlas
    import repro.dist.worker as worker

    # the module whose graduation calls the layer update
    module = worker if sharded(name) else atlas
    update = module.layer_update

    def altered(spec, rows):
        out = update(spec, rows)
        if not spec.activation and len(out) > 1:
            out[[0, 1]] = out[[1, 0]]  # two answers swapped
        return out

    monkeypatch.setattr(module, "layer_update", altered)
    line = run_small(name)
    assert line["correct"] is False


@pytest.mark.parametrize("name", ["sage-papers100m", "gcn-igb-large",
                                  "sage-papers100m-4chip"])
def test_the_control_reads_far_above_the_program(name):
    """At this size the widest gaps are smaller than at the cells' (the
    hubs are smaller), so the limit, set from chip readings at the
    cells' size, is not what is checked here: the control has to read
    at least three times what the program reads on the same seeds."""
    _, _, cfg, _ = small_cell(name)
    program, control = [], []
    for seed in (SEED, 1, 2):
        line = run_small(name, seed=seed)
        program.append(line["checks"]["max_gap"]["value"])
        indptr, indices = graphs.make_graph(cfg, seed)
        feats = graphs.make_features(SMALL_VERTICES, cfg["dims"][0], seed)
        weights = graphs.make_weights(cfg["kind"], cfg["dims"], seed)
        args = (cfg["kind"], indptr, indices, feats, weights)
        ref = reference.forward(*args)
        control.append(reference.max_gap(
            reference.forward(*args, precision="high"), ref))
    assert min(control) > 3 * max(program)
