"""The layer kinds under ``bench/kinds/``: the inputs, the reference and
the work counts are what they were before the kinds moved there, and a
kind is found by name, as a file alone."""

from __future__ import annotations

import hashlib
import textwrap

import numpy as np
import pytest

from bench import cells, graphs, reference, work

# sha256 (first 16 hex digits) of each part at V=2000, taken before the
# layer kinds moved out of graphs.py, reference.py, work.py and cells.py
GOLDEN = {
    ("gcn-igb-large", 0): {
        "graph": "ef370a54ccf11257", "features": "47f7a22c4e9f63b3",
        "weights": "e34b85e70ea8e30d", "exact": "897cc9d4a0056e5b",
        "high": "35eb4d921775b664"},
    ("gcn-igb-large", 1): {
        "graph": "46ffab5227b58604", "features": "3a0eb18e6bf36a0e",
        "weights": "bfd7ea5432125ca7", "exact": "987a7e58e8d957f9",
        "high": "c48c0b37a8cb469a"},
    ("gcn-igb-large", 2): {
        "graph": "867398154651fa29", "features": "29e904ff6cd58eaa",
        "weights": "3f166874632cfa26", "exact": "dd64ce745ab664e9",
        "high": "e815919bf445d76a"},
    ("graphsage-papers100m", 0): {
        "graph": "01e12b3dbe670bcc", "features": "4aa6387ff2a6dcd3",
        "weights": "121807b687bad26b", "exact": "4d7526026dd8fcb6",
        "high": "52e4ac4c030cbf87"},
    ("graphsage-papers100m", 1): {
        "graph": "e1cc6a27387a7496", "features": "a55db07ab4dcfa10",
        "weights": "3c943102d6c53e17", "exact": "686333fcdd0ae81d",
        "high": "0a81d47f55fd6a59"},
    ("graphsage-papers100m", 2): {
        "graph": "cfa28165d9bb0373", "features": "e8f3ed1692a0db69",
        "weights": "48171e77d92c8853", "exact": "de8720f95e3b04c4",
        "high": "517c1353edcfa1b4"},
}

# the work counts of the real configurations, at E = V * avg_degree
GOLDEN_WORK = {
    "gcn-igb-large": {
        "least_seconds": 0.0023220512820512824,
        "bounds": ["bandwidth", "bandwidth"],
        "pass_model_flops": 59023360000,
        "hot_bytes": {"ooc-quarter": 204800000, "in-memory": 819200000}},
    "graphsage-papers100m": {
        "least_seconds": 0.000841025641025641,
        "bounds": ["bandwidth", "bandwidth"],
        "pass_model_flops": 63820800000,
        "hot_bytes": {"ooc-quarter": 25600000, "in-memory": 409600000}},
}


def digest(*arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(a)
        h.update(f"{a.dtype.str}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()[:16]


@pytest.mark.parametrize("name, seed", sorted(GOLDEN))
def test_inputs_and_reference_match_the_golden_digests(name, seed):
    cfg = cells.load_config(name)
    cfg["num_vertices"] = 2000
    indptr, indices = graphs.make_graph(cfg, seed)
    feats = graphs.make_features(2000, cfg["dims"][0], seed)
    weights = graphs.make_weights(cfg["kind"], cfg["dims"], seed)
    args = (cfg["kind"], indptr, indices, feats, weights)
    got = {
        "graph": digest(indptr, indices),
        "features": digest(feats),
        "weights": digest(*[a for w in weights
                            for _, a in sorted(w.items())]),
        "exact": digest(reference.forward(*args)),
        "high": digest(reference.forward(*args, precision="high")),
    }
    assert got == GOLDEN[name, seed]


@pytest.mark.parametrize("name", sorted(GOLDEN_WORK))
def test_work_counts_match_the_golden_counts(name):
    cfg = cells.load_config(name)
    kind, v, dims = cfg["kind"], cfg["num_vertices"], cfg["dims"]
    e = int(v * cfg["avg_degree"])
    least = work.aggregation_least_seconds(kind, v, e, dims, 197e12, 819e9)
    want = GOLDEN_WORK[name]
    assert least == {"seconds": want["least_seconds"],
                     "bounds": want["bounds"]}
    assert work.pass_model_flops(kind, v, e, dims) == \
        want["pass_model_flops"]
    assert {m: cells.hot_bytes(cfg, cells.load_traffic(m))
            for m in want["hot_bytes"]} == want["hot_bytes"]


# A kind that exists only in a test: a plain sum of the neighbours' rows
# (every edge weight 1), a hot width of three times the input and one
# extra weight per layer, so that each lookup below can only have found
# this file.
THROWAWAY = textwrap.dedent('''
    import numpy as np

    from bench import reference, work


    def make_weights(rng, d_in, d_out):
        return {"w": rng.standard_normal((d_in, d_out)).astype(np.float32),
                "b": np.zeros(d_out, np.float32),
                "scale": np.float32(0.5) * np.ones(1, np.float32)}


    def reference_graph(indptr, indices, dtype):
        src, dst = reference.edges(indptr, indices)
        return reference.by_destination(np.ones(len(dst)), src, dst,
                                        len(indptr) - 1, dtype)


    def reference_layer(a, h, layer, product, dtype, activation):
        agg = np.asarray(product(a, h), dtype=dtype)
        agg *= layer["scale"].astype(dtype)
        return reference.linear(agg, layer, product, dtype, activation)


    def messages(num_vertices, num_edges):
        return num_edges


    def hot_width(d_in):
        return 3 * d_in


    def aggregation_work(num_vertices, num_edges, d_in):
        return work.weighted_sum_work(num_vertices, num_edges, d_in)


    def update_flops(num_vertices, d_in, d_out):
        return work.dense_update_flops(num_vertices, d_in, d_out)
''')


def test_a_new_kind_is_found_by_name_as_a_file(tmp_path, monkeypatch):
    (tmp_path / "sumagg.py").write_text(THROWAWAY)
    monkeypatch.setattr(cells, "KINDS_DIR", str(tmp_path))
    dims = [4, 3, 2]
    weights = graphs.make_weights("sumagg", dims, 5)
    assert [sorted(w) for w in weights] == [["b", "scale", "w"]] * 2
    assert weights[0]["w"].shape == (4, 3)

    indptr = np.asarray([0, 2, 3, 3])  # 0->1, 0->2, 1->2
    indices = np.asarray([1, 2, 2], dtype=np.int32)
    feats = np.arange(12, dtype=np.float32).reshape(3, 4)
    got = reference.forward("sumagg", indptr, indices, feats, weights)
    agg = np.asarray([[0] * 4, feats[0], feats[0] + feats[1]], np.float64)
    h = np.maximum(0.5 * agg @ weights[0]["w"], 0)
    want = 0.5 * np.asarray([[0] * 3, h[0], h[0] + h[1]]) @ weights[1]["w"]
    np.testing.assert_allclose(got, want, rtol=1e-12)

    assert work.messages("sumagg", 10, 30) == 30
    assert work.hot_width("sumagg", 4) == 12
    assert work.aggregation_work("sumagg", 10, 30, 4)[0] == 2 * 30 * 4
    assert work.update_flops("sumagg", 10, 4, 3) == 2 * 10 * 4 * 3
    cfg = {"kind": "sumagg", "num_vertices": 10, "dims": dims}
    assert cells.hot_bytes(cfg, {"name": "m", "hot_budget": {
        "widest_hot_state": True}}) == 10 * 12 * 4


def test_an_unknown_kind_names_the_file_it_looked_for():
    path = f"{cells.KINDS_DIR}/no_such_kind.py"
    for call in (lambda: graphs.make_weights("no_such_kind", [4, 2], 0),
                 lambda: work.messages("no_such_kind", 10, 30),
                 lambda: reference.forward("no_such_kind", np.zeros(2),
                                           np.zeros(0), np.zeros((1, 4)),
                                           [])):
        with pytest.raises(FileNotFoundError) as err:
            call()
        assert path in str(err.value)
