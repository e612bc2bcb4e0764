"""GraphSAGE with the pooling aggregator (Hamilton, Ying and Leskovec,
arXiv:1706.02216, section 3.3, eq. 3), in the form of PyG's
``SAGEConv(aggr="max", project=True)``:

    p_u  = ReLU(h_u @ W_pool + b_pool)          (W_pool: [d, d])
    a_v  = max_{u->v} p_u                       (elementwise; 0 with no in-edge)
    h'_v = act([h_v ; a_v] @ W + b)             (W: [2 d, d_out])

Two departures from the paper, shared with the mean kind: no per-layer
L2 normalisation, and PyG's bias.
"""

import numpy as np

from bench import graphs, reference, work

# edges per block of the reference's max: the gathered rows of a block
# take EDGE_BLOCK x d x 8 B (134 MB at d = 256 in float64)
EDGE_BLOCK = 1 << 16


def make_weights(rng: np.random.Generator, d_in: int, d_out: int) -> dict:
    """``{"w_pool": [d_in, d_in], "b_pool": [d_in], "w": [2 d_in, d_out],
    "b": [d_out]}``: ``w`` takes the self half and the neighbour half
    stacked.  The biases are drawn too (small), so that they are not left
    out unseen."""
    w_pool = graphs.glorot(rng, (d_in, d_in))
    b_pool = (0.01 * rng.standard_normal(d_in)).astype(np.float32)
    w = graphs.glorot(rng, (2 * d_in, d_out))
    b = (0.01 * rng.standard_normal(d_out)).astype(np.float32)
    return {"w_pool": w_pool, "b_pool": b_pool, "w": w, "b": b}


def reference_graph(indptr, indices, dtype):
    """The edges ``(src, dst)`` sorted by destination."""
    src, dst = reference.edges(indptr, indices)
    order = np.argsort(dst, kind="stable")
    return src[order], dst[order]


def neighbour_max(graph, p: np.ndarray, block: int = EDGE_BLOCK):
    """Per vertex the elementwise max of its in-neighbours' rows of ``p``,
    ``block`` edges at a time of the destination-sorted edges.  Every row
    of ``p`` is ``>= 0``, so 0 is the max's identity and a vertex with no
    in-edge reads 0."""
    src, dst = graph
    agg = np.zeros_like(p)
    for lo in range(0, len(src), block):
        s, d = src[lo:lo + block], dst[lo:lo + block]
        starts = np.flatnonzero(np.r_[True, d[1:] != d[:-1]])
        ud = d[starts]
        agg[ud] = np.maximum(agg[ud],
                             np.maximum.reduceat(p[s], starts, axis=0))
    return agg


def reference_layer(graph, h, layer, product, dtype, activation: bool):
    pool = {"w": layer["w_pool"], "b": layer["b_pool"]}
    p = reference.linear(h, pool, product, dtype, activation=True)
    x = np.concatenate([h, neighbour_max(graph, p)], axis=1)
    del p
    return reference.linear(x, layer, product, dtype, activation)


def messages(num_vertices: int, num_edges: int) -> int:
    """``E`` plus one self message per vertex."""
    return num_edges + num_vertices


def hot_width(d_in: int) -> int:
    """``[self ; neighbours]``: twice the input width."""
    return 2 * d_in


def aggregation_work(num_vertices: int, num_edges: int, d_in: int):
    """``(operations, bytes)`` of the max over the edges, from V, E and the
    width alone: one compare-select per edge and column; the projected
    rows read once (``4 V d``), a source and a destination per edge
    (``8 E``, no weight) and one row written per destination
    (``4 V d``).  The projection is a dense product: ``update_flops``
    counts it."""
    flops = num_edges * d_in
    nbytes = work.F32 * num_vertices * d_in + 8 * num_edges \
        + work.F32 * num_vertices * d_in
    return flops, nbytes


def update_flops(num_vertices: int, d_in: int, d_out: int) -> int:
    """The projection ``2 V d^2`` and the final product
    ``2 V (2 d) d_out``."""
    return work.dense_update_flops(num_vertices, d_in, d_in) \
        + work.dense_update_flops(num_vertices, hot_width(d_in), d_out)
