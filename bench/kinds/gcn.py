"""GCN (Kipf & Welling, arXiv:1609.02907):

    h'_v = act(sum_{u->v} h_u / sqrt(d(u) d(v)) @ W + b)

with ``d`` the in-degree; the self-loops are edges of the topology, and
``d`` counts them.
"""

import numpy as np

from bench import graphs, reference, work


def make_weights(rng: np.random.Generator, d_in: int, d_out: int) -> dict:
    """``{"w": [d_in, d_out], "b": [d_out]}``.  The bias is drawn too
    (small), so that it is not left out unseen."""
    w = graphs.glorot(rng, (d_in, d_out))
    b = (0.01 * rng.standard_normal(d_out)).astype(np.float32)
    return {"w": w, "b": b}


def reference_graph(indptr, indices, dtype):
    """The aggregation matrix, ``A[v, u] = 1 / sqrt(d(u) d(v))``."""
    deg = np.maximum(reference.in_degrees(indptr, indices), 1)
    deg = deg.astype(np.float64)
    src, dst = reference.edges(indptr, indices)
    return reference.by_destination(1.0 / np.sqrt(deg[src] * deg[dst]),
                                    src, dst, len(indptr) - 1, dtype)


def reference_layer(a, h, layer, product, dtype, activation: bool):
    agg = np.asarray(product(a, h), dtype=dtype)
    return reference.linear(agg, layer, product, dtype, activation)


def messages(num_vertices: int, num_edges: int) -> int:
    return num_edges


def hot_width(d_in: int) -> int:
    return d_in


def aggregation_work(num_vertices: int, num_edges: int, d_in: int):
    return work.weighted_sum_work(num_vertices,
                                  messages(num_vertices, num_edges), d_in)


def update_flops(num_vertices: int, d_in: int, d_out: int) -> int:
    return work.dense_update_flops(num_vertices, hot_width(d_in), d_out)
