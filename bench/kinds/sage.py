"""GraphSAGE with the mean aggregator (Hamilton et al.,
arXiv:1706.02216):

    h'_v = act([h_v ; mean_{u->v} h_u] @ W + b)

with one ``W`` over the self half and the neighbour half stacked.
"""

import numpy as np

from bench import graphs, reference, work


def make_weights(rng: np.random.Generator, d_in: int, d_out: int) -> dict:
    """``{"w": [2 d_in, d_out], "b": [d_out]}``: ``w`` takes the self half
    and the neighbour half stacked.  The bias is drawn too (small), so
    that it is not left out unseen."""
    w = graphs.glorot(rng, (2 * d_in, d_out))
    b = (0.01 * rng.standard_normal(d_out)).astype(np.float32)
    return {"w": w, "b": b}


def reference_graph(indptr, indices, dtype):
    """The aggregation matrix of the mean, ``A[v, u] = 1 / d(v)``."""
    deg = np.maximum(reference.in_degrees(indptr, indices), 1)
    deg = deg.astype(np.float64)
    src, dst = reference.edges(indptr, indices)
    return reference.by_destination(1.0 / deg[dst], src, dst,
                                    len(indptr) - 1, dtype)


def reference_layer(a, h, layer, product, dtype, activation: bool):
    agg = np.asarray(product(a, h), dtype=dtype)
    x = np.concatenate([h, agg], axis=1)
    del agg
    return reference.linear(x, layer, product, dtype, activation)


def messages(num_vertices: int, num_edges: int) -> int:
    """``E`` plus one self message per vertex."""
    return num_edges + num_vertices


def hot_width(d_in: int) -> int:
    """``[self ; neighbours]``: twice the input width."""
    return 2 * d_in


def aggregation_work(num_vertices: int, num_edges: int, d_in: int):
    return work.weighted_sum_work(num_vertices,
                                  messages(num_vertices, num_edges), d_in)


def update_flops(num_vertices: int, d_in: int, d_out: int) -> int:
    return work.dense_update_flops(num_vertices, hot_width(d_in), d_out)
