"""Plain reference of the benchmark's GNN layers, and the comparison that
decides ``correct``.

It follows the published layer equations and imports nothing of the
program.  Each layer kind's equation is in ``kinds/<kind>.py``
(``reference_graph`` and ``reference_layer``); this module runs them
layer by layer, with ReLU after every layer but the last:

    GCN   h'_v = act(sum_{u->v} h_u / sqrt(d(u) d(v)) @ W + b)
          (self-loops are in the topology, d counts them)
    SAGE  h'_v = act([h_v ; mean_{u->v} h_u] @ W + b)

with ``d`` the in-degree.

``precision="exact"`` computes in float64: the reference.  ``"high"`` is
the control: every product of two float32 numbers is taken as TPU's
``Precision.HIGH`` takes it, in three bfloat16 passes
(``a_hi*b_hi + a_hi*b_lo + a_lo*b_hi``, each product exact in float32),
summed in float32, and every intermediate is stored in float32.  A kind
takes every product through the ``product`` it is handed, so that the
control covers its whole equation.
"""

from __future__ import annotations

import functools

import numpy as np
import scipy.sparse as sp

from bench import cells

PRECISIONS = ("exact", "high")


def bf16_round(x: np.ndarray) -> np.ndarray:
    """Round float32 to the nearest bfloat16 (ties to even), as float32."""
    u = np.ascontiguousarray(x, dtype=np.float32).view(np.uint32)
    u = (u + np.uint32(0x7FFF) + ((u >> np.uint32(16)) & np.uint32(1)))
    return (u & np.uint32(0xFFFF0000)).view(np.float32)


def split_bf16(x: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """``x`` (float32) as ``hi + lo``, both bfloat16 values."""
    x = np.asarray(x, dtype=np.float32)
    hi = bf16_round(x)
    lo = bf16_round(x - hi)
    return hi, lo


def _product(a, b, precision: str):
    """``a @ b`` for a (sparse or dense) ``a`` and dense ``b``.  Under
    ``"high"`` the three bfloat16 products are exact in float32 and are
    accumulated in float32, as the MXU does."""
    if precision == "exact":
        return a @ b
    if sp.issparse(a):
        a_hi, a_lo = a.copy(), a.copy()
        a_hi.data, a_lo.data = split_bf16(a.data)
    else:
        a_hi, a_lo = split_bf16(a)
    b_hi, b_lo = split_bf16(b)
    out = np.asarray(a_hi @ b_hi, dtype=np.float32)
    out += np.asarray(a_hi @ b_lo, dtype=np.float32)
    out += np.asarray(a_lo @ b_hi, dtype=np.float32)
    return out


def in_degrees(indptr: np.ndarray, indices: np.ndarray) -> np.ndarray:
    return np.bincount(indices, minlength=len(indptr) - 1).astype(np.int64)


def edges(indptr, indices) -> tuple[np.ndarray, np.ndarray]:
    """``(src, dst)`` of every edge ``src -> dst``, as int64."""
    src = np.repeat(np.arange(len(indptr) - 1), np.diff(indptr))
    return src, np.asarray(indices, dtype=np.int64)


def by_destination(weight, src, dst, num_vertices: int,
                   dtype) -> sp.csr_matrix:
    """``A[v, u]`` = the summed ``weight`` of the edges ``u -> v``, in
    ``dtype``: row ``v`` of ``A @ h`` sums ``v``'s weighted messages."""
    a = sp.csr_matrix((weight, (dst, src)),
                      shape=(num_vertices, num_vertices))
    a.sum_duplicates()
    a.data = a.data.astype(dtype)
    return a


def linear(x: np.ndarray, layer: dict, product, dtype,
           activation: bool) -> np.ndarray:
    """``act(x @ W + b)`` with the layer's ``w`` and ``b``."""
    h = np.asarray(product(x, layer["w"].astype(dtype)), dtype=dtype)
    h += layer["b"].astype(dtype)
    if activation:
        np.maximum(h, 0, out=h)
    return h


def forward(kind: str, indptr, indices, feats: np.ndarray, weights,
            precision: str = "exact") -> np.ndarray:
    """The final layer's rows for every vertex, in vertex-id order."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision must be one of {PRECISIONS}")
    dtype = np.float64 if precision == "exact" else np.float32
    layer_kind = cells.layer_kind(kind)
    graph = layer_kind.reference_graph(indptr, indices, dtype)
    product = functools.partial(_product, precision=precision)
    h = feats.astype(dtype)
    for i, layer in enumerate(weights):
        h = layer_kind.reference_layer(graph, h, layer, product, dtype,
                                       activation=i < len(weights) - 1)
    return h


def max_gap(out: np.ndarray, ref: np.ndarray) -> float:
    """The widest gap between the program's rows and the reference's, as
    a share of the reference's root mean square: ``max|out - ref| /
    rms(ref)``.  A missing, misshapen or non-finite answer reads inf."""
    if out.shape != ref.shape or not np.all(np.isfinite(out)):
        return float("inf")
    diff = np.abs(out.astype(np.float64) - ref)
    rms = float(np.sqrt(np.mean(np.square(ref))))
    return float(diff.max() / rms)
