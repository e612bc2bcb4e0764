"""Plain reference of the benchmark's GNN layers, and the comparison that
decides ``correct``.

It follows the published layer equations and imports nothing of the
program:

    GCN   h'_v = act(sum_{u->v} h_u / sqrt(d(u) d(v)) @ W + b)
          (self-loops are in the topology, d counts them)
    SAGE  h'_v = act([h_v ; mean_{u->v} h_u] @ W + b)

with ``act`` = ReLU on every layer but the last, and ``d`` the in-degree.

``precision="exact"`` computes in float64: the reference.  ``"high"`` is
the control: every product of two float32 numbers is taken as TPU's
``Precision.HIGH`` takes it, in three bfloat16 passes
(``a_hi*b_hi + a_hi*b_lo + a_lo*b_hi``, each product exact in float32),
summed in float32, and every intermediate is stored in float32.
"""

from __future__ import annotations

import numpy as np
import scipy.sparse as sp

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


def aggregation_matrix(kind: str, indptr, indices) -> sp.csr_matrix:
    """``A[v, u]`` = the weight of edge ``u -> v`` for this layer kind."""
    num_vertices = len(indptr) - 1
    deg = np.maximum(in_degrees(indptr, indices), 1).astype(np.float64)
    src = np.repeat(np.arange(num_vertices), np.diff(indptr))
    dst = np.asarray(indices, dtype=np.int64)
    if kind == "gcn":
        w = 1.0 / np.sqrt(deg[src] * deg[dst])
    elif kind == "sage":
        w = 1.0 / deg[dst]
    else:
        raise ValueError(f"unknown GNN kind {kind!r}")
    a = sp.csr_matrix((w, (dst, src)), shape=(num_vertices, num_vertices))
    a.sum_duplicates()
    return a


def forward(kind: str, indptr, indices, feats: np.ndarray, weights,
            precision: str = "exact") -> np.ndarray:
    """The final layer's rows for every vertex, in vertex-id order."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision must be one of {PRECISIONS}")
    dtype = np.float64 if precision == "exact" else np.float32
    a = aggregation_matrix(kind, indptr, indices)
    if precision != "exact":
        a.data = a.data.astype(np.float32)
    h = feats.astype(dtype)
    for i, layer in enumerate(weights):
        agg = np.asarray(_product(a, h, precision), dtype=dtype)
        x = np.concatenate([h, agg], axis=1) if kind == "sage" else agg
        del agg
        w = layer["w"].astype(dtype)
        h = np.asarray(_product(x, w, precision), dtype=dtype)
        h += layer["b"].astype(dtype)
        del x
        if i < len(weights) - 1:
            np.maximum(h, 0, out=h)
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
