"""Message-passing GNN layer definitions + in-memory reference oracle.

The three models evaluated in the paper (§4.1): GraphConv/GCN [Kipf &
Welling], SAGEConv (mean) [Hamilton et al.] and GINConv [Xu et al.],
and GraphSAGE's pooling aggregator [Hamilton et al., §3.3].  Each layer
is described by a ``GNNLayerSpec`` that the broadcast engine, the gather
baselines, and the dense oracle all consume, so semantic equivalence is
checked against one single definition:

  GCN       m_{u->v} = h_u / sqrt(d_in(u) d_in(v))  (self-loops in topology)
            h'_v = act(W @ Σ m + b)
  SAGE      m_{u->v} = h_u / d_in(v)                (mean over in-neighbors)
            h'_v = act(W @ [h_v ; Σ m] + b)         (self-concat)
  SAGEPOOL  m_{u->v} = ReLU(h_u @ W_pool + b_pool)
            h'_v = act(W @ [h_v ; max m] + b)       (elementwise max; 0
                                                     with no in-edges)
  GIN       m_{u->v} = h_u
            h'_v = MLP((1+eps) h_v + Σ m)           (2-layer MLP)

The broadcast engine realises the self term for SAGE/SAGEPOOL/GIN as an
extra "self message" deposited when the vertex's own source chunk
streams by (required message count = d_in + 1), and for GCN via
self-loops.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import numpy as np

from repro.graphs.csr import CSRGraph, degrees_from_csr


@dataclasses.dataclass(frozen=True)
class GNNLayerSpec:
    kind: str  # 'gcn' | 'sage' | 'sagepool' | 'gin'
    in_dim: int
    out_dim: int
    activation: bool  # ReLU after update (False on final layer)
    params: dict  # numpy weights

    @property
    def hot_width(self) -> int:
        """Columns of partial state per vertex in the hot store.

        SAGE doubles the width (self ; neighbor-agg) — the paper calls out
        the resulting eviction pressure explicitly (§4.3).
        """
        return 2 * self.in_dim if self.concat_self else self.in_dim

    @property
    def concat_self(self) -> bool:
        """Hot layout ``[self ; neighbours]``: the self message fills
        columns ``[0, d)`` and the neighbour aggregate ``[d, 2d)``."""
        return self.kind in ("sage", "sagepool")

    @property
    def neighbour_offset(self) -> int:
        """First hot-store column of the neighbour aggregate."""
        return self.in_dim if self.concat_self else 0

    @property
    def aggregation(self) -> str:
        """How a destination combines its neighbours' messages: ``"sum"``
        of weighted rows, or the pooling aggregator's elementwise
        ``"max"`` of projected rows (every one ``>= 0``)."""
        return "max" if self.kind == "sagepool" else "sum"

    @property
    def source_projection(self):
        """``(w_pool, b_pool)`` of the pooling aggregator's message
        ``ReLU(h_u @ w_pool + b_pool)``; None for the other kinds."""
        if self.kind != "sagepool":
            return None
        return self.params["w_pool"], self.params["b_pool"]

    @property
    def extra_self_message(self) -> bool:
        return self.kind in ("sage", "sagepool", "gin")


def init_gnn_params(
    kind: str, dims: Sequence[int], seed: int = 0, gin_eps: float = 0.0
) -> list[GNNLayerSpec]:
    """Glorot-initialised stack of layers; dims = [in, hidden, ..., out]."""
    rng = np.random.default_rng(seed)
    specs = []
    for i in range(len(dims) - 1):
        d_in, d_out = dims[i], dims[i + 1]
        final = i == len(dims) - 2
        if kind == "gcn":
            w = _glorot(rng, (d_in, d_out))
            params = {"w": w, "b": np.zeros(d_out, np.float32)}
        elif kind == "sage":
            w = _glorot(rng, (2 * d_in, d_out))
            params = {"w": w, "b": np.zeros(d_out, np.float32)}
        elif kind == "sagepool":
            params = {
                "w_pool": _glorot(rng, (d_in, d_in)),
                "b_pool": np.zeros(d_in, np.float32),
                "w": _glorot(rng, (2 * d_in, d_out)),
                "b": np.zeros(d_out, np.float32),
            }
        elif kind == "gin":
            h = max(d_in, d_out)
            params = {
                "w1": _glorot(rng, (d_in, h)),
                "b1": np.zeros(h, np.float32),
                "w2": _glorot(rng, (h, d_out)),
                "b2": np.zeros(d_out, np.float32),
                "eps": np.float32(gin_eps),
            }
        else:
            raise ValueError(f"unknown GNN kind {kind!r}")
        specs.append(
            GNNLayerSpec(
                kind=kind,
                in_dim=d_in,
                out_dim=d_out,
                activation=not final,
                params=params,
            )
        )
    return specs


def _glorot(rng, shape) -> np.ndarray:
    limit = np.sqrt(6.0 / (shape[0] + shape[1]))
    return rng.uniform(-limit, limit, size=shape).astype(np.float32)


# --------------------------------------------------------------------------
# Edge weights (message normalisation, applied at construction time, §3.4)
# --------------------------------------------------------------------------


def edge_weights(
    kind: str, src: np.ndarray, dst: np.ndarray, in_deg: np.ndarray
) -> np.ndarray | None:
    """Per-edge scalar applied to the source embedding; None for the
    pooling aggregator, whose max takes no weights."""
    if kind == "gcn":
        d = np.maximum(in_deg, 1).astype(np.float64)
        return (1.0 / np.sqrt(d[src] * d[dst])).astype(np.float32)
    if kind == "sage":
        d = np.maximum(in_deg, 1).astype(np.float64)
        return (1.0 / d[dst]).astype(np.float32)
    if kind == "gin":
        return np.ones(len(src), dtype=np.float32)
    if kind == "sagepool":
        return None
    raise ValueError(kind)


def self_coefficient(spec: GNNLayerSpec) -> float:
    """Scale applied to a vertex's own embedding in its self message."""
    if spec.kind == "gin":
        return 1.0 + float(spec.params["eps"])
    return 1.0  # sage, sagepool: raw copy into the self half


def pool_projection(projection, h: np.ndarray) -> np.ndarray:
    """The pooling aggregator's messages ``ReLU(h @ w_pool + b_pool)``,
    ``projection`` being ``(w_pool, b_pool)``."""
    w_pool, b_pool = projection
    return np.maximum(h @ w_pool + b_pool, 0.0).astype(np.float32)


def aggregate_neighbours(
    spec: GNNLayerSpec,
    src_rows: np.ndarray,  # [m, d_in] the source row of each edge
    dst: np.ndarray,  # [m] the edge's destination, in [0, num_dst)
    num_dst: int,
    w: np.ndarray | None,  # [m] edge weights (sum aggregation)
) -> np.ndarray:
    """``[num_dst, d_in]``: per destination the weighted sum of its edges'
    rows, or for the pooling aggregator the elementwise max of their
    projections.  Every projection is ``>= 0`` after the ReLU, so the zero
    fill is the max's identity, and a destination with no edge reads 0."""
    agg = np.zeros((num_dst, spec.in_dim), dtype=np.float32)
    if spec.aggregation == "max":
        rows = pool_projection(spec.source_projection, src_rows)
        np.maximum.at(agg, dst, rows)
    else:
        np.add.at(agg, dst, src_rows * w[:, None])
    return agg


# --------------------------------------------------------------------------
# Layer update (the graduation transform — the accelerator step)
# --------------------------------------------------------------------------


def layer_update(spec: GNNLayerSpec, agg: np.ndarray) -> np.ndarray:
    """Dense transform on finalized aggregate rows [n, hot_width]."""
    if spec.kind in ("gcn", "sage", "sagepool"):
        out = agg @ spec.params["w"] + spec.params["b"]
    elif spec.kind == "gin":
        h = agg @ spec.params["w1"] + spec.params["b1"]
        h = np.maximum(h, 0.0)
        out = h @ spec.params["w2"] + spec.params["b2"]
    else:
        raise ValueError(spec.kind)
    if spec.activation:
        out = np.maximum(out, 0.0)
    return out.astype(np.float32)


# --------------------------------------------------------------------------
# Dense in-memory reference (the oracle, paper §4.1's "reference")
# --------------------------------------------------------------------------


def dense_reference(
    csr: CSRGraph, features: np.ndarray, specs: list[GNNLayerSpec]
) -> np.ndarray:
    """Full-graph layer-wise inference, everything in memory.

    Used to validate broadcast == gather == reference (paper reports
    mean-max-abs err 8e-5 on Papers at fp32).
    """
    in_deg, _ = degrees_from_csr(csr)
    src, dst = csr.edges_for_range(0, csr.num_vertices)
    h = features.astype(np.float32)
    for spec in specs:
        w = edge_weights(spec.kind, src, dst, in_deg)
        agg = aggregate_neighbours(spec, h[src], dst, csr.num_vertices, w)
        if spec.concat_self:
            agg = np.concatenate([h * self_coefficient(spec), agg], axis=1)
        elif spec.kind == "gin":
            agg = agg + h * self_coefficient(spec)
        h = layer_update(spec, agg)
    return h
