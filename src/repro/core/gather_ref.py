"""Gather-based baselines (paper §2.2-2.3, Fig 1/4 comparison points).

* ``layerwise_gather`` — DGI-style: layer-at-a-time, but each destination
  batch *pulls* its in-neighbors' rows from disk.  Reads are accounted at
  block granularity (4 KiB default): scattered single-row reads fetch whole
  blocks, and rows shared across batches are re-fetched — read volume
  scales with |E|, not |V|.
* ``vertexwise_gather`` — Ginex-style inference: per target batch, expand
  the full (unsampled) k-hop computation graph and pull every feature it
  needs; redundant both in I/O and compute.

Both produce numerically correct outputs (same oracle semantics), so the
benchmark compares *systems*, not approximations.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from repro.graphs.csr import CSRGraph, csr_to_csc, degrees_from_csr
from repro.models.gnn import (
    GNNLayerSpec,
    aggregate_neighbours,
    edge_weights,
    layer_update,
    self_coefficient,
)


@dataclasses.dataclass
class GatherStats:
    bytes_read: int = 0
    block_reads: int = 0
    rows_requested: int = 0
    compute_vertex_visits: int = 0


class BlockAccountant:
    """Models disk reads at block granularity over a row-major feature file.

    A batch's row set is deduplicated (an in-memory batch buffer, like
    DGI's), but nothing is cached *across* batches — matching the paper's
    observation that OOC gather re-fetches shared rows once per batch.
    """

    def __init__(self, row_bytes: int, block_bytes: int = 4096):
        self.row_bytes = row_bytes
        self.block_bytes = block_bytes

    def bytes_for_rows(self, row_ids: np.ndarray) -> tuple[int, int]:
        if len(row_ids) == 0:
            return 0, 0
        row_ids = np.unique(row_ids)
        starts = row_ids.astype(np.int64) * self.row_bytes
        ends = starts + self.row_bytes
        first_blk = starts // self.block_bytes
        last_blk = (ends - 1) // self.block_bytes
        # count distinct blocks across all row extents
        blocks = np.unique(
            np.concatenate(
                [np.arange(f, l + 1) for f, l in zip(first_blk, last_blk)]
            )
        )
        return len(blocks) * self.block_bytes, len(blocks)


def layerwise_gather(
    csr: CSRGraph,
    features: np.ndarray,
    specs: list[GNNLayerSpec],
    batch_size: int = 4096,
    block_bytes: int = 4096,
) -> tuple[np.ndarray, GatherStats]:
    """DGI-style layer-wise inference with per-batch neighbor gathers."""
    csc = csr_to_csc(csr)  # in-neighbors per destination
    in_deg, _ = degrees_from_csr(csr)
    stats = GatherStats()
    h = features.astype(np.float32)
    v = csr.num_vertices
    for spec in specs:
        acct = BlockAccountant(spec.in_dim * 4, block_bytes)
        out = np.empty((v, spec.out_dim), dtype=np.float32)
        for s in range(0, v, batch_size):
            e = min(s + batch_size, v)
            dst_local = np.arange(s, e)
            # pull in-neighbor lists (CSC) for this destination batch
            lo, hi = csc.indptr[s], csc.indptr[e]
            src = np.asarray(csc.indices[lo:hi], dtype=np.int64)
            counts = np.diff(csc.indptr[s : e + 1])
            dst = np.repeat(dst_local, counts)
            # disk model: gather unique neighbor rows at block granularity
            need = np.unique(np.concatenate([src, dst_local]))
            b, n = acct.bytes_for_rows(need)
            stats.bytes_read += b
            stats.block_reads += n
            stats.rows_requested += len(need)
            w = edge_weights(spec.kind, src, dst, in_deg)
            agg = aggregate_neighbours(spec, h[src], dst - s, e - s, w)
            if spec.concat_self:
                agg = np.concatenate(
                    [h[s:e] * self_coefficient(spec), agg], axis=1
                )
            elif spec.kind == "gin":
                agg = agg + h[s:e] * self_coefficient(spec)
            out[s:e] = layer_update(spec, agg)
            stats.compute_vertex_visits += e - s
        h = out
    return h, stats


def vertexwise_gather(
    csr: CSRGraph,
    features: np.ndarray,
    specs: list[GNNLayerSpec],
    batch_size: int = 1024,
    block_bytes: int = 4096,
) -> tuple[np.ndarray, GatherStats]:
    """Ginex-style inference: per batch, materialise the full k-hop
    computation graph and recompute every intermediate — neighborhood
    explosion in both reads and compute (paper challenge (3))."""
    csc = csr_to_csc(csr)
    in_deg, _ = degrees_from_csr(csr)
    stats = GatherStats()
    v = csr.num_vertices
    L = len(specs)
    feat = features.astype(np.float32)
    out = np.empty((v, specs[-1].out_dim), dtype=np.float32)
    acct = BlockAccountant(specs[0].in_dim * 4, block_bytes)

    def in_neighbors(vs: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        counts = (csc.indptr[vs + 1] - csc.indptr[vs]).astype(np.int64)
        idx = np.concatenate(
            [np.asarray(csc.indices[csc.indptr[x] : csc.indptr[x + 1]]) for x in vs]
        ) if len(vs) else np.empty(0, dtype=np.int64)
        return idx.astype(np.int64), counts

    for s in range(0, v, batch_size):
        e = min(s + batch_size, v)
        # frontier expansion: layers deep -> shallow
        frontiers = [np.arange(s, e, dtype=np.int64)]
        for _ in range(L):
            src, _ = in_neighbors(frontiers[-1])
            frontiers.append(np.unique(np.concatenate([frontiers[-1], src])))
        needed = frontiers[-1]
        b, n = acct.bytes_for_rows(needed)
        stats.bytes_read += b
        stats.block_reads += n
        stats.rows_requested += len(needed)
        # recursive forward over the computation graph
        h = {int(x): feat[x] for x in needed}
        hcur = feat[needed]
        pos = {int(x): i for i, x in enumerate(needed)}
        for li, spec in enumerate(specs):
            tgt = frontiers[L - 1 - li]
            src, counts = in_neighbors(tgt)
            dstrep = np.repeat(tgt, counts)
            w = edge_weights(spec.kind, src, dstrep, in_deg)
            src_rows = hcur[[pos[int(x)] for x in src]] if len(src) else np.empty((0, spec.in_dim), np.float32)
            tgt_index = {int(x): i for i, x in enumerate(tgt)}
            agg = aggregate_neighbours(
                spec, src_rows,
                np.asarray([tgt_index[int(x)] for x in dstrep], np.int64),
                len(tgt), w,
            )
            self_rows = hcur[[pos[int(x)] for x in tgt]]
            if spec.concat_self:
                agg = np.concatenate([self_rows * self_coefficient(spec), agg], axis=1)
            elif spec.kind == "gin":
                agg = agg + self_rows * self_coefficient(spec)
            hnext = layer_update(spec, agg)
            stats.compute_vertex_visits += len(tgt)
            pos = {int(x): i for i, x in enumerate(tgt)}
            hcur = hnext
        out[s:e] = hcur[[pos[int(x)] for x in range(s, e)]]
    return out, stats
