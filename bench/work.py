"""Operations and bytes of a pass, from the configuration alone.

Counted on the algorithm's work: the vertex count V, the edge count E
and the widths.  Never on the padded operands, the kernel's grid or the
program's chunking, so a kernel that skips empty blocks, or another
chunk size, reads against the same work.

Each layer kind counts its own work in ``kinds/<kind>.py``; the
functions here keep their signatures and ask it.  The two kinds there
share the arithmetic below.  For layer ``l`` with input width ``d`` and
output width ``d_out``:

* messages ``M`` (GCN: ``E``, its self-loops are edges; SAGE: ``E + V``,
  one self message per vertex);
* aggregation as a weighted sum: ``2 M d`` operations (a multiply and an
  add per message element); ``4 V d`` bytes to read each source row
  once, ``12 M`` bytes of edge records (source, destination, weight:
  4 B each) and ``4 V d`` bytes to write one output row per destination;
* update as one dense product: ``2 V h d_out`` operations, ``h`` the hot
  width (``2 d`` for SAGE's ``[self ; neighbours]``, ``d`` for GCN).
"""

from __future__ import annotations

from bench import cells

F32 = 4
EDGE_RECORD_BYTES = 12


def weighted_sum_work(num_vertices: int, num_messages: int,
                      d_in: int) -> tuple[int, int]:
    """``(operations, bytes)`` of summing ``num_messages`` weighted rows
    of width ``d_in`` into one row per destination."""
    flops = 2 * num_messages * d_in
    nbytes = F32 * num_vertices * d_in + EDGE_RECORD_BYTES * num_messages \
        + F32 * num_vertices * d_in
    return flops, nbytes


def dense_update_flops(num_vertices: int, hot: int, d_out: int) -> int:
    return 2 * num_vertices * hot * d_out


def messages(kind: str, num_vertices: int, num_edges: int) -> int:
    return cells.layer_kind(kind).messages(num_vertices, num_edges)


def hot_width(kind: str, d_in: int) -> int:
    return cells.layer_kind(kind).hot_width(d_in)


def aggregation_work(kind: str, num_vertices: int, num_edges: int,
                     d_in: int) -> tuple[int, int]:
    """``(operations, bytes)`` of one layer's aggregation."""
    return cells.layer_kind(kind).aggregation_work(num_vertices, num_edges,
                                                   d_in)


def update_flops(kind: str, num_vertices: int, d_in: int, d_out: int) -> int:
    return cells.layer_kind(kind).update_flops(num_vertices, d_in, d_out)


def aggregation_least_seconds(kind: str, num_vertices: int, num_edges: int,
                              dims, peak_flops: float,
                              peak_bytes_per_s: float) -> dict:
    """Least time of a pass's aggregations: per layer the larger of
    operations over peak FLOP/s and bytes over peak bandwidth, summed.
    Also says which bound is the larger in each layer."""
    total, bounds = 0.0, []
    for d_in in dims[:-1]:
        flops, nbytes = aggregation_work(kind, num_vertices, num_edges, d_in)
        t_flops, t_bytes = flops / peak_flops, nbytes / peak_bytes_per_s
        total += max(t_flops, t_bytes)
        bounds.append("compute" if t_flops >= t_bytes else "bandwidth")
    return {"seconds": total, "bounds": bounds}


def pass_model_flops(kind: str, num_vertices: int, num_edges: int,
                     dims) -> int:
    """Model operations of one whole pass: aggregation plus update, over
    every layer."""
    total = 0
    for d_in, d_out in zip(dims[:-1], dims[1:]):
        total += aggregation_work(kind, num_vertices, num_edges, d_in)[0]
        total += update_flops(kind, num_vertices, d_in, d_out)
    return total
