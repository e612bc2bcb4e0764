"""Elementwise max per destination over a chunk's edges, as a TPU Pallas
kernel: the aggregation of GraphSAGE's pooling aggregator.

    out[v] = max_{e : dst[e] = v} rows[src[e]]      (elementwise)

A max cannot ride ``edge_block_spmm``'s scatter GEMM, which sums.  This
kernel keeps that kernel's operands and grid: edges **sorted by
destination**, padded with the ``-1`` sentinel, and one work item per
(destination tile, edge block) pair that holds an edge (``work_items``),
by scalar prefetch:

    grid = (d_tiles, items, src_tiles)   — last axis innermost

Per item:

1. gather: over the source tiles, ``onehot(src) @ rows`` accumulates the
   block's message rows in a VMEM scratch (each edge's source lies in
   one tile, so the sum over tiles is the gathered row, exactly);
2. on the last source tile, a segmented max scan over the block: edges
   sorted by destination form runs, and ``log2(Eb)`` rolled
   compare-and-max steps leave each run's max on one edge of the run;
3. scatter: ``onehot(dst)^T`` over those edges alone (at most one per
   destination row) places each run's max on its row, and the row takes
   the max of that and what earlier items of its tile left there.

The scan and the choice of the edge that holds a run's max use one roll
primitive in both directions (a shift of ``s`` and of ``Eb - 1``), so
they agree whichever way it rotates.  Edges of a neighbouring tile and
the sentinel form runs of their own and match no destination column.

A tile's rows start at ``-inf`` on its first item: a row of a visited
tile with no edge reads ``-inf``, and tiles past the last that holds an
edge are never visited.  ``edge_block_segmax`` turns ``-inf`` into 0 (a
destination with no edge); ``_segmax_call``, the pre-aligned call that
``core.broadcast.PallasChunkAggregator`` jits behind the projection,
leaves the rows as they are, for callers that read only destinations
with edges.

``edge_block_segmax`` is the general API: it sorts and pads on the host,
builds the item tables and picks blocks with ``auto_blocks``.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.edge_block_spmm import _round_up, auto_blocks, work_items


def _segmax_kernel(
    items_ref,  # SMEM int32 [1 + 3 L]: n, tile_of, block_of, first
    src_ref,  # [Eb, 1] int32 (edge block block_of[i])
    dst_ref,  # [Eb, 1] int32
    rows_ref,  # [Vt, Db]
    out_ref,  # [DstT, Db] f32 running max (revisited over i, v)
    msgs_ref,  # VMEM [Eb, Db] f32: the block's gathered rows
):
    i = pl.program_id(1)  # work item
    v = pl.program_id(2)  # src tile
    num_items = (items_ref.shape[0] - 1) // 3
    eb, db = msgs_ref.shape
    dst_t = out_ref.shape[0]
    vt = rows_ref.shape[0]

    @pl.when(i < items_ref[0])  # padding items do nothing
    def _step():
        @pl.when(v == 0)
        def _clear():
            msgs_ref[...] = jnp.zeros_like(msgs_ref)

        # gather: one-hot over this source tile; HIGHEST keeps the rows
        # exact (a one-hot times f32 in three bf16 passes)
        src = src_ref[:, 0]
        v_ids = v * vt + jax.lax.broadcasted_iota(jnp.int32, (eb, vt), 1)
        src_oh = (src[:, None] == v_ids).astype(jnp.float32)
        msgs_ref[...] += jnp.dot(
            src_oh, rows_ref[...].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )

        @pl.when(v == pl.num_programs(2) - 1)
        def _scatter():
            j = items_ref[1 + i]  # dst tile
            x = msgs_ref[...]
            dst = jnp.broadcast_to(dst_ref[...], (eb, db))
            # segmented max scan: after the step of shift s an edge holds
            # the max of up to 2s edges of its run; merging only equal
            # destinations keeps every value inside its run, wrap-around
            # included
            s = 1
            while s < eb:
                same = pltpu.roll(dst, s, 0) == dst
                x = jnp.where(same, jnp.maximum(x, pltpu.roll(x, s, 0)), x)
                s *= 2
            # the run's far end in the scan's direction holds its max: the
            # edge whose neighbour one step the other way is in another
            # run, or lies across the block's wrap
            pos = jax.lax.broadcasted_iota(jnp.int32, (eb, db), 0)
            ends = (pltpu.roll(dst, eb - 1, 0) != dst) | (
                jnp.abs(pltpu.roll(pos, eb - 1, 0) - pos) != 1
            )
            end = jnp.max(ends.astype(jnp.int32), axis=1, keepdims=True)
            j_ids = j * dst_t + jax.lax.broadcasted_iota(
                jnp.int32, (eb, dst_t), 1
            )
            dst_oh = ((dst_ref[...] == j_ids) & (end == 1)).astype(
                jnp.float32
            ).T
            placed = jnp.dot(
                dst_oh, x, precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32,
            )
            has = jnp.sum(dst_oh, axis=1, keepdims=True) > 0
            first = items_ref[1 + 2 * num_items + i] == 1
            prev = jnp.where(first, -jnp.inf, out_ref[...])
            out_ref[...] = jnp.where(has, jnp.maximum(prev, placed), prev)


def _segmax_call(
    items,  # [1 + 3 L] int32, from work_items
    src_p,  # [ep, 1] int32, sorted by dst, -1 sentinel padding
    dst_p,  # [ep, 1] int32, ascending, -1 sentinel padding
    rows_p,  # [vp, dp]
    *,
    block_e: int,
    block_v: int,
    block_dst: int,
    block_d: int,
    num_dst_padded: int,
    interpret: bool,
) -> jax.Array:
    vp, dp = rows_p.shape
    num_items = (items.shape[0] - 1) // 3

    def edge_block(k, i, v, items_ref):
        return items_ref[1 + num_items + i], 0

    def out_block(k, i, v, items_ref):
        return items_ref[1 + i], k

    edge_spec = pl.BlockSpec((block_e, 1), edge_block)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(dp // block_d, num_items, vp // block_v),
        in_specs=[
            edge_spec, edge_spec,
            pl.BlockSpec((block_v, block_d), lambda k, i, v, items_ref: (v, k)),
        ],
        out_specs=pl.BlockSpec((block_dst, block_d), out_block),
        scratch_shapes=[pltpu.VMEM((block_e, block_d), jnp.float32)],
    )
    return pl.pallas_call(
        _segmax_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_dst_padded, dp), jnp.float32),
        interpret=interpret,
        # a stable name for the kernel's device ops in a profiler trace
        name="edge_block_segmax",
    )(items, src_p, dst_p, rows_p)


def edge_block_segmax(
    rows: jax.Array,  # [V_src, D]
    src: jax.Array,  # [E] int32
    dst: jax.Array,  # [E] int32
    num_dst: int,
    *,
    block_e: int | None = None,
    block_v: int | None = None,
    block_dst: int | None = None,
    block_d: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Returns [num_dst, D] f32: the elementwise max of the source rows of
    each destination's edges, 0 for a destination with none.

    The edge list is read on the host (concrete arrays only), sorted by
    destination and padded there; the item tables are built from it.
    Block sizes default to ``auto_blocks`` for the operand shapes.
    """
    v_src, d = rows.shape
    e = src.shape[0]
    if e == 0 or num_dst == 0:
        return jnp.zeros((num_dst, d), jnp.float32)

    a_e, a_v, a_dst, a_d = auto_blocks(v_src, d, e, num_dst, interpret)
    block_e = block_e or a_e
    block_v = block_v or a_v
    block_dst = block_dst or a_dst
    block_d = block_d or a_d

    ep = _round_up(e, block_e)
    vp = _round_up(v_src, block_v)
    jp_ = _round_up(num_dst, block_dst)
    dp = _round_up(d, block_d)

    rows_p = jnp.zeros((vp, dp), rows.dtype).at[:v_src, :d].set(rows)
    dst_h = np.asarray(dst, np.int32)
    order = np.argsort(dst_h, kind="stable")
    src_p = np.full((ep, 1), -1, np.int32)
    dst_p = np.full((ep, 1), -1, np.int32)
    src_p[:e, 0] = np.asarray(src, np.int32)[order]
    dst_p[:e, 0] = dst_h[order]
    items = work_items(
        dst_p[:e, 0], num_dst,
        block_e=block_e, block_dst=block_dst, ep=ep, jp=jp_,
    )
    out = _segmax_call(
        jnp.asarray(items), jnp.asarray(src_p), jnp.asarray(dst_p), rows_p,
        block_e=block_e, block_v=block_v, block_dst=block_dst,
        block_d=block_d, num_dst_padded=jp_, interpret=interpret,
    )[:num_dst, :d]
    return jnp.where(out == -jnp.inf, 0.0, out)
