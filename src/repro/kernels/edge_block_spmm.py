"""ATLAS broadcast aggregation as a TPU Pallas kernel.

The paper's CPU hot loop is ``out[dst[e]] += w[e] * feats[src[e]]`` over a
streamed chunk's edges.  TPUs have no fast random scatter/gather — the
TPU-idiomatic form (DESIGN.md §2) is the **one-hot MXU formulation**:

    msgs = onehot(src) @ feats        (gather  == GEMM on the MXU)
    out += onehot(dst)^T @ (w * msgs) (scatter == GEMM on the MXU)

Both one-hots are built on the fly from an iota comparison (never stored
in HBM).  The kernel tiles edges (Eb), source rows (Vt), destination rows
(DstT) and the feature dim (Db).

Edges arrive **sorted by destination**, so each destination tile owns a
contiguous run of edge blocks.  The grid runs over *work items*, one per
(destination tile, edge block) pair that holds at least one of the
tile's edges, in the style of a grouped matmul:

    grid = (d_tiles, items, src_tiles)   — last axis innermost

The item tables (``work_items``) arrive by scalar prefetch and steer the
BlockSpecs: the edge blocks read ``block_of[i]``, the out block is
``(tile_of[i], k)``.  Items are sorted by tile and the d tiles are
outermost, so an out block is revisited only on consecutive steps: it
accumulates in VMEM, is zeroed on its tile's first item (``first[i]`` and
``v == 0``) and written back once.  An edge of the block that belongs to
a neighbouring tile has an all-zero dst one-hot row, as the ``-1``
padding sentinel has (``src = dst = -1``, ``w = 0``).

Steps: ``items x d_tiles x src_tiles`` with ``items <= e_blocks +
dst_tiles``, against ``dst_tiles x e_blocks x d_tiles x src_tiles`` for a
grid over every pair.  The item count is padded to that static bound
(``max_work_items``), so the compiled programs depend on the padded
shapes alone; padding items repeat the last real item (no new DMA, no
extra write-back) and are skipped.  Output tiles past the last tile that
holds an edge are never visited and hold whatever the buffer held:
callers read only the first ``num_dst`` rows.

VMEM working set per step (defaults Eb=256, Vt=1024, DstT=256, Db=128,
fp32): feats 512 KiB + src-onehot 1 MiB + dst-onehot 256 KiB + msgs
128 KiB + out 128 KiB ≈ 2 MiB —
comfortably inside the ~16 MiB/core VMEM, and every matmul dim is a
multiple of the 128-lane MXU tile.  The item tables sit in SMEM.

Entry points:

* ``edge_block_spmm`` — the general API: sorts the edges by destination
  on the host, builds the item tables, pads each operand to its blocks
  (features only when not already aligned) and picks block sizes with
  ``auto_blocks`` when none are given.
* ``edge_block_spmm_padded`` — the jitted pre-aligned fast path used by
  ``core.broadcast.PallasChunkAggregator``, which sorts and pads on the
  host into reused scratch buffers and ships them with one
  ``device_put`` each.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def auto_blocks(
    v_src: int, d: int, e: int, num_dst: int, interpret: bool = False
) -> tuple[int, int, int, int]:
    """Pick ``(block_e, block_v, block_dst, block_d)`` for a chunk shape.

    On a real TPU the feature/lane blocks stay at the 128-lane MXU tile
    and the edge/source tiles at the documented VMEM budget.  Under
    interpret mode (CPU CI) the lane constraint does not exist, so blocks
    shrink to the operand size rounded to the 8-sublane tile — small
    chunks then pad by at most 7 rows instead of a full 128/256 tile.
    """
    if interpret:
        block_d = min(128, _round_up(max(d, 1), 8))
        block_dst = min(256, _round_up(max(num_dst, 1), 8))
        block_e = min(256, _round_up(max(e, 1), 8))
    else:
        block_d = 128
        block_dst = 256
        block_e = 256
    block_v = min(1024, _round_up(max(v_src, 1), 8))
    # cap the src-onehot tile (block_e x block_v f32) at ~1 MiB
    while block_e * block_v > 256 * 1024 and block_v > 128:
        block_v //= 2
    return block_e, block_v, block_dst, block_d


def max_work_items(ep: int, jp: int, block_e: int, block_dst: int) -> int:
    """Static bound on the work items of ``ep`` padded edges into ``jp``
    padded destination rows: consecutive tiles share at most one edge
    block, so the pairs that hold an edge number at most ``e_blocks +
    dst_tiles``."""
    return ep // block_e + jp // block_dst


def work_items(
    dst_sorted: np.ndarray,  # [m] destinations, ascending
    num_dst: int,
    *,
    block_e: int,
    block_dst: int,
    ep: int,
    jp: int,
) -> np.ndarray:
    """The kernel's item tables for edges sorted by destination.

    Returns one int32 array ``[1 + 3 L]``, ``L = max_work_items(...)``:
    ``[n, tile_of[L], block_of[L], first[L]]``.  Items ``0..n-1`` are the
    (destination tile, edge block) pairs that hold an edge, sorted by
    tile then block, for every tile of ``num_dst`` rows; ``first`` flags
    a tile's first item.  A tile with no edge still gets one item (on
    the block where its edges would start), so its rows are zeroed.
    Items ``n..L-1`` repeat item ``n-1`` with ``first = 0``.  Edges with
    a destination below 0 or at or past ``num_dst`` fall in no tile.
    """
    num_items = max_work_items(ep, jp, block_e, block_dst)
    e_blocks = ep // block_e
    n_tiles = -(-num_dst // block_dst)
    bounds = np.searchsorted(
        dst_sorted, np.arange(n_tiles + 1, dtype=np.int64) * block_dst
    )
    lo, hi = bounds[:-1], bounds[1:]
    b0 = np.minimum(lo // block_e, e_blocks - 1)
    b1 = np.maximum((hi - 1) // block_e, b0)
    count = b1 - b0 + 1
    n = int(count.sum())
    tile_of = np.repeat(np.arange(n_tiles), count)
    starts = np.cumsum(count) - count
    items = np.empty(1 + 3 * num_items, np.int32)
    items[0] = n
    tiles = items[1:1 + num_items]
    blocks = items[1 + num_items:1 + 2 * num_items]
    first = items[1 + 2 * num_items:]
    tiles[:n] = tile_of
    blocks[:n] = b0[tile_of] + np.arange(n) - starts[tile_of]
    first[:n] = 0
    first[starts] = 1
    tiles[n:] = tiles[n - 1]
    blocks[n:] = blocks[n - 1]
    first[n:] = 0
    return items


def _spmm_kernel(
    items_ref,  # SMEM int32 [1 + 3 L]: n, tile_of, block_of, first
    src_ref,  # [Eb, 1] int32 (edge block block_of[i])
    dst_ref,  # [Eb, 1] int32
    w_ref,  # [Eb, 1] f32
    feats_ref,  # [Vt, Db]
    out_ref,  # [DstT, Db] f32 accumulator (revisited over i, v)
):
    i = pl.program_id(1)  # work item
    v = pl.program_id(2)  # src tile
    num_items = (items_ref.shape[0] - 1) // 3
    dst_t = out_ref.shape[0]
    vt = feats_ref.shape[0]

    @pl.when(i < items_ref[0])  # padding items do nothing
    def _step():
        j = items_ref[1 + i]  # dst tile

        @pl.when((items_ref[1 + 2 * num_items + i] == 1) & (v == 0))
        def _init():
            out_ref[...] = jnp.zeros_like(out_ref)

        src = src_ref[:, 0]
        dst = dst_ref[:, 0]
        w = w_ref[:, 0]

        # gather: one-hot over this source tile (rows outside the tile -> 0)
        v_ids = v * vt + jax.lax.broadcasted_iota(
            jnp.int32, (src.shape[0], vt), 1
        )
        src_oh = (src[:, None] == v_ids).astype(jnp.float32)
        # HIGHEST: f32 contraction on the MXU; a bf16 pass would round the
        # features past the engine's 1e-4 error bound against the reference
        msgs = jnp.dot(
            src_oh, feats_ref[...].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )
        msgs = msgs * w[:, None]

        # scatter: one-hot over this destination tile, transposed GEMM;
        # the block's edges of other tiles match no column
        j_ids = j * dst_t + jax.lax.broadcasted_iota(
            jnp.int32, (dst.shape[0], dst_t), 1
        )
        dst_oh = (dst[:, None] == j_ids).astype(jnp.float32)
        out_ref[...] += jnp.dot(
            dst_oh.T, msgs, precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )


def _spmm_call(
    items,  # [1 + 3 L] int32, from work_items
    src_p,  # [ep, 1] int32, sorted by dst, -1 sentinel padding
    dst_p,  # [ep, 1] int32, ascending, -1 sentinel padding
    w_p,  # [ep, 1] f32, zero padding
    feats_p,  # [vp, dp]
    *,
    block_e: int,
    block_v: int,
    block_dst: int,
    block_d: int,
    num_dst_padded: int,
    interpret: bool,
) -> jax.Array:
    vp, dp = feats_p.shape
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
            edge_spec, edge_spec, edge_spec,
            pl.BlockSpec((block_v, block_d), lambda k, i, v, items_ref: (v, k)),
        ],
        out_specs=pl.BlockSpec((block_dst, block_d), out_block),
    )
    return pl.pallas_call(
        _spmm_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_dst_padded, dp), jnp.float32),
        interpret=interpret,
        # a stable name for the kernel's device ops in a profiler trace
        name="edge_block_spmm",
    )(items, src_p, dst_p, w_p, feats_p)


_STATIC = ("block_e", "block_v", "block_dst", "block_d", "num_dst_padded",
           "interpret")
_spmm_jit = jax.jit(_spmm_call, static_argnames=_STATIC)


def edge_block_spmm_padded(
    items: jax.Array,
    src_p: jax.Array,
    dst_p: jax.Array,
    w_p: jax.Array,
    feats_p: jax.Array,
    *,
    block_e: int,
    block_v: int,
    block_dst: int,
    block_d: int,
    num_dst_padded: int,
    interpret: bool = False,
) -> jax.Array:
    """Pre-aligned fast path: every operand already a block multiple,
    edges sorted by destination, edge padding carries ``src = dst = -1``
    and ``w = 0``, ``items`` from ``work_items`` over the same edges.
    Returns the padded ``[num_dst_padded, dp]`` accumulator, whose rows
    past the last tile that holds an edge are not written (slice it
    yourself)."""
    return _spmm_jit(
        items, src_p, dst_p, w_p, feats_p,
        block_e=block_e, block_v=block_v, block_dst=block_dst,
        block_d=block_d, num_dst_padded=num_dst_padded, interpret=interpret,
    )


def edge_block_spmm(
    feats: jax.Array,  # [V_src, D]
    src: jax.Array,  # [E] int32
    dst: jax.Array,  # [E] int32
    w: jax.Array,  # [E] float32
    num_dst: int,
    *,
    block_e: int | None = None,
    block_v: int | None = None,
    block_dst: int | None = None,
    block_d: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Returns [num_dst, D] f32: segment-sum of w-scaled source rows.

    The edge list is read on the host (concrete arrays only), sorted by
    destination and padded there; the item tables are built from it.
    Block sizes default to ``auto_blocks`` for the operand shapes.  The
    features are padded only when their shape is not already a block
    multiple, and an empty edge list short-circuits to zeros without
    launching the kernel.
    """
    v_src, d = feats.shape
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

    if (vp, dp) != (v_src, d):
        feats_p = jnp.zeros((vp, dp), feats.dtype).at[:v_src, :d].set(feats)
    else:
        feats_p = feats
    dst_h = np.asarray(dst, np.int32)
    order = np.argsort(dst_h, kind="stable")
    src_p = np.full((ep, 1), -1, np.int32)
    dst_p = np.full((ep, 1), -1, np.int32)
    w_p = np.zeros((ep, 1), np.float32)
    src_p[:e, 0] = np.asarray(src, np.int32)[order]
    dst_p[:e, 0] = dst_h[order]
    w_p[:e, 0] = np.asarray(w, np.float32)[order]
    items = work_items(
        dst_p[:e, 0], num_dst,
        block_e=block_e, block_dst=block_dst, ep=ep, jp=jp_,
    )

    out = edge_block_spmm_padded(
        jnp.asarray(items), jnp.asarray(src_p), jnp.asarray(dst_p),
        jnp.asarray(w_p), feats_p,
        block_e=block_e, block_v=block_v, block_dst=block_dst,
        block_d=block_d, num_dst_padded=jp_, interpret=interpret,
    )
    return out[:num_dst, :d]
