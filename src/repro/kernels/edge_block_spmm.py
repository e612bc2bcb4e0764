"""ATLAS broadcast aggregation as a TPU Pallas kernel.

The paper's CPU hot loop is ``out[dst[e]] += w[e] * feats[src[e]]`` over a
streamed chunk's edges.  TPUs have no fast random scatter/gather — the
TPU-idiomatic form (DESIGN.md §2) is the **one-hot MXU formulation**:

    msgs = onehot(src) @ feats        (gather  == GEMM on the MXU)
    out += onehot(dst)^T @ (w * msgs) (scatter == GEMM on the MXU)

Both one-hots are built on the fly from an iota comparison (never stored
in HBM).  The kernel tiles edges (Eb), source rows (Vt), destination rows
(DstT) and the feature dim (Db); the out block [DstT, Db] is revisited and
accumulated across the two inner grid axes (edge blocks x source tiles),
which is exactly a blocked SpMM reduction.

Grid: (dst_tiles, d_tiles, e_blocks, src_tiles)   — last axis innermost.
Padding edges carry src = dst = -1, whose one-hot rows are all-zero, so
they contribute nothing (no masking needed).

VMEM working set per step (defaults Eb=256, Vt=1024, DstT=256, Db=128,
fp32): feats 512 KiB + src-onehot 1 MiB + dst-onehot 256 KiB + msgs
128 KiB + out 128 KiB ≈ 2 MiB — comfortably inside the ~16 MiB/core VMEM,
and every matmul dim is a multiple of the 128-lane MXU tile.

Entry points:

* ``edge_block_spmm`` — the general API: pads each operand only when its
  shape is not already block-aligned (an aligned call does **zero**
  device-side copies, fixing the old always-materialize-(vp, dp) cost),
  and picks block sizes with ``auto_blocks`` when none are given.
* ``edge_block_spmm_padded`` — the jitted pre-aligned fast path used by
  ``core.broadcast.PallasChunkAggregator``, which pads on the host into
  reused scratch buffers and ships them with one ``device_put`` each.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


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


def _spmm_kernel(
    src_ref,  # [Eb, 1] int32 (block over e)
    dst_ref,  # [Eb, 1] int32
    w_ref,  # [Eb, 1] f32
    feats_ref,  # [Vt, Db]
    out_ref,  # [DstT, Db] f32 accumulator (revisited over e, v)
):
    j = pl.program_id(0)  # dst tile
    e = pl.program_id(2)  # edge block
    v = pl.program_id(3)  # src tile
    dst_t, db = out_ref.shape
    vt = feats_ref.shape[0]

    @pl.when((e == 0) & (v == 0))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    src = src_ref[:, 0]
    dst = dst_ref[:, 0]
    w = w_ref[:, 0]

    # gather: one-hot over this source tile (rows outside the tile -> 0)
    v_ids = v * vt + jax.lax.broadcasted_iota(jnp.int32, (src.shape[0], vt), 1)
    src_oh = (src[:, None] == v_ids).astype(jnp.float32)
    # HIGHEST: f32 contraction on the MXU; a bf16 pass would round the
    # features past the engine's 1e-4 error bound against the reference
    msgs = jnp.dot(
        src_oh, feats_ref[...].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    msgs = msgs * w[:, None]

    # scatter: one-hot over this destination tile, transposed GEMM
    j_ids = j * dst_t + jax.lax.broadcasted_iota(
        jnp.int32, (dst.shape[0], dst_t), 1
    )
    dst_oh = (dst[:, None] == j_ids).astype(jnp.float32)
    out_ref[...] += jnp.dot(
        dst_oh.T, msgs, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def _spmm_call(
    src_p,  # [ep, 1] int32, -1 sentinel padding
    dst_p,  # [ep, 1] int32, -1 sentinel padding
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
    ep = src_p.shape[0]
    vp, dp = feats_p.shape
    grid = (num_dst_padded // block_dst, dp // block_d, ep // block_e,
            vp // block_v)
    return pl.pallas_call(
        _spmm_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_e, 1), lambda j, k, e, v: (e, 0)),
            pl.BlockSpec((block_e, 1), lambda j, k, e, v: (e, 0)),
            pl.BlockSpec((block_e, 1), lambda j, k, e, v: (e, 0)),
            pl.BlockSpec((block_v, block_d), lambda j, k, e, v: (v, k)),
        ],
        out_specs=pl.BlockSpec((block_dst, block_d), lambda j, k, e, v: (j, k)),
        out_shape=jax.ShapeDtypeStruct((num_dst_padded, dp), jnp.float32),
        interpret=interpret,
    )(src_p, dst_p, w_p, feats_p)


_STATIC = ("block_e", "block_v", "block_dst", "block_d", "num_dst_padded",
           "interpret")
_spmm_jit = jax.jit(_spmm_call, static_argnames=_STATIC)


def edge_block_spmm_padded(
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
    edge padding carries ``src = dst = -1`` and ``w = 0``.  Returns the
    padded ``[num_dst_padded, dp]`` accumulator (slice it yourself)."""
    return _spmm_jit(
        src_p, dst_p, w_p, feats_p,
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

    Block sizes default to ``auto_blocks`` for the operand shapes.  Each
    operand is padded only when its shape is not already a block
    multiple — an aligned call performs no copies at all — and an empty
    edge list short-circuits to zeros without launching the kernel.
    """
    v_src, d = feats.shape
    e = src.shape[0]
    if e == 0:
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
    src = src.astype(jnp.int32)
    dst = dst.astype(jnp.int32)
    w = w.astype(jnp.float32)
    if ep != e:
        src_p = jnp.full((ep, 1), -1, jnp.int32).at[:e, 0].set(src)
        dst_p = jnp.full((ep, 1), -1, jnp.int32).at[:e, 0].set(dst)
        w_p = jnp.zeros((ep, 1), jnp.float32).at[:e, 0].set(w)
    else:
        src_p = src.reshape(ep, 1)
        dst_p = dst.reshape(ep, 1)
        w_p = w.reshape(ep, 1)

    out = edge_block_spmm_padded(
        src_p, dst_p, w_p, feats_p,
        block_e=block_e, block_v=block_v, block_dst=block_dst,
        block_d=block_d, num_dst_padded=jp_, interpret=interpret,
    )
    return out[:num_dst, :d]
