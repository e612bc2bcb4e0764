"""Broadcast chunk compute core (paper §3.1, Fig 2c).

For one streamed chunk of source vertices, construct all outgoing messages
(m_{u->v} = w(u,v) * h_u) and pre-aggregate them *by destination* so the
memory manager touches each destination slot exactly once per chunk.

Three interchangeable backends, selected by ``AtlasConfig.backend``:

  * numpy  — sort-by-destination + ``np.add.reduceat`` (host fallback;
             default on this CPU-only container),
  * jax    — gather/scale/``segment_sum`` jit; the semantics twin of the
             Pallas kernel and the reference it is atol-tested against,
  * pallas — the ``edge_block_spmm`` one-hot MXU kernel (kernels/),
             compiled for the TPU.  It refuses to run on a host whose
             device is not a TPU; ``pallas-interpret`` runs the same kernel
             body in interpret mode on any host (CI, equivalence tests).

All backends share one contract::

    (unique_dst int64 [s], partial float32 [s, d], counts int64 [s])

with ``unique_dst`` sorted ascending — callers (``_deliver``) rely on one
row per distinct destination.  The jax/pallas backends are *objects* (not
bare functions) so they can carry reusable host scratch between chunks
and time each step of the round trip (``dedup_seconds``, ``h2d_seconds``,
``kernel_wait_seconds``, ``d2h_seconds``) apart.
"""

from __future__ import annotations

import functools
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.kernels.edge_block_spmm import (
    auto_blocks,
    edge_block_spmm_padded,
)
from repro.obs.trace import NULL_TRACER


def chunk_aggregate_numpy(
    feats: np.ndarray,  # [n, d] chunk features (source rows)
    src_local: np.ndarray,  # [m] edge sources, chunk-local indices
    dst: np.ndarray,  # [m] edge destinations, global ids
    weights: np.ndarray,  # [m] per-edge scalars
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Returns (unique_dst, partial_sums[, counts]): one row per distinct
    destination touched by this chunk."""
    if len(dst) == 0:
        return (
            np.empty(0, dtype=np.int64),
            np.empty((0, feats.shape[1]), dtype=np.float32),
            np.empty(0, dtype=np.int64),
        )
    order = np.argsort(dst, kind="stable")
    sdst = dst[order]
    msgs = feats[src_local[order]].astype(np.float32)
    msgs *= weights[order][:, None]
    # segment boundaries over the destination-sorted edge list
    starts = np.nonzero(np.r_[True, sdst[1:] != sdst[:-1]])[0]
    unique_dst = sdst[starts].astype(np.int64)
    partial = np.add.reduceat(msgs, starts, axis=0)
    counts = np.diff(np.r_[starts, len(sdst)]).astype(np.int64)
    return unique_dst, partial, counts


@functools.partial(jax.jit, static_argnames=("num_segments",))
def _segment_messages(feats, src_local, seg_ids, weights, num_segments):
    msgs = feats[src_local] * weights[:, None]
    return jax.ops.segment_sum(msgs, seg_ids, num_segments=num_segments)


def chunk_aggregate_jax(
    feats: np.ndarray,
    src_local: np.ndarray,
    dst: np.ndarray,
    weights: np.ndarray,
    pad_to: int | None = None,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """JAX path: host computes the destination dictionary (data-dependent),
    device does gather*scale -> segment_sum.  ``pad_to`` buckets the edge
    count to bound recompilation (powers of two by default)."""
    if len(dst) == 0:
        return (
            np.empty(0, dtype=np.int64),
            np.empty((0, feats.shape[1]), dtype=np.float32),
            np.empty(0, dtype=np.int64),
        )
    unique_dst, seg_ids, counts = np.unique(
        dst, return_inverse=True, return_counts=True
    )
    m = len(dst)
    pad = pad_to if pad_to is not None else 1 << (m - 1).bit_length()
    n_seg = len(unique_dst)
    src_p = np.zeros(pad, dtype=np.int32)
    src_p[:m] = src_local
    seg_p = np.full(pad, n_seg, dtype=np.int32)  # padding lands in a dump row
    seg_p[:m] = seg_ids
    w_p = np.zeros(pad, dtype=np.float32)
    w_p[:m] = weights
    out = _segment_messages(
        jnp.asarray(feats, jnp.float32),
        jnp.asarray(src_p),
        jnp.asarray(seg_p),
        jnp.asarray(w_p),
        num_segments=n_seg + 1,
    )
    return (
        unique_dst.astype(np.int64),
        np.asarray(out[:n_seg]),
        counts.astype(np.int64),
    )


class JaxChunkAggregator:
    """``chunk_aggregate_jax`` semantics with the round trip timed.

    Same outputs as the bare function (shares ``_segment_messages``).
    Each step of the per-chunk round trip is timed into its own counter
    and traced under its own span: the host dictionary and padding
    (``dedup_seconds``), the device_put of the four operands onto
    ``device`` (``h2d_seconds``), the kernel call and the wait for it
    (``kernel_wait_seconds``) and the copy of the output back to the host
    (``d2h_seconds``).
    """

    backend = "jax"

    def __init__(self, device: jax.Device) -> None:
        self.device = device
        self.dedup_seconds = 0.0
        self.h2d_seconds = 0.0
        self.kernel_wait_seconds = 0.0
        self.d2h_seconds = 0.0
        self.tracer = NULL_TRACER

    def __call__(self, feats, src_local, dst, weights):
        if len(dst) == 0:
            return (
                np.empty(0, dtype=np.int64),
                np.empty((0, feats.shape[1]), dtype=np.float32),
                np.empty(0, dtype=np.int64),
            )
        tr = self.tracer
        with tr.span("dedup", "dedup"):
            t0 = time.perf_counter()
            unique_dst, seg_ids, counts = np.unique(
                dst, return_inverse=True, return_counts=True
            )
            m = len(dst)
            pad = 1 << (m - 1).bit_length()
            n_seg = len(unique_dst)
            src_p = np.zeros(pad, dtype=np.int32)
            src_p[:m] = src_local
            seg_p = np.full(pad, n_seg, dtype=np.int32)
            seg_p[:m] = seg_ids
            w_p = np.zeros(pad, dtype=np.float32)
            w_p[:m] = weights
            self.dedup_seconds += time.perf_counter() - t0
        with tr.span("h2d", "h2d"):
            t0 = time.perf_counter()
            feats_d, src_d, seg_d, w_d = (
                jax.device_put(x, self.device)
                for x in (np.ascontiguousarray(feats, np.float32),
                          src_p, seg_p, w_p)
            )
            jax.block_until_ready((feats_d, src_d, seg_d, w_d))
            self.h2d_seconds += time.perf_counter() - t0
        with tr.span("kernel_wait", "kernel"):
            t0 = time.perf_counter()
            out = _segment_messages(
                feats_d, src_d, seg_d, w_d, num_segments=n_seg + 1
            )
            out.block_until_ready()
            self.kernel_wait_seconds += time.perf_counter() - t0
        with tr.span("d2h", "d2h"):
            t0 = time.perf_counter()
            host = np.asarray(out)
            self.d2h_seconds += time.perf_counter() - t0
        return (
            unique_dst.astype(np.int64),
            host[:n_seg],
            counts.astype(np.int64),
        )


def _pow2_tiles(n: int, block: int) -> int:
    """Round ``n`` up to ``block * 2**k`` tiles — the static-shape buckets
    that bound jit recompiles when edge/segment counts drift per chunk."""
    tiles = -(-max(n, 1) // block)
    return block * (1 << (tiles - 1).bit_length())


def padded_dims(
    blocks: tuple[int, int, int, int], n: int, d: int, m: int, n_seg: int
) -> tuple[int, int, int, int]:
    """Padded kernel operand dims ``(ep, vp, dp, jp)`` — edges, source
    rows, feature width, destination segments — for a chunk of ``n``
    source rows of width ``d`` with ``m`` edges into ``n_seg`` distinct
    destinations, under ``blocks = (block_e, block_v, block_dst,
    block_d)``."""
    be, bv, bdst, bd = blocks
    return (
        _pow2_tiles(m, be),
        -(-n // bv) * bv,
        -(-d // bd) * bd,
        _pow2_tiles(n_seg, bdst),
    )


class PallasChunkAggregator:
    """Pallas ``edge_block_spmm`` as a chunk_aggregate backend.

    Host side mirrors the jax backend: ``np.unique`` builds the chunk's
    destination dictionary, so the kernel runs over *dense* segment ids
    (``num_dst = n_seg``) instead of global vertex ids — the out tile
    count tracks the chunk's fan-out, not |V|.

    Chunk-to-chunk reuse: operand padding happens in host scratch buffers
    keyed by padded shape (allocated once per bucket, refilled per call;
    pad margins carry the kernel's ``-1`` sentinel / zero weight), and
    padded shapes are pow-2-bucketed so jit traces a handful of shapes
    per layer rather than one per chunk.

    Operands go to ``device``.  The compiled kernel (``interpret=False``)
    needs that device to be a TPU and raises otherwise; interpret mode
    runs the kernel body on any device and is only ever asked for by
    name.  Block sizes default to ``auto_blocks`` from the first non-empty
    chunk's shape and stay frozen for scratch stability; explicit
    ``block_*`` kwargs override.
    """

    backend = "pallas"

    def __init__(
        self,
        device: jax.Device,
        interpret: bool = False,
        block_e: int | None = None,
        block_v: int | None = None,
        block_dst: int | None = None,
        block_d: int | None = None,
    ) -> None:
        if not interpret and device.platform != "tpu":
            raise RuntimeError(
                f"backend='pallas' compiles the edge_block_spmm kernel for a "
                f"TPU, but JAX found no TPU: the device is "
                f"{device.platform} ({device.device_kind}). Use "
                f"backend='pallas-interpret' to run the kernel in interpret "
                f"mode off-TPU."
            )
        self.device = device
        self.interpret = bool(interpret)
        self._blocks = (
            (block_e, block_v, block_dst, block_d)
            if all((block_e, block_v, block_dst, block_d))
            else None
        )
        self.dedup_seconds = 0.0
        self.h2d_seconds = 0.0
        self.kernel_wait_seconds = 0.0
        self.d2h_seconds = 0.0
        self.tracer = NULL_TRACER
        self._feat_scratch: dict[tuple[int, int], np.ndarray] = {}
        self._edge_scratch: dict[int, tuple[np.ndarray, np.ndarray, np.ndarray]] = {}

    def _edges(self, ep: int, m: int, src_local, seg_ids, weights):
        buf = self._edge_scratch.get(ep)
        if buf is None:
            buf = (
                np.full((ep, 1), -1, np.int32),
                np.full((ep, 1), -1, np.int32),
                np.zeros((ep, 1), np.float32),
            )
            self._edge_scratch[ep] = buf
        src_p, dst_p, w_p = buf
        src_p[:m, 0] = src_local
        src_p[m:, 0] = -1
        dst_p[:m, 0] = seg_ids
        dst_p[m:, 0] = -1
        w_p[:m, 0] = weights
        w_p[m:, 0] = 0.0
        return src_p, dst_p, w_p

    def _feats(self, vp: int, dp: int, feats: np.ndarray) -> np.ndarray:
        n, d = feats.shape
        if (vp, dp) == (n, d):
            return np.ascontiguousarray(feats, np.float32)
        buf = self._feat_scratch.get((vp, dp))
        if buf is None:
            buf = np.zeros((vp, dp), np.float32)
            self._feat_scratch[(vp, dp)] = buf
        # stale rows beyond n are never selected (src_local < n, and a
        # one-hot zero times any finite stale value is exactly 0)
        buf[:n, :d] = feats
        return buf

    def __call__(self, feats, src_local, dst, weights):
        if len(dst) == 0:
            return (
                np.empty(0, dtype=np.int64),
                np.empty((0, feats.shape[1]), dtype=np.float32),
                np.empty(0, dtype=np.int64),
            )
        tr = self.tracer
        with tr.span("dedup", "dedup"):
            t0 = time.perf_counter()
            unique_dst, seg_ids, counts = np.unique(
                dst, return_inverse=True, return_counts=True
            )
            n, d = feats.shape
            m = len(dst)
            n_seg = len(unique_dst)
            if self._blocks is None:
                self._blocks = auto_blocks(n, d, m, n_seg, self.interpret)
            be, bv, bdst, bd = self._blocks
            ep, vp, dp, jp = padded_dims(self._blocks, n, d, m, n_seg)

            src_p, dst_p, w_p = self._edges(
                ep, m, src_local, np.asarray(seg_ids, np.int32), weights
            )
            feats_p = self._feats(vp, dp, feats)
            self.dedup_seconds += time.perf_counter() - t0

        with tr.span("h2d", "h2d"):
            t0 = time.perf_counter()
            operands = tuple(
                jax.device_put(x, self.device)
                for x in (src_p, dst_p, w_p, feats_p)
            )
            jax.block_until_ready(operands)
            self.h2d_seconds += time.perf_counter() - t0

        with tr.span("kernel_wait", "kernel"):
            t0 = time.perf_counter()
            out = edge_block_spmm_padded(
                *operands,
                block_e=be, block_v=bv, block_dst=bdst, block_d=bd,
                num_dst_padded=jp, interpret=self.interpret,
            )
            out.block_until_ready()
            self.kernel_wait_seconds += time.perf_counter() - t0

        # the output is ready, so this times the copy alone: the whole
        # padded [jp, dp] block comes back
        with tr.span("d2h", "d2h"):
            t0 = time.perf_counter()
            host = np.asarray(out)
            self.d2h_seconds += time.perf_counter() - t0
        # slice on the host: a device-side slice would compile one program
        # per distinct n_seg, i.e. per chunk
        return (
            unique_dst.astype(np.int64),
            host[:n_seg, :d],
            counts.astype(np.int64),
        )


def chunk_aggregate(backend: str = "numpy", device_index: int = 0):
    """Resolve a backend name to a callable with the shared contract.

    ``numpy`` is a plain function and touches no JAX device.  The device
    backends return a fresh aggregator object (call once per layer — it
    carries scratch buffers) bound to ``jax.devices()[device_index]``,
    wrapping round when there are fewer devices than indices: shard
    ``s`` of a sharded run passes ``s``, so each shard's kernels run on
    its own chip.  ``pallas`` is the compiled kernel and raises off-TPU;
    ``pallas-interpret`` runs it in interpret mode on any host, which is
    what CI and the equivalence tests use.
    """
    if backend == "numpy":
        return chunk_aggregate_numpy
    if backend not in ("jax", "pallas", "pallas-interpret"):
        raise ValueError(f"unknown broadcast backend {backend!r}")
    devices = jax.devices()
    device = devices[device_index % len(devices)]
    if backend == "jax":
        return JaxChunkAggregator(device)
    return PallasChunkAggregator(device, interpret=backend == "pallas-interpret")
