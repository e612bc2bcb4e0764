"""Device-mesh building blocks for distributed ATLAS: the broadcast
execution model as a push-style SpMM over a (data, model) /
(pod, data, model) mesh.

(Salvaged from the seed's ``repro.distributed.atlas_dist``; the
out-of-core shard harness in ``repro.dist`` reuses the ``shard_map``
wrapper and the (src_shard, dst_shard) pre-bucketing idea, and
``MeshExchange`` routes its buckets with the same tiled ``all_to_all``.)

The paper's single-machine insight — *stream every source feature exactly
once and push messages along out-edges, instead of destinations pulling
with random repeated reads* — maps exactly onto a distributed push-SpMM
(DESIGN.md §2):

  * vertices are range-partitioned over the DP axes (the multi-device
    analogue of the paper's range-partitioned spill files);
  * the feature dim shards over `model` (TP) — messages stay D-sharded
    end-to-end, so the all_to_all moves 1/|model| of every message;
  * each device reads ITS source shard once (sequential, single-pass),
    builds messages in the bucket order the destination shard expects,
    and one `all_to_all` over the DP axes routes them (the paper's
    "broadcast along out-edges");
  * destinations segment-sum into their local accumulator (the hot store;
    sharding bounds it, so the cold-store tier is not needed on-device),
    then graduate through the dense transform: the agg-GEMM is
    row-parallel over `model` with a reduce-scatter epilogue
    (psum_scatter), leaving the output already sharded for the next layer.

Static shapes: edges are pre-bucketed by (src_shard, dst_shard) and padded
to the max bucket size; padding edges point at a dump row.

An optional inner chunk loop streams the source buckets in pieces —
bounding the message buffer exactly like the paper's 8 MiB chunks bound
the reader queue.
"""

from __future__ import annotations

import dataclasses

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.graphs.csr import CSRGraph, degrees_from_csr

from jax import shard_map as _shard_map


def shard_map(f, mesh, in_specs, out_specs, check_rep=False):
    return _shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                      check_vma=check_rep)


@dataclasses.dataclass
class EdgePlan:
    """Per-device, per-peer edge buckets (host-side prep, one pass)."""

    num_shards: int
    v_local: int  # padded vertices per shard
    bucket: int  # padded edges per (src_shard, dst_shard) bucket
    # on the SOURCE shard: local source row + weight for each outgoing msg
    src_local: np.ndarray  # [S, S, Eb]  (owner shard, dst shard, edge)
    weight: np.ndarray  # [S, S, Eb] float32
    # on the DEST shard: local dst row for each incoming msg, same order
    dst_local: np.ndarray  # [S, S, Eb]  (owner shard, src shard, edge)


def build_edge_plan(csr: CSRGraph, num_shards: int, kind: str = "gcn") -> EdgePlan:
    """Range-partition vertices; bucket edges by (src_shard, dst_shard).

    Message order within a bucket is (src, dst)-sorted — both sides derive
    it independently, so only message *values* ever travel."""
    v = csr.num_vertices
    v_local = -(-v // num_shards)
    in_deg, _ = degrees_from_csr(csr)
    src, dst = csr.edges_for_range(0, v)
    src = src.astype(np.int64)
    dst = dst.astype(np.int64)
    if kind == "gcn":
        d = np.maximum(in_deg, 1).astype(np.float64)
        w = (1.0 / np.sqrt(d[src] * d[dst])).astype(np.float32)
    elif kind == "sage":
        d = np.maximum(in_deg, 1).astype(np.float64)
        w = (1.0 / d[dst]).astype(np.float32)
    else:  # gin
        w = np.ones(len(src), np.float32)

    ssh, dsh = src // v_local, dst // v_local
    order = np.lexsort((dst, src, dsh, ssh))
    src, dst, w, ssh, dsh = src[order], dst[order], w[order], ssh[order], dsh[order]
    pair = ssh * num_shards + dsh
    counts = np.bincount(pair, minlength=num_shards * num_shards)
    bucket = max(1, int(counts.max()))

    s = num_shards
    src_local = np.full((s, s, bucket), v_local, np.int32)  # dump row
    weight = np.zeros((s, s, bucket), np.float32)
    dst_local = np.full((s, s, bucket), v_local, np.int32)
    starts = np.concatenate([[0], np.cumsum(counts)])
    for i in range(s):
        for j in range(s):
            lo, hi = starts[i * s + j], starts[i * s + j + 1]
            n = hi - lo
            src_local[i, j, :n] = src[lo:hi] - i * v_local
            weight[i, j, :n] = w[lo:hi]
            dst_local[j, i, :n] = dst[lo:hi] - j * v_local
    return EdgePlan(num_shards=s, v_local=v_local, bucket=bucket,
                    src_local=src_local, weight=weight, dst_local=dst_local)


def pad_features(feats: np.ndarray, plan: EdgePlan) -> np.ndarray:
    v, d = feats.shape
    vp = plan.num_shards * plan.v_local
    out = np.zeros((vp, d), feats.dtype)
    out[:v] = feats
    return out


@dataclasses.dataclass
class CombinedEdgePlan:
    """Edge plan with source-side combining (§Perf GNN iteration).

    The paper's chunk aggregation pre-sums messages *by destination*
    before they touch the hot store; distributed, the same combine runs
    BEFORE the all_to_all: each (src_shard, dst_shard) bucket ships one
    partial per *distinct* destination instead of one message per edge —
    wire volume drops from E to U = sum of per-bucket distinct
    destinations (the heavy-tailed fan-in is exactly where it wins).
    """

    num_shards: int
    v_local: int
    bucket: int  # padded edges per bucket (compute side)
    slots: int  # padded distinct destinations per bucket (wire side)
    src_local: np.ndarray  # [S, S, Eb] on the source shard
    weight: np.ndarray  # [S, S, Eb]
    edge_slot: np.ndarray  # [S, S, Eb] edge -> combine slot (source shard)
    slot_dst: np.ndarray  # [S, S, U] slot -> dst_local (dest shard)
    reuse: float  # E / U  (combining win on this graph)


def build_combined_plan(
    csr: CSRGraph, num_shards: int, kind: str = "gcn"
) -> CombinedEdgePlan:
    base = build_edge_plan(csr, num_shards, kind)
    s, eb, vl = base.num_shards, base.bucket, base.v_local
    edge_slot = np.zeros((s, s, eb), np.int32)
    slot_lists = []
    u_max = 1
    total_edges = 0
    total_slots = 0
    for i in range(s):
        for j in range(s):
            dst = base.dst_local[j, i]  # receiver order == sender order
            valid = dst < vl
            uniq, inv = np.unique(dst[valid], return_inverse=True)
            sl = np.zeros(eb, np.int32)
            sl[valid] = inv
            sl[~valid] = len(uniq)  # dump slot for padding edges
            edge_slot[i, j] = sl
            slot_lists.append((i, j, uniq))
            u_max = max(u_max, len(uniq) + 1)
            total_edges += int(valid.sum())
            total_slots += len(uniq)
    slot_dst = np.full((s, s, u_max), vl, np.int32)
    for i, j, uniq in slot_lists:
        slot_dst[j, i, : len(uniq)] = uniq  # stored on the DEST shard
    return CombinedEdgePlan(
        num_shards=s, v_local=vl, bucket=eb, slots=u_max,
        src_local=base.src_local, weight=base.weight,
        edge_slot=edge_slot, slot_dst=slot_dst,
        reuse=total_edges / max(total_slots, 1),
    )


def make_combined_layer_step(
    mesh: Mesh,
    *,
    has_self: bool = False,
    activation: bool = True,
):
    """Broadcast layer with source-side combining: segment-sum per
    destination BEFORE the all_to_all (wire volume E -> U)."""
    dp = tuple(a for a in mesh.axis_names if a != "model")
    dp_spec = dp if len(dp) > 1 else dp[0]

    def step(feats, src_local, weight, edge_slot, slot_dst, w_agg, w_self, bias):
        src_local = src_local.reshape(src_local.shape[1:])  # [S, Eb]
        weight = weight.reshape(weight.shape[1:])
        edge_slot = edge_slot.reshape(edge_slot.shape[1:])
        slot_dst = slot_dst.reshape(slot_dst.shape[1:])  # [S, U]
        s_eff, u = slot_dst.shape
        vl = feats.shape[0]
        dump = jnp.zeros((1, feats.shape[1]), feats.dtype)
        feats_pad = jnp.concatenate([feats, dump], axis=0)

        msgs = feats_pad[src_local] * weight[..., None].astype(feats.dtype)
        # source-side combine: one partial per distinct destination
        combined = jax.vmap(
            lambda m, sl: jax.ops.segment_sum(
                m.astype(jnp.float32), sl, num_segments=u
            )
        )(msgs, edge_slot)  # [S, U, Dl]
        combined = combined.astype(feats.dtype)
        recv = jax.lax.all_to_all(
            combined, dp_spec, split_axis=0, concat_axis=0, tiled=True
        )
        flat = recv.reshape(-1, recv.shape[-1])
        agg = jax.ops.segment_sum(
            flat.astype(jnp.float32), slot_dst.reshape(-1), num_segments=vl + 1
        )[:vl]

        out = jnp.dot(agg.astype(w_agg.dtype), w_agg,
                      preferred_element_type=jnp.float32)
        if w_self is not None:
            out = out + jnp.dot(feats, w_self, preferred_element_type=jnp.float32)
        out = jax.lax.psum_scatter(out, "model", scatter_dimension=1, tiled=True)
        out = out + bias.astype(jnp.float32)
        if activation:
            out = jnp.maximum(out, 0.0)
        return out.astype(feats.dtype)

    edge = P(dp_spec, None, None)
    w_spec = P("model", None)
    fn = step if has_self else (
        lambda f, sl, w, es, sd, wa, b: step(f, sl, w, es, sd, wa, None, b)
    )
    in_specs = (P(dp_spec, "model"), edge, edge, edge, edge, w_spec)
    in_specs += (w_spec, P("model")) if has_self else (P("model"),)
    sharded = shard_map(fn, mesh, in_specs, P(dp_spec, "model"))
    return jax.jit(sharded)


def make_layer_step(
    mesh: Mesh,
    *,
    has_self: bool = False,
    activation: bool = True,
    chunks: int = 1,
):
    """One broadcast GNN layer on the mesh, jit'd.

    signature: step(feats, src_local, weight, dst_local, w_agg[, w_self],
                    bias) -> next_feats

      feats      [Vp, D]      P(dp, 'model')
      src_local  [S, S, Eb]   P(dp, None, None)   (int32, padded)
      weight     [S, S, Eb]   P(dp, None, None)
      dst_local  [S, S, Eb]   P(dp, None, None)
      w_agg      [D, F]       P('model', None)    (row-parallel)
      w_self     [D, F]       P('model', None)    (SAGE/GIN self term)
      bias       [F]          P('model')
      returns    [Vp, F]      P(dp, 'model')
    """
    dp = tuple(a for a in mesh.axis_names if a != "model")
    dp_spec = dp if len(dp) > 1 else dp[0]

    def step(feats, src_local, weight, dst_local, w_agg, w_self, bias):
        # shard_map local views; squeeze the owner dim (== my shard)
        src_local = src_local.reshape(src_local.shape[1:])  # [S, Eb]
        weight = weight.reshape(weight.shape[1:])
        dst_local = dst_local.reshape(dst_local.shape[1:])
        s_eff, eb = src_local.shape
        vl = feats.shape[0]
        dump = jnp.zeros((1, feats.shape[1]), feats.dtype)
        feats_pad = jnp.concatenate([feats, dump], axis=0)

        def route_and_aggregate(src_idx, wgt, dst_idx):
            msgs = feats_pad[src_idx] * wgt[..., None].astype(feats.dtype)
            recv = jax.lax.all_to_all(
                msgs, dp_spec, split_axis=0, concat_axis=0, tiled=True
            )  # [S, Eb_c, Dl]; index 0 = sender shard
            flat = recv.reshape(-1, recv.shape[-1])
            seg = dst_idx.reshape(-1)
            agg = jax.ops.segment_sum(
                flat.astype(jnp.float32), seg, num_segments=vl + 1
            )
            return agg[:vl]

        if chunks == 1:
            agg = route_and_aggregate(src_local, weight, dst_local)
        else:
            cb = -(-eb // chunks)
            pad = chunks * cb - eb
            src_c = jnp.pad(src_local, ((0, 0), (0, pad)), constant_values=vl)
            w_c = jnp.pad(weight, ((0, 0), (0, pad)))
            dst_c = jnp.pad(dst_local, ((0, 0), (0, pad)), constant_values=vl)
            src_c = src_c.reshape(s_eff, chunks, cb).transpose(1, 0, 2)
            w_c = w_c.reshape(s_eff, chunks, cb).transpose(1, 0, 2)
            dst_c = dst_c.reshape(s_eff, chunks, cb).transpose(1, 0, 2)

            def body(acc, xs):
                si, wi, di = xs
                return acc + route_and_aggregate(si, wi, di), None

            agg0 = jnp.zeros((vl, feats.shape[1]), jnp.float32)
            agg, _ = jax.lax.scan(body, agg0, (src_c, w_c, dst_c))

        # graduation: row-parallel GEMM, reduce-scatter epilogue
        out = jnp.dot(agg.astype(w_agg.dtype), w_agg,
                      preferred_element_type=jnp.float32)
        if w_self is not None:
            out = out + jnp.dot(feats, w_self, preferred_element_type=jnp.float32)
        out = jax.lax.psum_scatter(out, "model", scatter_dimension=1, tiled=True)
        out = out + bias.astype(jnp.float32)
        if activation:
            out = jnp.maximum(out, 0.0)
        return out.astype(feats.dtype)

    edge = P(dp_spec, None, None)
    w_spec = P("model", None)
    in_specs = (P(dp_spec, "model"), edge, edge, edge, w_spec,
                w_spec if has_self else None, P("model"))
    fn = step if has_self else (
        lambda f, sl, w, dl, wa, b: step(f, sl, w, dl, wa, None, b)
    )
    if not has_self:
        in_specs = in_specs[:5] + (P("model"),)
    sharded = shard_map(fn, mesh, in_specs, P(dp_spec, "model"))
    return jax.jit(sharded)
