"""Main-path programs compiled for a described TPU v5e (no chip needed).

The TPU compiler is installed with JAX; it compiles for a topology that
is described, not attached.  These tests compile what the chip runs —
the ``edge_block_spmm`` kernel, its item tables by scalar prefetch, at
the padded shapes ``PallasChunkAggregator`` produces with the blocks it
picks on a TPU, the pooling aggregator's projection and
``edge_block_segmax`` kernel at the same shapes,
and the mesh exchange's ``all_to_all`` route over four chips — so a
change the chip's compiler would refuse (unaligned tiles, too much VMEM,
a collective that cannot be partitioned) fails here, not on the chip.

The topology is described inside a fixture, never at import: only one
process may hold the TPU library, and every test worker imports this
file.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core.broadcast import _pool_segmax, padded_dims
from repro.dist.exchange import all_to_all_route
from repro.kernels.edge_block_spmm import (
    _spmm_jit,
    auto_blocks,
    max_work_items,
)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.mark.parametrize(
    "n,d,m,n_seg",
    [
        # 1 MiB chunk at d=128 (ogbn-papers100M width): 2048 source rows,
        # ~15 out-edges each, hub-heavy destinations
        (2048, 128, 30_000, 14_000),
        # 1 MiB chunk at d=1024 (the paper's widest features)
        (256, 1024, 3_840, 3_500),
    ],
)
def test_spmm_kernel_compiles_for_v5e(topo, n, d, m, n_seg):
    blocks = auto_blocks(n, d, m, n_seg, interpret=False)
    ep, vp, dp, jp = padded_dims(blocks, n, d, m, n_seg)
    be, bv, bdst, bd = blocks
    one_chip = SingleDeviceSharding(topo.devices[0])

    def shape(s, dtype):
        return jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)

    # the item tables arrive by scalar prefetch, padded to their bound
    items = 1 + 3 * max_work_items(ep, jp, be, bdst)
    compiled = _spmm_jit.lower(
        shape((items,), jnp.int32),
        shape((ep, 1), jnp.int32),
        shape((ep, 1), jnp.int32),
        shape((ep, 1), jnp.float32),
        shape((vp, dp), jnp.float32),
        block_e=be, block_v=bv, block_dst=bdst, block_d=bd,
        num_dst_padded=jp, interpret=False,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().output_size_in_bytes == jp * dp * 4


@pytest.mark.parametrize(
    "n,d,m,n_seg",
    [
        # 1 MiB chunks of the SAGE-pool cell's two layers (d = 128, 256)
        (2048, 128, 30_000, 14_000),
        (1024, 256, 15_000, 9_000),
    ],
)
def test_segmax_kernel_compiles_for_v5e(topo, n, d, m, n_seg):
    blocks = auto_blocks(n, d, m, n_seg, interpret=False)
    ep, vp, dp, jp = padded_dims(blocks, n, d, m, n_seg)
    be, bv, bdst, bd = blocks
    one_chip = SingleDeviceSharding(topo.devices[0])

    def shape(s, dtype):
        return jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)

    items = 1 + 3 * max_work_items(ep, jp, be, bdst)
    compiled = _pool_segmax.lower(
        shape((items,), jnp.int32),
        shape((ep, 1), jnp.int32),
        shape((ep, 1), jnp.int32),
        shape((vp, dp), jnp.float32),
        shape((dp, dp), jnp.float32),
        shape((dp,), jnp.float32),
        block_e=be, block_v=bv, block_dst=bdst, block_d=bd,
        num_dst_padded=jp, interpret=False,
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "edge_block_segmax" in text
    assert "spmm" not in text
    assert compiled.memory_analysis().output_size_in_bytes == jp * dp * 4


def test_mesh_exchange_route_compiles_for_four_chips(topo):
    shards, k, w = 4, 1024, 128
    mesh = Mesh(np.array(topo.devices[:shards]), ("shards",))
    sharding = NamedSharding(mesh, P("shards"))

    def shape(s, dtype):
        return jax.ShapeDtypeStruct(s, dtype, sharding=sharding)

    compiled = all_to_all_route(mesh).lower(
        shape((shards, shards, k), jnp.int32),
        shape((shards, shards, k), jnp.int32),
        shape((shards, shards, k, w), jnp.float32),
    ).compile()
    assert "all-to-all" in compiled.as_text()
