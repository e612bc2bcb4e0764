"""Per-kernel allclose sweeps: Pallas (interpret=True) vs ref.py oracles.

Sweeps shapes/dtypes per the brief; hypothesis drives the edge-list
generator for the SpMM kernel (arbitrary src/dst index patterns).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.kernels.edge_block_segmax import edge_block_segmax
from repro.kernels.edge_block_spmm import edge_block_spmm
from repro.kernels.flash_attention import flash_attention
from repro.kernels.fused_graduate import fused_graduate
from repro.kernels.ssd_chunk import ssd_scan
from repro.kernels import ref

TOL = {jnp.float32: 1e-5, jnp.bfloat16: 2e-2}


def rtol_for(dt):
    return TOL[dt]


# --------------------------------------------------------------- spmm


@pytest.mark.parametrize(
    "v_src,num_dst,e,d",
    [(64, 64, 200, 16), (300, 150, 1000, 32), (1100, 700, 4000, 130),
     (50, 2000, 512, 64),
     # two hub destinations, each over several 128-edge blocks
     (64, 2, 1000, 16),
     # a single edge
     (40, 1, 1, 8),
     # 2 d tiles, 3 src tiles, dst tile boundaries inside edge blocks
     (600, 260, 3000, 72)],
)
def test_spmm_shapes(v_src, num_dst, e, d):
    rng = np.random.default_rng(e)
    feats = jnp.asarray(rng.normal(size=(v_src, d)), jnp.float32)
    src = jnp.asarray(rng.integers(0, v_src, e), jnp.int32)
    dst = jnp.asarray(rng.integers(0, num_dst, e), jnp.int32)
    w = jnp.asarray(rng.uniform(0.1, 1.0, e), jnp.float32)
    out = edge_block_spmm(
        feats, src, dst, w, num_dst, block_e=128, block_v=256,
        block_dst=128, block_d=64, interpret=True,
    )
    want = ref.edge_block_spmm_ref(feats, src, dst, w, num_dst)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)


@settings(max_examples=20, deadline=None)
@given(
    e=st.integers(1, 300),
    v_src=st.integers(1, 90),
    num_dst=st.integers(1, 90),
    seed=st.integers(0, 2**31 - 1),
)
def test_spmm_property(e, v_src, num_dst, seed):
    """Invariant: kernel == segment_sum oracle for any index pattern,
    including repeated edges, self-edges and unpadded ragged sizes."""
    rng = np.random.default_rng(seed)
    d = 8
    feats = jnp.asarray(rng.normal(size=(v_src, d)), jnp.float32)
    src = jnp.asarray(rng.integers(0, v_src, e), jnp.int32)
    dst = jnp.asarray(rng.integers(0, num_dst, e), jnp.int32)
    w = jnp.asarray(rng.uniform(-1, 1, e), jnp.float32)
    out = edge_block_spmm(
        feats, src, dst, w, num_dst, block_e=64, block_v=64,
        block_dst=64, block_d=8, interpret=True,
    )
    want = ref.edge_block_spmm_ref(feats, src, dst, w, num_dst)
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-4)


def test_spmm_bf16_inputs():
    rng = np.random.default_rng(0)
    feats = jnp.asarray(rng.normal(size=(256, 64)), jnp.bfloat16)
    src = jnp.asarray(rng.integers(0, 256, 800), jnp.int32)
    dst = jnp.asarray(rng.integers(0, 128, 800), jnp.int32)
    w = jnp.asarray(rng.uniform(0.1, 1.0, 800), jnp.float32)
    out = edge_block_spmm(feats, src, dst, w, 128, interpret=True,
                          block_e=128, block_v=128, block_dst=128, block_d=64)
    want = ref.edge_block_spmm_ref(feats.astype(jnp.float32), src, dst, w, 128)
    np.testing.assert_allclose(out, want, rtol=2e-2, atol=2e-2)


# ------------------------------------------------------------- segmax


def _segment_max_numpy(rows, src, dst, num_dst):
    """The elementwise max of each destination's source rows, 0 for a
    destination with no edge."""
    out = np.full((num_dst, rows.shape[1]), -np.inf, np.float32)
    np.maximum.at(out, dst, rows[src])
    out[np.isneginf(out)] = 0.0
    return out


@pytest.mark.parametrize(
    "v_src,num_dst,e,d,blocks",
    [(64, 64, 200, 16, (128, 256, 128, 64)),
     # 2 d tiles, 3 src tiles, dst tile boundaries inside edge blocks
     (600, 260, 3000, 72, (128, 256, 128, 64)),
     # two hub destinations, each over several edge blocks
     (64, 2, 1000, 16, (128, 256, 128, 64)),
     # most destinations empty, whole tiles of them
     (50, 2000, 512, 64, (64, 64, 64, 64)),
     # a single edge, blocks from auto_blocks
     (40, 1, 1, 8, None),
     # sentinel padding: 300 edges in 3 blocks of 128
     (90, 40, 300, 24, (128, 128, 64, 8))],
)
def test_segmax_shapes(v_src, num_dst, e, d, blocks):
    """The kernel (interpret mode) gives the numpy segment max bit for bit:
    negative and positive rows, sentinel-padded edge blocks, destinations
    with no edge (0) and hubs over several blocks."""
    rng = np.random.default_rng(e + d)
    rows = rng.normal(size=(v_src, d)).astype(np.float32)
    src = rng.integers(0, v_src, e).astype(np.int32)
    dst = rng.integers(0, num_dst, e).astype(np.int32)
    be, bv, bdst, bd = blocks or (None,) * 4
    out = edge_block_segmax(
        jnp.asarray(rows), src, dst, num_dst, block_e=be, block_v=bv,
        block_dst=bdst, block_d=bd, interpret=True,
    )
    want = _segment_max_numpy(rows, src, dst, num_dst)
    np.testing.assert_array_equal(np.asarray(out), want)


def test_segmax_empty_and_all_negative():
    """No edge gives zeros without a launch; a destination whose rows are
    all negative keeps its (negative) max, not 0."""
    rows = -np.abs(np.random.default_rng(1).normal(size=(16, 8))).astype(
        np.float32) - 1.0
    empty = edge_block_segmax(jnp.asarray(rows), np.zeros(0, np.int32),
                              np.zeros(0, np.int32), 5, interpret=True)
    assert np.array_equal(np.asarray(empty), np.zeros((5, 8), np.float32))
    src = np.arange(16, dtype=np.int32)
    dst = (np.arange(16) % 3).astype(np.int32)
    out = np.asarray(edge_block_segmax(jnp.asarray(rows), src, dst, 4,
                                       interpret=True))
    np.testing.assert_array_equal(out, _segment_max_numpy(rows, src, dst, 4))
    assert (out[:3] < 0).all() and (out[3] == 0).all()


# ----------------------------------------------------------- graduate


@pytest.mark.parametrize("n,k,m", [(100, 24, 16), (1000, 48, 8), (513, 130, 257)])
@pytest.mark.parametrize("act", ["none", "relu", "gelu"])
@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
def test_graduate(n, k, m, act, dt):
    rng = np.random.default_rng(n + m)
    x = jnp.asarray(rng.normal(size=(n, k)), dt)
    w = jnp.asarray(rng.normal(size=(k, m)) * 0.1, dt)
    b = jnp.asarray(rng.normal(size=(m,)) * 0.1, dt)
    out = fused_graduate(x, w, b, act, block_n=128, block_k=64, block_m=128,
                         interpret=True)
    want = ref.fused_graduate_ref(x, w, b, act)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        rtol=rtol_for(dt), atol=rtol_for(dt),
    )


# ----------------------------------------------------------- attention


@pytest.mark.parametrize("hq,hkv", [(4, 4), (8, 2), (8, 1)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention(hq, hkv, causal):
    rng = np.random.default_rng(hq * 10 + hkv)
    b, s, d = 2, 256, 64
    q = jnp.asarray(rng.normal(size=(b, hq, s, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, hkv, s, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, hkv, s, d)), jnp.float32)
    out = flash_attention(q, k, v, causal, block_q=64, block_kv=64,
                          interpret=True)
    want = ref.gqa_attention_ref(q, k, v, causal)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dt", [jnp.bfloat16])
def test_flash_attention_bf16(dt):
    rng = np.random.default_rng(99)
    b, hq, hkv, s, d = 1, 4, 2, 128, 128
    q = jnp.asarray(rng.normal(size=(b, hq, s, d)), dt)
    k = jnp.asarray(rng.normal(size=(b, hkv, s, d)), dt)
    v = jnp.asarray(rng.normal(size=(b, hkv, s, d)), dt)
    out = flash_attention(q, k, v, True, block_q=64, block_kv=64, interpret=True)
    want = ref.gqa_attention_ref(q, k, v, True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        rtol=5e-2, atol=5e-2,
    )


# ----------------------------------------------------------------- ssd


@pytest.mark.parametrize("s,chunk,p,n", [(128, 32, 16, 32), (256, 64, 64, 128)])
def test_ssd_scan(s, chunk, p, n):
    rng = np.random.default_rng(s)
    bh = 3
    x = jnp.asarray(rng.normal(size=(bh, s, p)), jnp.float32)
    a = jnp.asarray(rng.uniform(0.7, 1.0, size=(bh, s)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(bh, s, n)) * 0.3, jnp.float32)
    c = jnp.asarray(rng.normal(size=(bh, s, n)) * 0.3, jnp.float32)
    out = ssd_scan(x, a, b, c, chunk=chunk, interpret=True)

    def one(xb, ab, bb, cb):
        y, _ = ref.ssd_chunk_ref(xb, ab, bb, cb, jnp.zeros((p, n), jnp.float32))
        return y

    want = jax.vmap(one)(x, a, b, c)
    np.testing.assert_allclose(out, want, rtol=2e-4, atol=2e-4)


def test_ssd_state_carries_across_chunks():
    """A length-2T scan must not equal two independent length-T scans —
    proves the VMEM scratch really carries state across the chunk axis."""
    rng = np.random.default_rng(5)
    bh, s, p, n = 1, 128, 8, 16
    x = jnp.asarray(rng.normal(size=(bh, s, p)), jnp.float32)
    a = jnp.asarray(rng.uniform(0.8, 0.99, size=(bh, s)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(bh, s, n)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(bh, s, n)), jnp.float32)
    full = ssd_scan(x, a, b, c, chunk=64, interpret=True)
    halves = jnp.concatenate(
        [ssd_scan(x[:, :64], a[:, :64], b[:, :64], c[:, :64], chunk=64, interpret=True),
         ssd_scan(x[:, 64:], a[:, 64:], b[:, 64:], c[:, 64:], chunk=64, interpret=True)],
        axis=1,
    )
    assert not np.allclose(full, halves)
    np.testing.assert_allclose(full[:, :64], halves[:, :64], rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------ rms_norm


@pytest.mark.parametrize("n,d", [(64, 128), (100, 256), (257, 512)])
@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
def test_rms_norm_fused(n, d, dt):
    from repro.kernels.rms_norm import rms_norm_fused
    from repro.models.layers import rms_norm

    rng = np.random.default_rng(n)
    x = jnp.asarray(rng.normal(size=(n, d)), dt)
    scale = jnp.asarray(rng.normal(size=(d,)) * 0.1, dt)
    out = rms_norm_fused(x, scale, interpret=True, block_n=64)
    want = rms_norm(x, scale)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        rtol=rtol_for(dt), atol=rtol_for(dt),
    )
