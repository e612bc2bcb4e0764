"""Pallas TPU kernels for the ATLAS reproduction.

Kernels (each: <name>.py kernel + ops.py jit wrapper + ref.py oracle):
  * edge_block_spmm — ATLAS broadcast aggregation as one-hot MXU GEMMs
                      (driven by core.broadcast.PallasChunkAggregator,
                      not ops.py)
  * edge_block_segmax — the elementwise max per destination of GraphSAGE's
                      pooling aggregator, on the same operands (driven
                      the same way; the numpy aggregator is its oracle)
  * fused_graduate  — graduation transform act(x @ W + b), fused epilogue
  * flash_attention — causal GQA flash attention (LM prefill hot-spot)
  * ssd_chunk       — Mamba-2 state-space-duality chunked scan
  * rms_norm        — fused RMSNorm (one HBM round trip per row tile)
"""

from repro.kernels.ops import (  # noqa: F401
    attention,
    graduate,
    ssd,
)
