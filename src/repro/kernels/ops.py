"""Jit'd public wrappers for the Pallas kernels.

On non-TPU backends (this CPU container) every kernel runs in
``interpret=True`` mode — the kernel body executes in Python/XLA-CPU for
correctness validation, while the BlockSpec/VMEM structure is the TPU
deployment artifact.  On TPU the same code compiles to Mosaic.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.fused_graduate import fused_graduate
from repro.kernels.ssd_chunk import ssd_scan


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("activation",))
def graduate(x, w, b, activation: str = "relu"):
    """Fused graduation transform act(x @ w + b)."""
    return fused_graduate(x, w, b, activation, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("causal",))
def attention(q, k, v, causal: bool = True):
    """Causal GQA flash attention, [B,Hq,S,D] x [B,Hkv,S,D] -> [B,Hq,S,D]."""
    return flash_attention(q, k, v, causal, interpret=_interpret())


@jax.jit
def ssd(x, a, b, c):
    """Mamba-2 SSD chunked scan, [BH,S,P] -> [BH,S,P]."""
    return ssd_scan(x, a, b, c, interpret=_interpret())


# re-exported oracles so tests import one module
fused_graduate_ref = ref.fused_graduate_ref
gqa_attention_ref = ref.gqa_attention_ref
mha_attention_ref = ref.mha_attention_ref
ssd_chunk_ref = ref.ssd_chunk_ref


def ssd_ref(x, a, b, c):
    """Batched oracle for ssd_scan via the naive recurrence."""
    def one(xb, ab, bb, cb):
        y, _ = ref.ssd_chunk_ref(
            xb, ab, bb, cb, jnp.zeros((xb.shape[-1], bb.shape[-1]), jnp.float32)
        )
        return y

    return jax.vmap(one)(x, a, b, c)
