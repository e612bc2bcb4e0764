"""The benchmark's own inputs, made from ``--seed``: graph, features, weights.

A copy of the heavy-tailed in-degree generator, the feature generator and
the Glorot initialisation that the program ships (``repro.graphs.synth``,
``repro.models.gnn``), kept here so that no later change to the program
can change the benchmark's graphs or its reference.  Every seed gives the
same vertex count, degree law and widths; only which vertices are hubs,
which edges exist and the values differ.

Each of the three parts draws from its own stream of
``numpy.random.SeedSequence([seed, stream])``, so any whole seed works,
however large.
"""

from __future__ import annotations

import numpy as np

from bench import cells

GRAPH_STREAM, FEATURE_STREAM, WEIGHT_STREAM = 1, 2, 3


def rng_for(seed: int, stream: int) -> np.random.Generator:
    if seed < 0:
        raise ValueError(f"seed must be a whole number >= 0, got {seed}")
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def build_csr(src: np.ndarray, dst: np.ndarray, num_vertices: int):
    """CSR grouped by source: ``(indptr int64 [V+1], indices int32 [E])``."""
    counts = np.bincount(src, minlength=num_vertices).astype(np.int64)
    indptr = np.zeros(num_vertices + 1, dtype=np.int64)
    np.cumsum(counts, out=indptr[1:])
    order = np.argsort(src, kind="stable")
    return indptr, dst[order].astype(np.int32)


def powerlaw_edges(num_vertices: int, avg_degree: float, seed: int,
                   exponent: float, self_loops: bool):
    """Edge list ``(src, dst)`` with Zipf-like in-degree and uniform
    sources: destinations are drawn with weight ``rank**-exponent`` over a
    seeded permutation of the ids, so hubs are spread over the id range,
    as in relabelled citation graphs.  Edges with ``src == dst`` are
    dropped; ``self_loops`` then adds one loop per vertex (GCN)."""
    rng = rng_for(seed, GRAPH_STREAM)
    num_edges = int(num_vertices * avg_degree)
    weights = np.arange(1, num_vertices + 1, dtype=np.float64) ** (-exponent)
    weights /= weights.sum()
    perm = rng.permutation(num_vertices)
    dst = perm[rng.choice(num_vertices, size=num_edges, p=weights)]
    src = rng.integers(0, num_vertices, size=num_edges)
    keep = src != dst
    src, dst = src[keep], dst[keep]
    if self_loops:
        loop = np.arange(num_vertices, dtype=src.dtype)
        src = np.concatenate([src, loop])
        dst = np.concatenate([dst, loop])
    return src, dst


def make_graph(cfg: dict, seed: int):
    """``(indptr, indices)`` of the configuration's graph for ``seed``."""
    gen = dict(cfg["generator"])
    if gen.pop("name") != "powerlaw":
        raise ValueError(f"unknown graph generator {cfg['generator']}")
    src, dst = powerlaw_edges(cfg["num_vertices"], cfg["avg_degree"], seed,
                              **gen)
    return build_csr(src, dst, cfg["num_vertices"])


def make_features(num_vertices: int, dim: int, seed: int) -> np.ndarray:
    """Standard normal float32 features scaled by ``1/sqrt(dim)``."""
    rng = rng_for(seed, FEATURE_STREAM)
    feats = rng.standard_normal((num_vertices, dim), dtype=np.float32)
    feats *= np.float32(1.0 / np.sqrt(dim))
    return feats


def glorot(rng: np.random.Generator, shape) -> np.ndarray:
    limit = np.sqrt(6.0 / (shape[0] + shape[1]))
    return rng.uniform(-limit, limit, size=shape).astype(np.float32)


def make_weights(kind: str, dims, seed: int) -> list[dict]:
    """Per layer the weights of ``kinds/<kind>.py``, drawn layer by layer
    from one stream of the seed."""
    rng = rng_for(seed, WEIGHT_STREAM)
    layer_weights = cells.layer_kind(kind).make_weights
    return [layer_weights(rng, d_in, d_out)
            for d_in, d_out in zip(dims[:-1], dims[1:])]
