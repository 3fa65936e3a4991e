"""Pure-jnp oracles for the Pallas kernels (the allclose ground truth).

Each kernel in this package has its exact reference here; tests sweep shapes
and dtypes and assert the kernel (interpreted on CPU) matches.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.graph import CSCGraph
from repro.core.mfg import MFG, mean_aggregate
from repro.core.sampler import build_indptr, sample_neighbors


def ref_fused_sample(graph: CSCGraph, seeds: jnp.ndarray, fanout: int,
                     salt) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Oracle for kernels.fused_sample: (samples (S,F) int32, R (S+1,) int32).

    Matches Algorithm 1's outputs: per-seed neighbor draws in CSC order plus
    the row-pointer vector R_l.
    """
    samples, valid = sample_neighbors(graph, seeds, fanout, salt)
    return samples, build_indptr(valid)


def window_truncated_graph(graph: CSCGraph, window: int) -> CSCGraph:
    """``graph`` with every in-edge list cut to its first ``window``
    entries — the neighbor set the fused kernel draws a hub from."""
    import numpy as np

    indptr = np.asarray(graph.indptr)
    indices = np.asarray(graph.indices)
    deg = np.diff(indptr)
    wdeg = np.minimum(deg, window)
    windptr = np.zeros_like(indptr)
    np.cumsum(wdeg, out=windptr[1:])
    pos_in_row = np.arange(indices.size) - np.repeat(indptr[:-1], deg)
    windices = indices[pos_in_row < np.repeat(wdeg, deg)]
    return CSCGraph(indptr=jnp.asarray(windptr, jnp.int32),
                    indices=jnp.asarray(windices, jnp.int32))


def ref_windowed_fused_sample(graph: CSCGraph, seeds: jnp.ndarray,
                              fanout: int, salt, window: int):
    """Window-clamped oracle for kernels.fused_sample.

    The kernel draws a hub's neighbors uniformly from the *first*
    ``window`` entries of its in-edge list.  This oracle truncates every
    neighbor list to ``window`` and reruns the exact reference draw — the
    kernel must match it bit-for-bit — and also returns the expected
    ``overflow_count``.
    """
    import numpy as np

    samples, r = ref_fused_sample(window_truncated_graph(graph, window),
                                  seeds, fanout, salt)
    deg = np.diff(np.asarray(graph.indptr))
    s_np = np.asarray(seeds)
    overflow = int((deg[s_np[s_np >= 0]] > window).sum())
    return samples, r, overflow


def ref_feature_gather(ids: jnp.ndarray, table: jnp.ndarray) -> jnp.ndarray:
    """Oracle for kernels.feature_gather: table[ids], zero rows for -1."""
    rows = table[jnp.clip(ids, 0)]
    return rows * (ids >= 0)[:, None].astype(table.dtype)


def ref_mean_aggregate(edges: jnp.ndarray, h_src: jnp.ndarray) -> jnp.ndarray:
    """Oracle for kernels.sage_aggregate.

    edges: (S, F) int32 local src ids, -1 invalid.  h_src: (N, D).
    Returns (S, D) masked mean.
    """
    mask = edges >= 0
    idx = jnp.clip(edges, 0)
    gathered = h_src[idx]                                  # (S, F, D)
    m = mask[..., None].astype(h_src.dtype)
    total = jnp.sum(gathered * m, axis=1)
    count = jnp.maximum(jnp.sum(m, axis=1), jnp.asarray(1, h_src.dtype))
    return total / count
