"""Public wrappers dispatching to the Pallas kernels.

Kernels run compiled on an accelerator and in interpret mode on the CPU
backend (``repro.kernels.resolve_interpret``, decided at trace time).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.graph import CSCGraph
from repro.core.mfg import MFG
from repro.core.sampler import build_indptr, register_backend, relabel
from repro.kernels import fused_sample as _fs
from repro.kernels import sage_aggregate as _agg


def fused_sample(graph: CSCGraph, seeds: jnp.ndarray, fanout: int, salt,
                 window: int = _fs.MAX_DEG_WINDOW):
    """Kernel-backed neighbor sampling emitting CSC directly (Algorithm 1).

    Returns (samples, R, overflow_count); ``overflow_count`` is the number
    of seeds whose degree exceeded the VMEM window (their draws cover the
    first ``window`` neighbors only).
    """
    return _fs.fused_sample(graph.indptr, graph.indices, seeds,
                            jnp.asarray(salt, jnp.uint32), fanout=fanout,
                            window=window)


def fused_sample_level(graph: CSCGraph, seeds: jnp.ndarray, fanout: int,
                       salt, *, overflow_sink: list | None = None,
                       window: int = _fs.MAX_DEG_WINDOW) -> MFG:
    """Drop-in ``level_fn`` for ``sample_mfgs`` backed by the fused kernel.

    The kernel emits (samples, R); the sort-based relabel (Algorithm 1's
    second loop, DESIGN.md §2) finishes the MFG.

    The kernel also counts frontier nodes whose degree exceeded its VMEM
    neighbor ``window`` (their draws cover the first ``window`` neighbors
    only).  Callers that want that truncation observable pass
    ``overflow_sink`` — a Python list the traced () int32 count is
    appended to per level — and the step surfaces the total as the
    ``sampler_window_overflow`` metric (``repro.pipeline.prefetch``)
    instead of discarding it.
    """
    samples, indptr, overflow = fused_sample(graph, seeds, fanout, salt,
                                             window=window)
    if overflow_sink is not None:
        overflow_sink.append(overflow)
    valid = samples >= 0
    edges, src_nodes, num_src = relabel(seeds, samples, valid)
    return MFG(dst_nodes=seeds, src_nodes=src_nodes, num_src=num_src,
               edges=edges, edge_mask=valid, indptr=indptr)


# advertises the overflow_sink kwarg to the step builder (a function
# attribute, since functools.partial would not carry one)
fused_sample_level.supports_overflow_sink = True

# resolvable by name through the level-backend registry (repro.core.sampler)
register_backend("fused_pallas", fused_sample_level)


def sage_aggregate(mfg: MFG, h_src: jnp.ndarray, *, tile_s: int = 128,
                   tile_n: int = 128) -> jnp.ndarray:
    """Kernel-backed masked neighbor-mean (same contract as
    ``repro.core.mfg.mean_aggregate``)."""
    return _agg.sage_aggregate(mfg.edges, h_src, tile_s=tile_s,
                               tile_n=tile_n)


def feature_gather(ids: jnp.ndarray, table: jnp.ndarray, *,
                   tile_i: int = 128, tile_t: int = 128) -> jnp.ndarray:
    """Kernel-backed row gather (hybrid feature-fetch payload hot-spot)."""
    from repro.kernels import feature_gather as _fg
    return _fg.feature_gather(ids, table, tile_i=tile_i, tile_t=tile_t)
