"""GraphSAGE neighbor-mean aggregation kernel (the GNN compute hot-spot).

A sampled MFG level is a gather + segment-mean: out[i] = mean over valid f of
h_src[edges[i, f]].  On GPU this is an irregular gather; the TPU-native
formulation (DESIGN.md §2) turns each (dst-tile, src-tile) pair into a small
*one-hot count matrix* W (TILE_S x TILE_N) contracted with the source-feature
tile on the MXU:

    W[s, j]   = #{f : edges[s, f] == src_tile_start + j}
    acc[s, :] += W @ h_src_tile

Duplicate sampled edges (with-replacement draws) are naturally weighted by
their multiplicity, matching the oracle.  The grid is
(dst_tiles, src_tiles); the accumulator initializes at src_tile 0 and the
mean division happens on the last src tile, so each output block is written
hot in VMEM exactly once.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

TILE_S = 128      # dst rows per block
TILE_N = 128      # src rows per block


def _sage_aggregate_kernel(edges_ref, hsrc_ref, out_ref, *, num_src_tiles):
    t = pl.program_id(1)
    edges = edges_ref[...]                       # (TILE_S, F) int32
    h = hsrc_ref[...]                            # (TILE_N, D)

    tile_n = h.shape[0]
    base = t * tile_n
    local = edges - base                         # position within this tile
    in_tile = (edges >= 0) & (local >= 0) & (local < tile_n)

    # one-hot count matrix on the fly: W (TILE_S, TILE_N)
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, 1, tile_n), 2)
    oh = (local[:, :, None] == iota) & in_tile[:, :, None]
    w = jnp.sum(oh.astype(h.dtype), axis=1)      # fold fanout into counts

    part = jax.lax.dot(w, h, preferred_element_type=jnp.float32)

    @pl.when(t == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += part.astype(out_ref.dtype)

    @pl.when(t == num_src_tiles - 1)
    def _finish():
        count = jnp.sum((edges >= 0).astype(jnp.float32), axis=1,
                        keepdims=True)
        out_ref[...] = (out_ref[...]
                        / jnp.maximum(count, 1.0).astype(out_ref.dtype))


@functools.partial(jax.jit,
                   static_argnames=("tile_s", "tile_n", "interpret"))
def sage_aggregate(edges: jnp.ndarray, h_src: jnp.ndarray, *,
                   tile_s: int = TILE_S, tile_n: int = TILE_N,
                   interpret: bool | None = None) -> jnp.ndarray:
    """Masked mean of h_src rows per dst: edges (S, F) int32 [-1 invalid],
    h_src (N, D) -> (S, D)."""
    S, F = edges.shape
    N, D = h_src.shape
    tile_s = min(tile_s, S)
    tile_n = min(tile_n, N)
    S_pad = -(-S // tile_s) * tile_s
    N_pad = -(-N // tile_n) * tile_n
    edges_p = jnp.full((S_pad, F), -1, jnp.int32).at[:S].set(edges)
    h_p = jnp.zeros((N_pad, D), h_src.dtype).at[:N].set(h_src)
    num_src_tiles = N_pad // tile_n

    out = pl.pallas_call(
        functools.partial(_sage_aggregate_kernel,
                          num_src_tiles=num_src_tiles),
        grid=(S_pad // tile_s, num_src_tiles),
        in_specs=[
            pl.BlockSpec((tile_s, F), lambda i, t: (i, 0)),
            pl.BlockSpec((tile_n, D), lambda i, t: (t, 0)),
        ],
        out_specs=pl.BlockSpec((tile_s, D), lambda i, t: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((S_pad, D), h_src.dtype),
        interpret=resolve_interpret(interpret),
    )(edges_p, h_p)
    return out[:S]
