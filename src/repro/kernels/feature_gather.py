"""Feature-gather kernel: rows = table[ids] for the hybrid feature fetch.

The hybrid scheme's per-step payload is a batched row gather from the local
feature shard (serving peers' requests).  On TPU a row gather is MXU-
friendly as a one-hot contraction per (ids-tile x table-tile) pair — the
same blocking idiom as ``sage_aggregate`` minus the mean:

    W[i, j] = 1{ids[i] == table_tile_start + j}
    out[i]  = sum_tiles W @ table_tile

Invalid ids (-1, cache hits or padding) produce zero rows, matching the
pure-jnp reference semantics used by ``dist.fetch_features``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

TILE_I = 128
TILE_T = 128


def _gather_kernel(ids_ref, table_ref, out_ref, *, num_table_tiles):
    t = pl.program_id(1)
    ids = ids_ref[...]                            # (TILE_I,)
    tbl = table_ref[...]                          # (TILE_T, D)

    tile_t = tbl.shape[0]
    base = t * tile_t
    local = ids - base
    in_tile = (ids >= 0) & (local >= 0) & (local < tile_t)

    iota = jax.lax.broadcasted_iota(jnp.int32, (1, tile_t), 1)
    w = ((local[:, None] == iota) & in_tile[:, None]).astype(tbl.dtype)

    part = jax.lax.dot(w, tbl, preferred_element_type=jnp.float32)

    @pl.when(t == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += part.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile_i", "tile_t",
                                             "interpret"))
def feature_gather(ids: jnp.ndarray, table: jnp.ndarray, *,
                   tile_i: int = TILE_I, tile_t: int = TILE_T,
                   interpret: bool | None = None) -> jnp.ndarray:
    """ids (N,) int32 [-1 -> zero row]; table (M, D) -> (N, D)."""
    N = ids.shape[0]
    M, D = table.shape
    tile_i = min(tile_i, N)
    tile_t = min(tile_t, M)
    N_pad = -(-N // tile_i) * tile_i
    M_pad = -(-M // tile_t) * tile_t
    ids_p = jnp.full((N_pad,), -1, jnp.int32).at[:N].set(ids)
    tbl_p = jnp.zeros((M_pad, D), table.dtype).at[:M].set(table)
    num_table_tiles = M_pad // tile_t

    out = pl.pallas_call(
        functools.partial(_gather_kernel, num_table_tiles=num_table_tiles),
        grid=(N_pad // tile_i, num_table_tiles),
        in_specs=[
            pl.BlockSpec((tile_i,), lambda i, t: (i,)),
            pl.BlockSpec((tile_t, D), lambda i, t: (t, 0)),
        ],
        out_specs=pl.BlockSpec((tile_i, D), lambda i, t: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N_pad, D), table.dtype),
        interpret=resolve_interpret(interpret),
    )(ids_p, tbl_p)
    return out[:N]
