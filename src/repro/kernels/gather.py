"""Pallas row gather — the feature-store device hot path.

``fetch_features`` moves (N, D) feature rows through two ``all_to_all``
rounds; the pinned/staged feature stores (``repro.core.feature_store``)
replace the hot part of that stream with a plain device-memory gather
from a pinned table.  This kernel is that gather, written the way the
``fused_sample`` kernel streams neighbor rows: the table stays in HBM
(``pl.ANY``), the block's ids arrive in SMEM, every requested row of the
block rides its own async DMA into a VMEM scratch, and all of them are
in flight before the first is waited on.  The Pallas pipeline
double-buffers the output blocks, so block *i*'s write-back overlaps
block *i+1*'s row DMAs.

TPU DMAs slice HBM in whole 128-lane tiles, so a table whose width is
not a multiple of 128 is padded to one in the wrapper; the kernel copies
the first ``D`` lanes of each row out.

Semantics match the ``jnp.take`` oracle bit for bit, including the
feature path's padding convention: ids that are ``-1`` (padded frontier
slots) or otherwise out of ``[0, K)`` produce exact ``+0.0`` rows.

Tests run the kernel in interpret mode on CPU (``tests/test_kernels.py``);
``tests/test_tpu_compile.py`` compiles it for a v5e chip at real shapes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

# rows gathered per grid step: the ids arrive as a 1-D int32 SMEM block,
# which must match the 1024-element tile XLA gives such arrays on TPU
BLOCK_ROWS = 1024
LANES = 128


def gather_rows_reference(table: jnp.ndarray,
                          ids: jnp.ndarray) -> jnp.ndarray:
    """The ``jnp.take`` oracle the kernel is bit-identical to.

    ``table`` is (K, D); ``ids`` (N,) int32 with -1 (or any id outside
    ``[0, K)``) meaning "no row" -> an exact zero row.
    """
    K = table.shape[0]
    ok = (ids >= 0) & (ids < K)
    rows = jnp.take(table, jnp.clip(ids, 0, K - 1), axis=0)
    return jnp.where(ok[:, None], rows, jnp.zeros_like(rows))


def _gather_kernel(ids_ref, table_hbm, out_ref, rows, sem, *, block: int,
                   table_rows: int, width: int):
    def row_dma(j, idx):
        return pltpu.make_async_copy(table_hbm.at[pl.ds(idx, 1)],
                                     rows.at[pl.ds(j, 1)], sem.at[0])

    def row_ok(j):
        raw = ids_ref[j]
        return (raw >= 0) & (raw < table_rows), raw

    def start(j, carry):
        ok, raw = row_ok(j)

        @pl.when(ok)
        def _fetch():
            row_dma(j, raw).start()

        @pl.when(jnp.logical_not(ok))
        def _zero():
            rows[pl.ds(j, 1), :] = jnp.zeros((1, rows.shape[1]), rows.dtype)
        return carry

    def wait(j, carry):
        ok, raw = row_ok(j)

        @pl.when(ok)
        def _wait():
            row_dma(j, raw).wait()
        return carry

    # every row DMA of the block is in flight before the first wait
    jax.lax.fori_loop(0, block, start, 0)
    jax.lax.fori_loop(0, block, wait, 0)
    out_ref[...] = rows[:, :width]


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def gather_rows(table: jnp.ndarray, ids: jnp.ndarray, *,
                block: int = BLOCK_ROWS,
                interpret: bool | None = None) -> jnp.ndarray:
    """Gather ``table[ids]`` with one async row DMA per id.

    Parameters
    ----------
    table : jnp.ndarray
        (K, D) pinned row table (HBM-resident on TPU).
    ids : jnp.ndarray
        (N,) int32 row indices; -1 / out-of-range ids yield zero rows.
    block : int, default BLOCK_ROWS
        Rows per grid step (the wrapper pads N up to a multiple).  The
        TPU compiler needs a multiple of 1024; interpret mode takes any.
    interpret : bool, optional
        Run the kernel body in interpret mode; ``None`` interprets only
        on the CPU backend (``repro.kernels.resolve_interpret``).

    Returns
    -------
    jnp.ndarray
        (N, D) rows, bit-identical to ``gather_rows_reference``.
    """
    if table.ndim != 2:
        raise ValueError(f"table must be (K, D), got {table.shape}")
    if ids.ndim != 1:
        raise ValueError(f"ids must be (N,), got {ids.shape}")
    N = ids.shape[0]
    K, D = table.shape
    if N == 0 or K == 0:
        return jnp.zeros((N, D), table.dtype)
    padded = -(-N // block) * block
    ids_p = jnp.concatenate(
        [ids.astype(jnp.int32),
         jnp.full((padded - N,), -1, jnp.int32)])
    d_pad = -(-D // LANES) * LANES
    table_p = jnp.pad(table, ((0, 0), (0, d_pad - D)))

    kernel = functools.partial(_gather_kernel, block=block, table_rows=K,
                               width=D)
    return pl.pallas_call(
        kernel,
        grid=(padded // block,),
        in_specs=[
            pl.BlockSpec((block,), lambda i: (i,),
                         memory_space=pltpu.SMEM),    # ids
            pl.BlockSpec(memory_space=pl.ANY),        # table (HBM)
        ],
        out_specs=pl.BlockSpec((block, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((padded, D), table.dtype),
        scratch_shapes=[pltpu.VMEM((block, d_pad), table.dtype),
                        pltpu.SemaphoreType.DMA((1,))],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=resolve_interpret(interpret),
        name="gather_rows",
    )(ids_p, table_p)[:N]
