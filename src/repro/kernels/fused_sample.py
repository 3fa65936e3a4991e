"""Fused sampling kernel — Algorithm 1, adapted to TPU (DESIGN.md §2).

The paper's CPU kernel fuses three passes (sample -> COO, compact, COO->CSC)
into one: the row-pointer vector ``R`` falls out of the sampling loop for
free and samples are written straight into CSC layout.

TPU adaptation:
  * each grid step handles a block of ``SEED_BLOCK`` seeds; the grid runs
    sequentially, so the running ``R`` accumulation lives in SMEM scratch
    exactly like the scalar accumulator in the paper's loop;
  * the wrapper reads each seed's ``(start, deg)`` from ``indptr`` with one
    vectorized gather, and the kernel receives them per block in SMEM —
    no graph-sized array ever lives in SMEM;
  * ``indices`` stays in HBM, viewed as ``(rows, 128)``; each draw DMAs
    the 128-lane row holding its neighbor into SMEM and reads the id out
    with a scalar load.  Seed *r+1*'s DMAs are started before seed *r*'s
    are waited on (a 2-slot ring), so the fetch latency of one seed hides
    behind the next;
  * randomness is the same stateless SplitMix32 hash of (node id, slot)
    used by the pure-JAX sampler, computed on the scalar unit, so kernel
    output is *bit-identical* to the oracle (for degrees within the
    window).

Tests run the same ``pallas_call`` in interpret mode on CPU;
``tests/test_tpu_compile.py`` compiles it for a v5e chip at real shapes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

MAX_DEG_WINDOW = 2048
# seeds per grid step: a 1-D int32 block must match the 1024-element tile
# XLA gives such arrays in HBM on TPU
SEED_BLOCK = 1024
LANES = 128


def _hash_u32(x, salt):
    x = x + salt * jnp.uint32(0x9E3779B9)
    x = (x ^ (x >> 16)) * jnp.uint32(0x85EBCA6B)
    x = (x ^ (x >> 13)) * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def _fused_sample_kernel(salt_ref, seeds_ref, start_ref, deg_ref,
                         indices_hbm, samples_ref, r_ref, overflow_ref,
                         rows, pos, acc, sems, *, fanout: int, window: int,
                         block: int):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        acc[0] = 0
        acc[1] = 0

    salt = salt_ref[0]

    def row_dma(slot, j, p):
        return pltpu.make_async_copy(
            indices_hbm.at[pl.ds(p // LANES, 1)],
            rows.at[slot, pl.ds(j, 1)], sems.at[slot])

    def start_seed(r, slot):
        """Draw seed ``r``'s columns, start their row DMAs into ``slot``
        and account R / overflow (in seed order, as Algorithm 1 does)."""
        s = seeds_ref[r]
        ok = s >= 0
        v = jnp.maximum(s, 0)
        start = start_ref[r]
        deg = jnp.where(ok, deg_ref[r], 0)
        # hubs wider than the window draw from the first `window`
        # neighbors: clamping the *degree used in the modulo* keeps the
        # draw uniform over that visible set — bit-identical to a
        # window-truncated reference
        eff_deg = jnp.minimum(deg, window)
        n_take = jnp.minimum(eff_deg, fanout)
        take_all = eff_deg <= fanout
        base = v.astype(jnp.uint32) * jnp.uint32(2654435761)
        modulus = jnp.maximum(eff_deg, 1).astype(jnp.uint32)
        for j in range(fanout):
            bits = _hash_u32(base + jnp.uint32(j), salt)
            col = jnp.where(take_all, j, (bits % modulus).astype(jnp.int32))
            p = jnp.where(j < n_take, start + col, -1)
            pos[slot, j] = p

            @pl.when(p >= 0)
            def _start():
                row_dma(slot, j, p).start()

        # Algorithm 1 line "R_l[i+1] <- R_l[i] + |sampled|"
        acc[0] = acc[0] + n_take
        r_ref[r] = acc[0]
        # surface window truncation instead of failing silently
        acc[1] = acc[1] + jnp.where(ok & (deg > window), 1, 0)

    start_seed(0, 0)

    def body(r, carry):
        slot = r % 2

        @pl.when(r + 1 < block)
        def _prefetch():
            start_seed(r + 1, 1 - slot)

        # the slot's DMAs share one semaphore and may land in any order:
        # a wait proves only that *some* row arrived, so every row of the
        # seed is waited for before any is read
        for j in range(fanout):
            p = pos[slot, j]

            @pl.when(p >= 0)
            def _wait():
                row_dma(slot, j, p).wait()

        for j in range(fanout):
            p = pos[slot, j]
            samples_ref[r * fanout + j] = jnp.where(
                p >= 0, rows[slot, j, jnp.maximum(p, 0) % LANES], -1)
        return carry

    jax.lax.fori_loop(0, block, body, 0)
    overflow_ref[0] = acc[1]


@functools.partial(jax.jit, static_argnames=("fanout", "window",
                                             "interpret"))
def fused_sample(indptr: jnp.ndarray, indices: jnp.ndarray,
                 seeds: jnp.ndarray, salt: jnp.ndarray, *, fanout: int,
                 window: int = MAX_DEG_WINDOW,
                 interpret: bool | None = None):
    """Sample ``fanout`` in-neighbors per seed, emitting CSC directly.

    Degrees above ``window`` draw uniformly from the first ``window``
    neighbors and are counted in ``overflow_count`` so truncation is
    observable rather than a silent bias.  ``interpret=None`` interprets
    only on the CPU backend (``repro.kernels.resolve_interpret``).

    Returns (samples (S, fanout) int32 global ids [-1 invalid],
             R (S+1,) int32 row pointers,
             overflow_count () int32 — seeds with degree > window).
    """
    S, block = seeds.shape[0], SEED_BLOCK
    s_pad = -(-S // block) * block
    seeds_p = jnp.concatenate([seeds.astype(jnp.int32),
                               jnp.full((s_pad - S,), -1, jnp.int32)])
    v = jnp.maximum(seeds_p, 0)
    start = indptr[v]
    deg = indptr[v + 1] - start
    # (rows, 128) view of the neighbor list: TPU DMAs slice HBM at row
    # granularity, never inside a 1-D array's 1024-element tiles
    e_pad = -(-max(indices.shape[0], 1) // (8 * LANES)) * (8 * LANES)
    indices_2d = jnp.pad(indices, (0, e_pad - indices.shape[0]),
                         constant_values=-1).reshape(-1, LANES)
    salt_arr = jnp.asarray(salt, jnp.uint32).reshape(1)

    smem = pltpu.SMEM
    per_block = pl.BlockSpec((block,), lambda i: (i,), memory_space=smem)
    kernel = functools.partial(_fused_sample_kernel, fanout=fanout,
                               window=window, block=block)
    samples, r_incl, overflow = pl.pallas_call(
        kernel,
        grid=(s_pad // block,),
        in_specs=[
            pl.BlockSpec((1,), lambda i: (0,), memory_space=smem),  # salt
            per_block,                                   # seeds
            per_block,                                   # start
            per_block,                                   # deg
            pl.BlockSpec(memory_space=pl.ANY),           # indices (HBM)
        ],
        out_specs=[
            pl.BlockSpec((block * fanout,), lambda i: (i,),
                         memory_space=smem),             # samples
            per_block,                                   # R[1:]
            pl.BlockSpec((1,), lambda i: (0,), memory_space=smem),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((s_pad * fanout,), jnp.int32),
            jax.ShapeDtypeStruct((s_pad,), jnp.int32),
            jax.ShapeDtypeStruct((1,), jnp.int32),
        ],
        scratch_shapes=[pltpu.SMEM((2, fanout, LANES), jnp.int32),  # rows
                        pltpu.SMEM((2, fanout), jnp.int32),  # draw positions
                        pltpu.SMEM((2,), jnp.int32),         # R, overflow
                        pltpu.SemaphoreType.DMA((2,))],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=resolve_interpret(interpret),
        name="fused_sample",
    )(salt_arr, seeds_p, start, deg, indices_2d)
    samples = samples.reshape(s_pad, fanout)[:S]
    r = jnp.concatenate([jnp.zeros((1,), jnp.int32), r_incl[:S]])
    return samples, r, overflow[0]
