"""Fixed-shape layered neighbor sampling (§3.1 eqs. 4–5, Algorithm 1).

TPU adaptation (see DESIGN.md §2): variable-length neighbor lists become
fixed-fanout padded tensors with validity masks; the hash-map relabel of
Algorithm 1 becomes a sort-based unique with static capacity.  Semantics match
DGL's random neighborhood sampling: a node with deg <= fanout contributes all
of its neighbors exactly once; a node with deg > fanout contributes ``fanout``
uniform draws.

Randomness is a *stateless per-node hash* of (node id, level salt, slot).
This makes a node's sampled neighborhood independent of which worker samples
it — the property that makes hybrid and vanilla distributed sampling
bit-identical (paper §4.2 "mathematically equivalent"), which
``tests/test_dist.py`` asserts.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, Sequence

import jax
import jax.numpy as jnp

from repro.core.graph import CSCGraph
from repro.core.mfg import MFG

_SENTINEL = jnp.iinfo(jnp.int32).max


def hash_u32(x: jnp.ndarray, salt: jnp.ndarray | int) -> jnp.ndarray:
    """SplitMix32-style integer hash, vectorized, uint32 in/out."""
    x = x.astype(jnp.uint32) + jnp.uint32(salt) * jnp.uint32(0x9E3779B9)
    x = (x ^ (x >> 16)) * jnp.uint32(0x85EBCA6B)
    x = (x ^ (x >> 13)) * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def level_salt(salt, depth: int) -> jnp.ndarray:
    """Per-level sampling salt: the ONE derivation every scheme uses.

    Cross-scheme bit-identity of minibatches (paper §4.2) requires that a
    node at level ``depth`` hashes the same stream no matter which worker
    or placement scheme draws it — so hybrid (``sample_mfgs``), vanilla
    (``dist.vanilla_sample``), and partial-replication sampling all derive
    their level salt here.
    """
    return jnp.uint32(salt) * jnp.uint32(1000003) + depth


def sample_neighbors(graph: CSCGraph, seeds: jnp.ndarray, fanout: int,
                     salt: jnp.ndarray | int):
    """Per-seed neighbor draws: ``Choose(C_G[R_G[v]:R_G[v+1]]; N_l)``.

    seeds: (S,) int32 global node ids, -1 = padding.
    Returns (samples (S, F) int32 global ids [-1 invalid], valid (S, F) bool).
    """
    S = seeds.shape[0]
    seed_ok = seeds >= 0
    v = jnp.clip(seeds, 0)
    start = graph.indptr[v]
    deg = graph.indptr[v + 1] - start

    slots = jnp.arange(fanout, dtype=jnp.uint32)[None, :]
    # independent draw per (seed, slot): hash(node, salt*K + slot)
    bits = hash_u32(v[:, None].astype(jnp.uint32) * jnp.uint32(2654435761)
                    + slots, salt)
    rand_idx = (bits % jnp.maximum(deg, 1)[:, None].astype(jnp.uint32)
                ).astype(jnp.int32)

    take_all = (deg <= fanout)[:, None]
    col = jnp.where(take_all, jnp.arange(fanout, dtype=jnp.int32)[None, :],
                    rand_idx)
    valid = (jnp.arange(fanout)[None, :] < jnp.minimum(deg, fanout)[:, None])
    valid = valid & seed_ok[:, None]
    samples = graph.indices[start[:, None] + col]
    samples = jnp.where(valid, samples, -1)
    return samples, valid


def relabel(seeds: jnp.ndarray, samples: jnp.ndarray, valid: jnp.ndarray):
    """Compact (seeds ∪ samples) into local ids — Algorithm 1's second loop.

    The hash map M of the paper is replaced by a sort-based unique (DESIGN.md
    §2).  Ordering differs from first-appearance order (new nodes come out
    sorted ascending) — a pure relabeling, mathematically irrelevant.

    Returns (edges_local (S,F) int32, src_nodes (S + S*F,) int32 padded -1,
             num_src ()).  src_nodes[:S] == seeds.
    """
    with jax.named_scope("relabel"):
        S = seeds.shape[0]
        cap = samples.size

        seed_ok = seeds >= 0
        seeds_key = jnp.where(seed_ok, seeds, _SENTINEL)
        seed_order = jnp.argsort(seeds_key)
        seeds_sorted = seeds_key[seed_order]

        flat = samples.reshape(-1)
        flat_valid = valid.reshape(-1)

        # membership of each sample in the seed set
        pos = jnp.searchsorted(seeds_sorted, flat)
        pos_c = jnp.clip(pos, 0, S - 1)
        is_seed = (seeds_sorted[pos_c] == flat) & flat_valid
        seed_local = seed_order[pos_c]

        # unique over non-seed samples
        nonseed = jnp.where(flat_valid & ~is_seed, flat, _SENTINEL)
        ns_sorted = jnp.sort(nonseed)
        first = jnp.concatenate([jnp.array([True]),
                                 ns_sorted[1:] != ns_sorted[:-1]])
        is_new = first & (ns_sorted != _SENTINEL)
        rank = jnp.cumsum(is_new) - 1                 # rank among new nodes
        num_new = jnp.sum(is_new).astype(jnp.int32)

        # compact the unique new nodes (sorted ascending), pad with sentinel
        new_nodes = jnp.full((cap,), _SENTINEL, jnp.int32)
        scatter_to = jnp.where(is_new, rank, cap)         # cap = dropped
        new_nodes = new_nodes.at[scatter_to].set(ns_sorted, mode="drop")

        # local id of each non-seed sample = S + its rank among unique new
        # nodes
        ns_rank = jnp.searchsorted(new_nodes, flat)
        local = jnp.where(is_seed, seed_local, S + ns_rank).astype(jnp.int32)
        local = jnp.where(flat_valid, local, -1)

        src_nodes = jnp.concatenate([
            jnp.where(seed_ok, seeds, -1),
            jnp.where(new_nodes == _SENTINEL, -1, new_nodes),
        ])
        num_src = S + num_new
        return local.reshape(samples.shape), src_nodes, num_src


def build_indptr(valid: jnp.ndarray) -> jnp.ndarray:
    """The R_l vector of Algorithm 1: cumsum of per-seed valid counts."""
    counts = jnp.sum(valid.astype(jnp.int32), axis=1)
    return jnp.concatenate([jnp.zeros((1,), jnp.int32),
                            jnp.cumsum(counts).astype(jnp.int32)])


def sample_level(graph: CSCGraph, seeds: jnp.ndarray, fanout: int,
                 salt: jnp.ndarray | int) -> MFG:
    """One sampling level -> one MFG (the unfused two-step reference path)."""
    samples, valid = sample_neighbors(graph, seeds, fanout, salt)
    edges, src_nodes, num_src = relabel(seeds, samples, valid)
    return MFG(dst_nodes=seeds, src_nodes=src_nodes, num_src=num_src,
               edges=edges, edge_mask=valid, indptr=build_indptr(valid))


def unfused_coo_csc_pass(samples: jnp.ndarray, valid: jnp.ndarray):
    """The DGL-style COO materialize -> sort -> recount -> CSC passes that
    the fused kernel eliminates (§3.2, Fig. 1).

    Returns (samples, valid, indptr) — values identical to the fused path,
    but computed through the redundant intermediate representation.
    """
    S, fanout = samples.shape
    # -- step 1: COO materialization -------------------------------------
    dst_pos = jnp.repeat(jnp.arange(S, dtype=jnp.int32), fanout)
    coo_src = samples.reshape(-1)
    coo_valid = valid.reshape(-1)

    # -- step 2: COO -> CSC conversion (redundant sort + recount) --------
    sort_key = jnp.where(coo_valid, dst_pos, S)
    order = jnp.argsort(sort_key, stable=True)          # the conversion sort
    src_sorted = coo_src[order]
    key_sorted = sort_key[order]
    counts = jnp.bincount(jnp.where(coo_valid, dst_pos, S),
                          length=S + 1)[:S]              # the recount
    indptr = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(counts).astype(jnp.int32)])
    # scatter back to padded (S, F) layout to relabel (undo the sort)
    inv = jnp.argsort(order)
    samples_rt = src_sorted[inv].reshape(S, fanout)
    valid_rt = (key_sorted[inv] < S).reshape(S, fanout)
    return samples_rt, valid_rt, indptr


def sample_level_unfused(graph: CSCGraph, seeds: jnp.ndarray, fanout: int,
                         salt: jnp.ndarray | int) -> MFG:
    """DGL-style two-step baseline the paper's fused kernel replaces (§3.2).

    Output is identical to ``sample_level``; cost includes the COO
    intermediate.
    """
    samples, valid = sample_neighbors(graph, seeds, fanout, salt)
    samples_rt, valid_rt, indptr = unfused_coo_csc_pass(samples, valid)
    edges, src_nodes, num_src = relabel(seeds, samples_rt, valid_rt)
    return MFG(dst_nodes=seeds, src_nodes=src_nodes, num_src=num_src,
               edges=edges, edge_mask=valid_rt, indptr=indptr)


# --------------------------------------------------------------------------
# level-backend registry
# --------------------------------------------------------------------------
# A *level backend* is any ``level_fn(graph, seeds, fanout, salt) -> MFG``.
# Registering by name lets the distributed step builders, benchmarks, and
# the repro.pipeline API resolve kernels declaratively — and lets
# third-party backends plug in without touching core modules.

_LEVEL_BACKENDS: dict[str, Callable] = {}


def register_backend(name: str, level_fn: Callable, *,
                     overwrite: bool = False) -> None:
    """Register ``level_fn`` under ``name`` (see ``resolve_backend``)."""
    if not overwrite and name in _LEVEL_BACKENDS \
            and _LEVEL_BACKENDS[name] is not level_fn:
        raise ValueError(f"backend {name!r} already registered; "
                         f"pass overwrite=True to replace it")
    _LEVEL_BACKENDS[name] = level_fn


def available_backends() -> tuple[str, ...]:
    """Names currently registered (kernel backends appear once imported)."""
    return tuple(sorted(_LEVEL_BACKENDS))


def resolve_backend(name: str) -> Callable:
    """Look up a level backend by name.

    Built-ins: ``"reference"`` (fused-semantics jnp path), ``"unfused"``
    (DGL-style COO->CSC baseline), ``"fused_pallas"`` (Pallas kernel,
    registered by ``repro.kernels.ops`` — imported lazily on first miss).
    """
    import_err = None
    if name not in _LEVEL_BACKENDS:
        try:  # kernel-backed backends register at import time
            import repro.kernels.ops  # noqa: F401
        except ImportError as e:
            import_err = e
    try:
        return _LEVEL_BACKENDS[name]
    except KeyError:
        msg = (f"unknown sampling backend {name!r}; "
               f"available: {available_backends()}")
        if import_err is not None:
            msg += f" (importing repro.kernels.ops failed: {import_err})"
        raise KeyError(msg) from import_err


register_backend("reference", sample_level)
register_backend("unfused", sample_level_unfused)


def sample_mfgs(graph: CSCGraph, seeds: jnp.ndarray,
                fanouts: Sequence[int], salt: jnp.ndarray | int,
                level_fn=None, backend: str | None = None) -> list[MFG]:
    """Recursive L-level sampling (eqs. 4–5).

    fanouts: (N_L, ..., N_1) — top level first, matching the paper's
    (N_3, N_2, N_1) notation.  Returns MFGs top-level first; a GNN consumes
    them in reverse (layer 1 eats the bottom-most MFG).

    The per-level kernel is chosen by ``backend`` name (registry above) or
    by passing ``level_fn`` directly; the default is the ``"reference"``
    path.  ``backend="fused_pallas"`` swaps in the fused Pallas kernel.
    """
    if level_fn is not None and backend is not None:
        raise ValueError("pass either level_fn or backend, not both")
    if level_fn is None:
        level_fn = resolve_backend(backend or "reference")
    mfgs = []
    frontier = seeds
    for depth, fanout in enumerate(fanouts):
        with jax.named_scope(f"level{depth}"):
            mfg = level_fn(graph, frontier, int(fanout),
                           level_salt(salt, depth))
        mfgs.append(mfg)
        frontier = mfg.src_nodes
    return mfgs
