"""Distributed sampling-based training step (§3.3, Fig. 3).

One per-worker program, written against a named worker axis with
``jax.lax`` collectives only (the paper likewise uses exclusively synchronous
collectives).  The same function runs:

  * under ``jax.vmap(..., axis_name=AXIS)``      — single-device simulation
    (CPU container), bit-identical collective semantics;
  * under ``jax.shard_map`` on a real mesh       — production path.

Communication schemes (paper's accounting):

  * vanilla  : topology + features partitioned.  Top level samples locally;
               each of the L-1 lower levels needs a request round and a reply
               round; feature fetch needs 2 more.           -> 2L rounds.
  * hybrid   : topology replicated, features partitioned.   -> 2 rounds.

Placement is pluggable: ``repro.core.placement`` wraps these programs (plus
the degree-aware ``hybrid_partial`` scheme that interpolates between them)
in a ``PlacementScheme`` registry the pipeline dispatches through.

Every ``exchange`` call increments a trace-time round counter — categorized
as sampling vs feature rounds — so tests can assert the 2L -> 2 reduction
structurally.

These primitives are composed into the per-step program by
``repro.pipeline.worker`` (fused) and ``repro.pipeline.prefetch`` (split
at the prefetch boundary for double-buffered execution); see
``docs/architecture.md`` for the data-flow walkthrough.
"""
from __future__ import annotations

import dataclasses
import warnings
from functools import partial
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.graph import CSCGraph
from repro.core.mfg import MFG
from repro.core.sampler import (build_indptr, hash_u32, level_salt, relabel,
                                sample_level, sample_mfgs)

AXIS = "data"


class RoundCounter:
    """Counts communication rounds at *trace* time (program structure).

    Every ``exchange`` in a traced step ticks the counter once, so after
    one trace ``rounds`` is the per-step round count — the quantity the
    paper's 2L -> 2 claim is about — independent of how many steps run.

    Rounds are categorized by what they carry — ``"sampling"`` (frontier
    ids / neighbor replies of the partitioned protocols) vs ``"feature"``
    (the 2 id/row rounds of the feature fetch) — so reports can show where
    partial-replication schemes land between the hybrid (2) and vanilla
    (2L) extremes.  ``rounds`` stays the category sum for backward
    compatibility.

    Attributes
    ----------
    kinds : list[str]
        Category of each traced round, in trace order.
    bytes_per_round : list[int]
        Buffer capacity (bytes) of each round — *capacity*, not utilized
        bytes; padding slots count.  (Utilized bytes are data-dependent;
        the step program reports them per category in its ``metrics``.)

    Examples
    --------
    >>> c = RoundCounter()
    >>> (c.rounds, c.sampling_rounds, c.feature_rounds)
    (0, 0, 0)
    """

    def __init__(self):
        self.kinds: list[str] = []
        self.bytes_per_round: list[int] = []

    @property
    def rounds(self) -> int:
        """Total all_to_all rounds traced (all categories)."""
        return len(self.kinds)

    @property
    def sampling_rounds(self) -> int:
        """Rounds carrying sampling requests/replies."""
        return sum(k == "sampling" for k in self.kinds)

    @property
    def feature_rounds(self) -> int:
        """Rounds carrying feature ids/rows."""
        return sum(k == "feature" for k in self.kinds)

    def capacity_bytes(self, kind: str | None = None) -> int:
        """Summed buffer capacity over rounds of ``kind`` (None = all)."""
        return sum(b for k, b in zip(self.kinds, self.bytes_per_round)
                   if kind is None or k == kind)

    def tick(self, buf, kind: str = "other") -> None:
        """Record one round of category ``kind`` carrying pytree ``buf``."""
        self.kinds.append(kind)
        self.bytes_per_round.append(
            sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(buf)))


def exchange(buf: jnp.ndarray, counter: RoundCounter | None,
             kind: str = "other") -> jnp.ndarray:
    """One all_to_all communication round over the worker axis.

    Parameters
    ----------
    buf : jnp.ndarray
        Per-worker buffer of shape (P, cap, ...): row q is the payload
        destined for worker q.
    counter : RoundCounter or None
        Ticked at trace time when given.
    kind : str, default "other"
        Round category recorded by the counter ("sampling" / "feature").

    Returns
    -------
    jnp.ndarray
        Same layout where row q is the payload *received from* worker q.

    Examples
    --------
    Under vmap simulation with P=2 workers, row exchange is a transpose
    of the stacked (P, P, cap) buffer::

        out = jax.vmap(lambda b: exchange(b, None), axis_name=AXIS)(bufs)
    """
    if counter is not None:
        counter.tick(buf, kind=kind)
    return lax.all_to_all(buf, AXIS, split_axis=0, concat_axis=0)


# --------------------------------------------------------------------------
# order-deterministic worker-axis reductions
# --------------------------------------------------------------------------

def pmean_ordered(x, axis_name: str = AXIS):
    """``lax.pmean`` with a reduction order fixed by the program itself.

    ``lax.pmean``/``lax.psum`` leave the summation order to the backend:
    XLA's intra-process reduction and gloo's cross-process ring allreduce
    (the CPU collectives the ``"multiprocess"`` executor runs on) sum in
    different orders, so their float results can differ in the last bit.
    This variant makes the order part of the program — ``all_gather``
    (pure data movement, bit-exact on every backend) followed by a local
    mean over the gathered worker axis — so vmap, shard_map, and
    multi-process gloo all execute the *same* reduction and agree
    bit-for-bit (``tests/test_multihost.py`` asserts it).

    Works on any pytree, like ``lax.pmean``.
    """
    return jax.tree.map(
        lambda a: jnp.mean(lax.all_gather(a, axis_name), axis=0), x)


def psum_ordered(x, axis_name: str = AXIS):
    """``lax.psum`` with a program-fixed reduction order (all_gather +
    local sum over the gathered worker axis); see ``pmean_ordered`` for
    why backend-ordered reductions break cross-process bit-equivalence.
    """
    return jax.tree.map(
        lambda a: jnp.sum(lax.all_gather(a, axis_name), axis=0), x)


# --------------------------------------------------------------------------
# owner-based packing
# --------------------------------------------------------------------------

def owner_of(offsets: jnp.ndarray, ids: jnp.ndarray) -> jnp.ndarray:
    """Owning worker of each (relabeled, contiguously-owned) node id.

    Parameters
    ----------
    offsets : jnp.ndarray
        (P + 1,) partition boundaries from the layout.
    ids : jnp.ndarray
        Global node ids (any shape).

    Returns
    -------
    jnp.ndarray
        int32 worker indices, same shape as ``ids``.

    Examples
    --------
    >>> import jax.numpy as jnp
    >>> list(owner_of(jnp.array([0, 3, 6]), jnp.array([0, 2, 3, 5])))
    [Array(0, dtype=int32), Array(0, dtype=int32), Array(1, dtype=int32), Array(1, dtype=int32)]
    """
    return (jnp.searchsorted(offsets, ids, side="right") - 1).astype(jnp.int32)


def pack_by_owner(ids: jnp.ndarray, owner: jnp.ndarray, num_parts: int):
    """Group ``ids`` into per-peer request buffers of static capacity N.

    The inverse mapping is kept so replies can be scattered back to the
    original positions — the pattern every communication round uses.

    Parameters
    ----------
    ids : jnp.ndarray
        (N,) node ids; -1 marks padding (dropped from every buffer).
    owner : jnp.ndarray
        (N,) owning worker per id (``owner_of``).
    num_parts : int
        Number of workers P.

    Returns
    -------
    (buf, owner_idx, slot_idx)
        ``buf`` (P, N) int32 padded -1; element i of ``ids`` sits at
        ``buf[owner_idx[i], slot_idx[i]]`` so a reply indexed the same
        way restores the original order.
    """
    N = ids.shape[0]
    key = jnp.where(ids >= 0, owner, num_parts)
    order = jnp.argsort(key, stable=True)
    ids_s = ids[order]
    key_s = key[order]
    seg_start = jnp.searchsorted(key_s, jnp.arange(num_parts))
    slot = (jnp.arange(N) - seg_start[jnp.clip(key_s, 0, num_parts - 1)]
            ).astype(jnp.int32)

    buf = jnp.full((num_parts, N), -1, jnp.int32)
    row = jnp.where(key_s < num_parts, key_s, 0)
    col = jnp.where(key_s < num_parts, slot, N)       # N -> dropped
    buf = buf.at[row, col].set(jnp.where(key_s < num_parts, ids_s, -1),
                               mode="drop")

    owner_idx = jnp.zeros(N, jnp.int32).at[order].set(
        jnp.clip(key_s, 0, num_parts - 1))
    slot_idx = jnp.zeros(N, jnp.int32).at[order].set(jnp.clip(slot, 0, N - 1))
    return buf, owner_idx, slot_idx


# --------------------------------------------------------------------------
# local-CSC sampling (vanilla workers only store their partition's in-edges)
# --------------------------------------------------------------------------

def sample_neighbors_local(local_indptr: jnp.ndarray,
                           local_indices: jnp.ndarray,
                           my_offset: jnp.ndarray,
                           n_local: jnp.ndarray,
                           ids: jnp.ndarray, fanout: int,
                           salt) -> jnp.ndarray:
    """Sample neighbors of (globally-identified) ``ids`` this worker owns.

    Identical draw semantics and hash stream as
    ``sampler.sample_neighbors`` — the property that makes vanilla and hybrid
    schemes produce bit-identical minibatches (paper §4.2).
    Returns samples (N, F) int32 global ids, -1 where invalid / not owned.
    """
    local = ids - my_offset
    owned = (ids >= 0) & (local >= 0) & (local < n_local)
    lrow = jnp.clip(local, 0)
    start = local_indptr[lrow]
    deg = jnp.where(owned, local_indptr[lrow + 1] - start, 0)

    slots = jnp.arange(fanout, dtype=jnp.uint32)[None, :]
    v = jnp.clip(ids, 0)
    bits = hash_u32(v[:, None].astype(jnp.uint32) * jnp.uint32(2654435761)
                    + slots, salt)
    rand_idx = (bits % jnp.maximum(deg, 1)[:, None].astype(jnp.uint32)
                ).astype(jnp.int32)
    take_all = (deg <= fanout)[:, None]
    col = jnp.where(take_all, jnp.arange(fanout, dtype=jnp.int32)[None, :],
                    rand_idx)
    valid = (jnp.arange(fanout)[None, :]
             < jnp.minimum(deg, fanout)[:, None]) & owned[:, None]
    samples = local_indices[start[:, None] + col]
    return jnp.where(valid, samples, -1)


def exchange_sample_level(shard: "WorkerShard", offsets: jnp.ndarray,
                          num_parts: int, frontier: jnp.ndarray,
                          fanout: int, salt,
                          counter: RoundCounter | None):
    """One lower level of the partitioned sampling protocol (2 rounds):
    pack the frontier by owner, ``exchange`` requests, draw on the owning
    worker, ``exchange`` replies back to the requesting slots.

    Shared by every scheme that falls back to owner-side sampling (the
    vanilla scheme for its whole frontier, ``hybrid_partial`` for the cold
    remainder), so the protocol — and its utilized-byte accounting — has
    one implementation.

    Returns
    -------
    (samples, utilized_bytes)
        ``samples`` (N, fanout) int32 global ids (-1 where the frontier
        slot was padding/invalid); ``utilized_bytes`` traced f32 scalar of
        valid request-id + reply payload bytes this worker contributed.
    """
    me = lax.axis_index(AXIS)
    my_offset = offsets[me]
    n_local = offsets[me + 1] - my_offset

    own = owner_of(offsets, frontier)
    buf, oidx, sidx = pack_by_owner(frontier, own, num_parts)
    reqs = exchange(buf, counter, kind="sampling")              # round: ids
    got = sample_neighbors_local(
        shard.local_indptr, shard.local_indices, my_offset, n_local,
        reqs.reshape(-1), fanout, salt)
    reply = exchange(got.reshape(num_parts, -1, fanout),
                     counter, kind="sampling")                  # round: nbrs
    samples = reply[oidx, sidx]
    samples = jnp.where((frontier >= 0)[:, None], samples, -1)
    m = jnp.sum((frontier >= 0).astype(jnp.float32))
    return samples, m * 4.0 * (1.0 + fanout)


def finish_level(frontier: jnp.ndarray, samples: jnp.ndarray,
                 fused: bool) -> MFG:
    """Turn one level's raw draws into its MFG — the level-construction
    tail every partitioned sampling protocol shares.

    ``fused`` selects direct row-pointer construction (the paper's fused
    kernel semantics); False pays the DGL-style COO->CSC conversion passes
    first (values are identical either way, cost is not).
    """
    valid = samples >= 0
    if fused:
        indptr = build_indptr(valid)
    else:
        from repro.core.sampler import unfused_coo_csc_pass
        samples, valid, indptr = unfused_coo_csc_pass(samples, valid)
    edges, src_nodes, num_src = relabel(frontier, samples, valid)
    return MFG(dst_nodes=frontier, src_nodes=src_nodes, num_src=num_src,
               edges=edges, edge_mask=valid, indptr=indptr)


# --------------------------------------------------------------------------
# per-worker state
# --------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class WorkerShard:
    """Per-worker slice of the partitioned data (leading P axis when stacked).

    Vanilla workers use local_indptr/local_indices; hybrid workers ignore
    them.  ``replicated`` is the topology every worker holds whole — the
    placement plan's ``replicated_state()`` (the full CSC for hybrid, the
    hot subgraph for hybrid_partial, ``None`` for vanilla).  It has no
    worker axis: executors broadcast it (vmap) or replicate it over the
    mesh (shard_map), and it enters the jitted step as an argument, never
    as a constant baked into the program.
    """
    features: jnp.ndarray       # (n_max, D)
    labels: jnp.ndarray         # (n_max,)
    local_indptr: jnp.ndarray   # (n_max + 1,)
    local_indices: jnp.ndarray  # (nnz_max,)
    replicated: Any = None

    def tree_flatten(self):
        return (self.features, self.labels, self.local_indptr,
                self.local_indices, self.replicated), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    def axes(self, worker, whole):
        """A prefix tree giving ``worker`` for the per-worker fields and
        ``whole`` for ``replicated`` — the in_axes / PartitionSpec /
        sharding tree executors bind a stacked shard with."""
        return WorkerShard(worker, worker, worker, worker, whole)

    def worker(self, i):
        """Worker ``i``'s slice of a stacked shard (``replicated`` is
        shared, not sliced)."""
        return WorkerShard(self.features[i], self.labels[i],
                           self.local_indptr[i], self.local_indices[i],
                           self.replicated)


# --------------------------------------------------------------------------
# the two sampling schemes (per-worker programs)
# --------------------------------------------------------------------------

def hybrid_sample(graph: CSCGraph, seeds: jnp.ndarray,
                  fanouts: Sequence[int], salt,
                  level_fn=sample_level) -> list[MFG]:
    """Multi-level sampling under the hybrid scheme: topology replicated,
    so sampling is entirely local (0 communication rounds).

    Parameters
    ----------
    graph : CSCGraph
        The replicated topology.
    seeds : jnp.ndarray
        (batch,) seed node ids (-1 padding allowed).
    fanouts : Sequence[int]
        Per-level fanouts, top level first.
    salt
        uint32 sampling salt (the deterministic hash stream).
    level_fn : Callable, optional
        Level backend (see ``repro.core.sampler.resolve_backend``).

    Returns
    -------
    list[MFG]
        One message-flow graph per level, top first.
    """
    return sample_mfgs(graph, seeds, fanouts, salt, level_fn=level_fn)


def vanilla_sample(shard: WorkerShard, offsets: jnp.ndarray,
                   num_parts: int, seeds: jnp.ndarray,
                   fanouts: Sequence[int], salt,
                   counter: RoundCounter | None,
                   fused: bool = False,
                   with_stats: bool = False):
    """Multi-level sampling under the vanilla scheme: topology
    partitioned -> 2 rounds per level below the top (Fig. 3).

    Each lower level packs its frontier by owner (``pack_by_owner``),
    ``exchange``s requests, samples on the owning worker
    (``sample_neighbors_local``), and ``exchange``s replies.  Draw
    semantics are identical to ``hybrid_sample`` — the schemes produce
    bit-identical minibatches (paper §4.2).

    Parameters
    ----------
    shard, offsets, num_parts
        Per-worker data + partition boundaries.
    seeds, fanouts, salt
        As in ``hybrid_sample``.
    counter : RoundCounter or None
        Ticked once per ``exchange`` at trace time.
    fused : bool, default False
        False additionally pays the DGL-style COO->CSC conversion per
        level (paper Fig. 6 'vanilla' scenario); True composes the
        partitioned protocol with fused level construction (an ablation
        the paper doesn't run but our harness can).
    with_stats : bool, default False
        Also return the traced f32 scalar of *utilized* sampling-exchange
        bytes this worker contributed (valid request ids + their replies).

    Returns
    -------
    list[MFG] or (list[MFG], jnp.ndarray)
        One message-flow graph per level, top first; with ``with_stats``,
        also the utilized sampling bytes.
    """
    me = lax.axis_index(AXIS)
    my_offset = offsets[me]
    n_local = offsets[me + 1] - my_offset

    util = jnp.zeros((), jnp.float32)
    mfgs = []
    frontier = seeds
    for depth, fanout in enumerate(fanouts):
        with jax.named_scope(f"level{depth}"):
            fanout = int(fanout)
            if depth == 0:
                # top level: seeds are local labeled nodes -> no communication
                samples = sample_neighbors_local(
                    shard.local_indptr, shard.local_indices, my_offset,
                    n_local, frontier, fanout, level_salt(salt, depth))
            else:
                samples, level_bytes = exchange_sample_level(
                    shard, offsets, num_parts, frontier, fanout,
                    level_salt(salt, depth), counter)
                util = util + level_bytes
            mfg = finish_level(frontier, samples, fused)
        mfgs.append(mfg)
        frontier = mfg.src_nodes
    if with_stats:
        return mfgs, util
    return mfgs


def fetch_features(src_nodes: jnp.ndarray, offsets: jnp.ndarray,
                   num_parts: int, features_local: jnp.ndarray,
                   counter: RoundCounter | None,
                   cache=None) -> jnp.ndarray:
    """The 2 feature rounds shared by both schemes (ids out, rows back).

    Parameters
    ----------
    src_nodes : jnp.ndarray
        (N,) global ids to fetch (-1 padding yields zero rows).
    offsets, num_parts
        Partition boundaries / worker count.
    features_local : jnp.ndarray
        (n_local_max, D) this worker's feature shard.
    counter : RoundCounter or None
        Ticked twice (id round + row round) at trace time.
    cache : repro.core.cache.FeatureCache, optional
        Makes hot remote features a first-class stage of the fetch: hits
        are served locally and only misses ride the all_to_all.  Rows are
        bit-identical with or without a cache; use
        ``fetch_features_cached`` to also get the hit count.

    Returns
    -------
    jnp.ndarray
        (N, D) feature rows aligned with ``src_nodes``.
    """
    if cache is not None:
        h, _ = fetch_features_cached(src_nodes, offsets, num_parts,
                                     features_local, cache, counter)
        return h
    me = lax.axis_index(AXIS)
    my_offset = offsets[me]
    n_local = features_local.shape[0]

    own = owner_of(offsets, src_nodes)
    buf, oidx, sidx = pack_by_owner(src_nodes, own, num_parts)
    reqs = exchange(buf, counter, kind="feature")               # round: ids
    local = reqs - my_offset
    ok = (reqs >= 0) & (local >= 0) & (local < n_local)
    rows = features_local[jnp.clip(local, 0, n_local - 1)]
    rows = rows * ok[..., None].astype(rows.dtype)
    reps = exchange(rows, counter, kind="feature")              # round: rows
    h = reps[oidx, sidx]
    return h * (src_nodes >= 0)[:, None].astype(h.dtype)


def fetch_features_cached(src_nodes: jnp.ndarray, offsets: jnp.ndarray,
                          num_parts: int, features_local: jnp.ndarray,
                          cache, counter: RoundCounter | None = None):
    """Cache-aware feature fetch (bit-identical rows to ``fetch_features``).

    ``cache`` is a ``repro.core.cache.FeatureCache`` (stacked per worker).
    Returns (h (N, D), hit_count scalar).  Hits never enter the request
    buffer (their slot carries -1), so utilized communication bytes drop by
    the hit rate; buffer capacity is unchanged (static shapes).
    """
    K = cache.capacity
    pos = jnp.searchsorted(cache.ids, src_nodes)
    pos_c = jnp.clip(pos, 0, K - 1)
    is_hit = (cache.ids[pos_c] == src_nodes) & (src_nodes >= 0)
    hit_rows = cache.rows[pos_c]

    miss_ids = jnp.where(is_hit, -1, src_nodes)
    h_miss = fetch_features(miss_ids, offsets, num_parts,
                            features_local, counter)
    h = jnp.where(is_hit[:, None], hit_rows.astype(h_miss.dtype), h_miss)
    return h, jnp.sum(is_hit)


# --------------------------------------------------------------------------
# full distributed train step (deprecated shim — see repro.pipeline)
# --------------------------------------------------------------------------

def make_worker_step(*, graph_replicated: CSCGraph | None,
                     offsets: jnp.ndarray, num_parts: int,
                     fanouts: Sequence[int], scheme: str,
                     loss_fn: Callable, level_fn=sample_level,
                     counter: RoundCounter | None = None,
                     vanilla_fused: bool = False):
    """Deprecated: build the per-worker train step.

    Use ``repro.pipeline.Pipeline.build(...)`` (or, for the raw per-worker
    program, ``repro.pipeline.worker.make_worker_step``) instead — kernels
    there resolve by registry name and the feature cache is first-class.

    Returns step(params, shard, seeds, salt) -> (loss, grads), with grads
    already pmean-ed over the worker axis.
    """
    warnings.warn(
        "repro.core.dist.make_worker_step is deprecated; use "
        "repro.pipeline.Pipeline.build(...).train_step(...) or "
        "repro.pipeline.worker.make_worker_step",
        DeprecationWarning, stacklevel=2)
    from repro.pipeline.worker import make_worker_step as _make

    inner = _make(graph_replicated=graph_replicated, offsets=offsets,
                  num_parts=num_parts, fanouts=fanouts, scheme=scheme,
                  loss_fn=loss_fn, level_fn=level_fn, counter=counter,
                  vanilla_fused=vanilla_fused)

    def step(params, shard: WorkerShard, seeds, salt):
        loss, grads, _metrics = inner(params, shard, seeds, salt)
        return loss, grads

    return step


def run_stacked(step, params, shards: WorkerShard, seeds, salt):
    """Single-device simulation: vmap over the stacked worker axis."""
    vstep = jax.vmap(step, in_axes=(None, shards.axes(0, None), 0, None),
                     axis_name=AXIS)
    loss, grads = vstep(params, shards, seeds, salt)
    # pmean makes every worker's copy identical; take worker 0's
    return loss[0], jax.tree.map(lambda g: g[0], grads)


def make_shard_map_step(step, mesh, params_spec, shard_spec, seeds_spec):
    """Production path: the same per-worker program under shard_map."""
    from jax.sharding import PartitionSpec as P

    def wrapper(params, shards, seeds, salt):
        squeeze = lambda a: a[0]
        shards1 = jax.tree.map(squeeze, shards)
        seeds1 = seeds[0]
        loss, grads = step(params, shards1, seeds1, salt)
        return loss, grads

    return jax.shard_map(
        wrapper, mesh=mesh,
        in_specs=(params_spec, shard_spec, seeds_spec, P()),
        out_specs=(P(), params_spec),
        check_vma=False)
