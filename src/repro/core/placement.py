"""Placement schemes as a first-class registry (the ROADMAP's "new schemes
as registry entries" item).

The paper's two placements — ``"vanilla"`` (topology + features
partitioned, 2L communication rounds) and ``"hybrid"`` (topology
replicated, 2 rounds) — are the extremes of a memory <-> rounds
trade-off: full replication stops scaling at billion-edge graphs, full
partitioning pays 2 rounds per sampling level.  This module makes the
placement axis pluggable, mirroring ``repro.core.sampler.register_backend``:

  * a ``PlacementScheme`` owns its plan construction
    (``build(layout) -> plan``), its per-level sampling program
    (``sample(plan, shard, seeds, fanouts, salt, ...) -> (mfgs, bytes)``),
    and its round/volume accounting (``trace_sampling_rounds`` — program
    structure — and ``expected_sampling_rounds`` — a data-dependent
    estimate of *utilized* rounds);
  * ``repro.pipeline`` dispatches through the scheme object instead of
    branching on a string, so third-party placements plug in with
    ``register_scheme`` and a ``PlanSpec(scheme=...)`` name.

Built-in schemes:

  ``"vanilla"``            behavior-preserving port of the partitioned
                           protocol (``dist.vanilla_sample``).
  ``"hybrid"``             behavior-preserving port of the replicated
                           protocol (``dist.hybrid_sample``).
  ``"hybrid_partial"``     degree-aware partial replication: every worker
                           replicates the in-edge lists of the top-``frac``
                           highest-in-degree nodes ("hot" nodes) and falls
                           back to the vanilla 2-round exchange for cold
                           frontier nodes.  Memory interpolates between the
                           two extremes; *utilized* sampling rounds land
                           between 0 and 2(L-1) in proportion to the cold
                           request mass.  Parameterized either as
                           ``PlanSpec(scheme="hybrid_partial",
                           replicate_frac=0.25)`` or as the inline form
                           ``scheme="hybrid_partial(0.25)"``.

All three schemes produce **bit-identical minibatches** for the same seeds
and salt: sampling draws are a stateless hash of (node id, level salt,
slot), so *where* a node's neighbors are drawn (replicated copy, hot
replica, or owner via exchange) never changes *what* is drawn — the
paper's §4.2 equivalence, extended to the partial scheme and asserted by
``tests/test_placement.py``.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import dist
from repro.core.graph import CSCGraph
from repro.core.mfg import MFG
from repro.core.sampler import level_salt, sample_neighbors


# --------------------------------------------------------------------------
# plans: what a scheme materializes for the traced per-worker program
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PlacementPlan:
    """Host-side product of ``scheme.build(layout)``.

    Holds the partition boundaries plus whatever topology the scheme
    replicates on every worker (``replicated_state``, which pipelines hand
    to the step through ``WorkerShard.replicated``), and — when built from
    a layout — the stacked per-worker local topology for ``WorkerShard``
    construction.

    Attributes
    ----------
    scheme : PlacementScheme
        The scheme that built this plan (sampling dispatches through it).
    offsets : jnp.ndarray
        (P + 1,) contiguous ownership boundaries.
    num_parts : int
        Worker count P.
    local_indptr, local_indices : jnp.ndarray or None
        Stacked (P, ...) per-worker in-edge slices for the shard pytree;
        ``None`` for plans built without a layout (abstract/dry-run use)
        or for schemes whose workers never store local topology.
    """
    scheme: "PlacementScheme"
    offsets: jnp.ndarray
    num_parts: int
    local_indptr: jnp.ndarray | None = None
    local_indices: jnp.ndarray | None = None
    # fraction of edges whose source lives on a different partition than
    # their destination — the first-order probability that an exchanged
    # frontier request actually leaves its worker.  1.0 (the conservative
    # structural value) for plans built without a layout; set from the
    # actual partitioning by ``scheme.build(layout)``, which is what makes
    # ``expected_rounds`` a measured function of the PARTITIONER, not just
    # the scheme.
    remote_source_fraction: float = 1.0

    # -- convenience delegation --------------------------------------------
    def sample(self, shard, seeds, fanouts, salt, *, level_fn=None,
               fused: bool = False, counter=None):
        """``scheme.sample`` with this plan bound (see ``PlacementScheme``)."""
        return self.scheme.sample(self, shard, seeds, fanouts, salt,
                                  level_fn=level_fn, fused=fused,
                                  counter=counter)

    def shard_topology(self):
        """(local_indptr, local_indices) stacked per worker, for the
        ``WorkerShard``; placeholder arrays when the scheme's workers never
        read local topology."""
        if self.local_indptr is None or self.local_indices is None:
            raise ValueError(
                f"plan for scheme {self.scheme.name!r} was built without a "
                f"layout; shard topology is unavailable")
        return self.local_indptr, self.local_indices

    def trace_rounds(self, num_layers: int) -> int:
        """Total all_to_all rounds in the traced per-step program:
        the scheme's structural sampling rounds + 2 feature rounds."""
        return self.scheme.trace_sampling_rounds(num_layers, plan=self) + 2

    def expected_rounds(self, num_layers: int) -> float:
        """Data-dependent estimate of *utilized* rounds per step: the
        scheme's expected sampling rounds + 2 feature rounds."""
        return self.scheme.expected_sampling_rounds(self, num_layers) + 2.0

    @property
    def replicated_graph(self) -> CSCGraph | None:
        """Fully-replicated topology, when the scheme has one (hybrid)."""
        return None

    def replicated_state(self):
        """The topology every worker holds whole, as the pytree the traced
        sampling program reads (``WorkerShard.replicated``); ``None`` when
        the scheme replicates nothing."""
        return None

    def replicated_of(self, shard):
        """``shard.replicated`` — how pipelines hand the replicated state
        to the step, as a jit argument — else this plan's own copy (plans
        built without a pipeline, e.g. ``plan_from_legacy``)."""
        if shard.replicated is not None:
            return shard.replicated
        return self.replicated_state()


def _remote_edge_mass(layout, src_mask: np.ndarray | None = None) -> float:
    """Fraction of the layout's edges whose source is owned by a
    different partition than their destination (optionally restricted to
    edges whose source satisfies ``src_mask``) — the probability mass of
    frontier draws that must cross the fabric during an exchange round.
    Pure numpy over the relabeled CSC; no CSR view is materialized."""
    graph = layout.graph
    indptr = np.asarray(graph.indptr)
    indices = np.asarray(graph.indices)
    if indices.size == 0:
        return 0.0
    offsets = np.asarray(layout.offsets)
    node_owner = (np.searchsorted(offsets, np.arange(graph.num_nodes),
                                  side="right") - 1)
    owner_dst = np.repeat(node_owner, np.diff(indptr))
    remote = node_owner[indices] != owner_dst
    if src_mask is not None:
        remote &= src_mask[indices]
    return float(np.mean(remote))


def _placeholder_topology(num_parts: int):
    """Minimal stacked arrays for schemes that never read local topology
    (keeps the shard pytree's leading worker axis everywhere)."""
    return (jnp.zeros((num_parts, 2), jnp.int32),
            jnp.full((num_parts, 1), -1, jnp.int32))


@dataclasses.dataclass(frozen=True)
class HybridPlacementPlan(PlacementPlan):
    """Hybrid plan: every worker holds the whole topology."""
    graph: CSCGraph | None = None

    @property
    def replicated_graph(self) -> CSCGraph | None:
        return self.graph

    def replicated_state(self):
        return self.graph


@dataclasses.dataclass(frozen=True)
class PartialPlacementPlan(PlacementPlan):
    """Degree-aware partial replication plan.

    Attributes
    ----------
    hot_graph : CSCGraph
        Full-width CSC whose in-edge lists are populated only for hot
        nodes (cold rows are empty) — replicated on every worker.  Edge
        lists keep the global CSC's order, so draws are bit-identical to
        the other schemes.
    hot_mask : jnp.ndarray
        (n,) bool, True for replicated (hot) nodes — replicated.
    frac : float
        Requested replication fraction (top-``frac`` by in-degree).
    hot_count : int
        Number of hot nodes (``complete`` when == n).
    cold_source_fraction : float
        Fraction of edges whose *source* is cold — the probability mass of
        frontier draws that must fall back to the exchange protocol.
    cold_remote_source_fraction : float
        Fraction of edges whose source is cold AND owned by a different
        partition than the destination — the cold request mass that
        actually crosses the fabric, which drives the expected-round
        estimate (and is where the partitioner choice shows up).
    replicated_edges : int
        In-edges replicated per worker (the memory cost knob).
    replicated_edge_fraction : float
        ``replicated_edges`` over the graph's total edge count.
    """
    hot_graph: CSCGraph | None = None
    hot_mask: jnp.ndarray | None = None
    frac: float = 0.0
    hot_count: int = 0
    cold_source_fraction: float = 1.0
    cold_remote_source_fraction: float = 1.0
    replicated_edges: int = 0
    replicated_edge_fraction: float = 0.0

    def replicated_state(self):
        return (self.hot_graph, self.hot_mask)

    @property
    def complete(self) -> bool:
        """True when every node is hot — the program degenerates to the
        hybrid scheme (zero sampling exchanges traced)."""
        n = int(self.hot_mask.shape[0]) if self.hot_mask is not None else -1
        return self.hot_count >= n >= 0


# --------------------------------------------------------------------------
# scheme objects
# --------------------------------------------------------------------------

class PlacementScheme:
    """Base class: a placement scheme owns plan construction, the per-level
    sampling program, and round/volume accounting.

    Subclasses implement:

    ``build(layout) -> PlacementPlan``
        Host-side: materialize replicated constants + per-worker topology.
    ``sample(plan, shard, seeds, fanouts, salt, *, level_fn, fused,
    counter) -> (mfgs, sampling_utilized_bytes)``
        The traced per-worker multi-level sampling program (runs under the
        named axis ``dist.AXIS``).  ``sampling_utilized_bytes`` is a traced
        f32 scalar: valid id/reply payload bytes this worker contributed to
        sampling ``exchange`` rounds (0 for communication-free schemes).
        Kernel dispatch follows the protocol: fully-replicated sampling
        (hybrid) runs each level through ``level_fn`` (the
        ``SamplerSpec.backend`` registry entry); partitioned protocols
        (vanilla, and hybrid_partial's hot+cold merge) draw through the
        protocol's own samplers — for them the backend name only selects
        fused vs unfused level *construction* via ``fused``, exactly as
        the pre-registry vanilla path behaved.  Draws are bit-identical
        across all of these by construction (stateless hashing).
    ``trace_sampling_rounds(num_layers, plan=None) -> int``
        Structural sampling ``exchange`` rounds in one traced step.
    ``expected_sampling_rounds(plan, num_layers) -> float``
        Data-dependent estimate of *utilized* sampling rounds (== the
        structural count for vanilla/hybrid; in (0, 2(L-1)) for partial
        replication).
    """

    name: str = "?"

    def build(self, layout) -> PlacementPlan:
        raise NotImplementedError

    def sample(self, plan, shard, seeds, fanouts, salt, *, level_fn=None,
               fused: bool = False, counter=None):
        raise NotImplementedError

    def trace_sampling_rounds(self, num_layers: int, plan=None) -> int:
        raise NotImplementedError

    def expected_sampling_rounds(self, plan, num_layers: int) -> float:
        return float(self.trace_sampling_rounds(num_layers, plan=plan))


class VanillaScheme(PlacementScheme):
    """Paper baseline: topology + features partitioned -> 2 rounds per
    lower level (behavior-preserving port of ``dist.vanilla_sample``)."""

    name = "vanilla"

    def build(self, layout) -> PlacementPlan:
        from repro.core.partition import build_vanilla
        vplan = build_vanilla(layout)
        return PlacementPlan(scheme=self, offsets=layout.offsets,
                             num_parts=layout.num_parts,
                             local_indptr=vplan.local_indptr,
                             local_indices=vplan.local_indices,
                             remote_source_fraction=_remote_edge_mass(
                                 layout))

    def sample(self, plan, shard, seeds, fanouts, salt, *, level_fn=None,
               fused: bool = False, counter=None):
        return dist.vanilla_sample(shard, plan.offsets, plan.num_parts,
                                   seeds, fanouts, salt, counter,
                                   fused=fused, with_stats=True)

    def trace_sampling_rounds(self, num_layers: int, plan=None) -> int:
        return 2 * (num_layers - 1)

    def expected_sampling_rounds(self, plan, num_layers: int) -> float:
        """Each of the 2(L-1) structural exchange rounds is *utilized* in
        proportion to the request mass that actually leaves its worker —
        first order, the partitioner's cross-partition edge mass.  A
        better partitioner therefore lowers this estimate at an unchanged
        structural count."""
        if plan is None:
            return float(self.trace_sampling_rounds(num_layers))
        return (2.0 * (num_layers - 1)
                * float(plan.remote_source_fraction))


class HybridScheme(PlacementScheme):
    """The paper's contribution: topology replicated, features partitioned
    -> sampling is local (behavior-preserving port of
    ``dist.hybrid_sample``)."""

    name = "hybrid"

    def build(self, layout) -> HybridPlacementPlan:
        li, lx = _placeholder_topology(layout.num_parts)
        return HybridPlacementPlan(scheme=self, offsets=layout.offsets,
                                   num_parts=layout.num_parts,
                                   local_indptr=li, local_indices=lx,
                                   graph=layout.graph)

    def sample(self, plan, shard, seeds, fanouts, salt, *, level_fn=None,
               fused: bool = False, counter=None):
        graph = plan.replicated_of(shard)
        if graph is None:
            raise ValueError("hybrid scheme needs the replicated topology")
        mfgs = dist.hybrid_sample(graph, seeds, fanouts, salt,
                                  level_fn=level_fn)
        return mfgs, jnp.zeros((), jnp.float32)

    def trace_sampling_rounds(self, num_layers: int, plan=None) -> int:
        return 0


class HybridPartialScheme(PlacementScheme):
    """Degree-aware partial replication (the §5 future-work direction):
    replicate only the in-edge lists of the top-``frac`` highest-in-degree
    nodes; cold frontier nodes fall back to the vanilla 2-round exchange.

    ``frac=1.0`` is the hybrid program (zero sampling exchanges traced);
    ``frac=0.0`` is the vanilla program; in between, the traced program
    keeps the 2(L-1) exchange rounds but their *utilized* payload — and
    therefore the expected rounds — shrinks with the hot set's edge
    coverage (power-law graphs concentrate edge mass in few nodes, so a
    small ``frac`` removes most of the request volume).

    Like the vanilla protocol, draws run through the protocol's own
    samplers (``sample_neighbors`` on the hot replica,
    ``dist.exchange_sample_level`` for the cold fallback) so hot and cold
    samples can be merged *before* relabeling; ``SamplerSpec.backend``
    therefore selects only fused vs unfused level construction here, not
    the per-draw kernel (minibatches are bit-identical either way).
    """

    name = "hybrid_partial"

    def __init__(self, frac: float | None = None):
        if frac is None:
            raise ValueError(
                "hybrid_partial needs a replication fraction: use "
                "PlanSpec(scheme='hybrid_partial', replicate_frac=...) or "
                "the inline form scheme='hybrid_partial(0.25)'")
        frac = float(frac)
        if not 0.0 <= frac <= 1.0:
            raise ValueError(f"replicate_frac must be in [0, 1], got {frac}")
        self.frac = frac

    # hot-set scorer registry name ranking the replication candidates
    # (``repro.core.cache.resolve_hot_scorer``); "degree" reproduces the
    # pre-registry stable in-degree argsort bit-identically
    hot_scorer = "degree"

    def build(self, layout) -> PartialPlacementPlan:
        from repro.core.cache import resolve_hot_scorer
        from repro.core.partition import build_vanilla

        graph = layout.graph
        indptr = np.asarray(graph.indptr)
        indices = np.asarray(graph.indices)
        n = graph.num_nodes
        deg = np.diff(indptr)

        k = int(np.round(self.frac * n))
        hot_ids = resolve_hot_scorer(self.hot_scorer).top_ids(graph, k)
        hot_mask = np.zeros(n, bool)
        hot_mask[hot_ids] = True

        keep = np.repeat(hot_mask, deg)
        hot_indices = indices[keep]
        hot_deg = np.where(hot_mask, deg, 0)
        hot_indptr = np.zeros(n + 1, np.int64)
        np.cumsum(hot_deg, out=hot_indptr[1:])
        if hot_indices.size == 0:       # keep indexing well-defined
            hot_indices = np.full(1, -1, np.int64)
        hot_graph = CSCGraph(indptr=jnp.asarray(hot_indptr, jnp.int32),
                             indices=jnp.asarray(hot_indices, jnp.int32))

        num_edges = max(int(indices.size), 1)
        cold_src = float(np.mean(~hot_mask[indices])) if indices.size else 0.0
        cold_remote = _remote_edge_mass(layout, src_mask=~hot_mask)
        replicated = int(hot_deg.sum())

        # workers keep their vanilla partition slice to serve cold requests
        vplan = build_vanilla(layout)
        return PartialPlacementPlan(
            scheme=self, offsets=layout.offsets,
            num_parts=layout.num_parts,
            local_indptr=vplan.local_indptr,
            local_indices=vplan.local_indices,
            remote_source_fraction=_remote_edge_mass(layout),
            hot_graph=hot_graph,
            hot_mask=jnp.asarray(hot_mask),
            frac=self.frac, hot_count=k,
            cold_source_fraction=cold_src,
            cold_remote_source_fraction=cold_remote,
            replicated_edges=replicated,
            replicated_edge_fraction=replicated / num_edges)

    def sample(self, plan, shard, seeds, fanouts, salt, *, level_fn=None,
               fused: bool = False, counter=None):
        offsets, P = plan.offsets, plan.num_parts
        me = lax.axis_index(dist.AXIS)
        my_offset = offsets[me]
        n_local = offsets[me + 1] - my_offset
        hot_any = plan.hot_count > 0        # static: specializes the trace
        complete = plan.complete
        hot_graph, hot_mask = plan.replicated_of(shard)

        util = jnp.zeros((), jnp.float32)
        mfgs: list[MFG] = []
        frontier = seeds
        for depth, fanout in enumerate(fanouts):
            with jax.named_scope(f"level{depth}"):
                fanout = int(fanout)
                if depth == 0:
                    # seeds are locally-owned labeled nodes -> no communication
                    samples = dist.sample_neighbors_local(
                        shard.local_indptr, shard.local_indices, my_offset,
                        n_local, frontier, fanout, level_salt(salt, depth))
                else:
                    if hot_any:
                        is_hot = (hot_mask[jnp.clip(frontier, 0)]
                                  & (frontier >= 0))
                        hot_frontier = jnp.where(is_hot, frontier, -1)
                        hot_samples, _ = sample_neighbors(
                            hot_graph, hot_frontier, fanout,
                            level_salt(salt, depth))
                    if complete:
                        samples = hot_samples
                    else:
                        cold_frontier = (jnp.where(is_hot, -1, frontier)
                                         if hot_any else frontier)
                        cold_samples, level_bytes = dist.exchange_sample_level(
                            shard, offsets, P, cold_frontier, fanout,
                            level_salt(salt, depth), counter)
                        samples = (jnp.where(is_hot[:, None], hot_samples,
                                             cold_samples)
                                   if hot_any else cold_samples)
                        util = util + level_bytes
                mfg = dist.finish_level(frontier, samples, fused)
            mfgs.append(mfg)
            frontier = mfg.src_nodes
        return mfgs, util

    def trace_sampling_rounds(self, num_layers: int, plan=None) -> int:
        if plan is not None:
            if plan.complete:
                return 0
            return 2 * (num_layers - 1)
        # nominal (no data): frac pins the two degenerate cases
        if self.frac >= 1.0:
            return 0
        return 2 * (num_layers - 1)

    def expected_sampling_rounds(self, plan, num_layers: int) -> float:
        """First-order utilized-round estimate: each of the 2(L-1)
        exchange rounds is utilized in proportion to the cold request
        mass that actually crosses partitions (cold source AND remote
        owner) — at ``frac=0`` this degenerates to the vanilla estimate
        on the same layout, and a lower-edge-cut partitioner lowers it
        for every ``frac``."""
        if plan is None:
            return 0.0 if self.frac >= 1.0 else 2.0 * (num_layers - 1)
        if plan.complete:
            return 0.0
        return (2.0 * (num_layers - 1)
                * float(plan.cold_remote_source_fraction))


# --------------------------------------------------------------------------
# registry
# --------------------------------------------------------------------------

_SCHEMES: dict[str, Callable[..., PlacementScheme]] = {}

_PARAM_RE = re.compile(r"^([A-Za-z_][\w+-]*)\(([^()]*)\)$")


def parse_scheme_name(name: str) -> tuple[str, float | None]:
    """Split an optionally-parameterized scheme name.

    Examples
    --------
    >>> parse_scheme_name("hybrid")
    ('hybrid', None)
    >>> parse_scheme_name("hybrid_partial(0.25)")
    ('hybrid_partial', 0.25)
    """
    m = _PARAM_RE.match(name)
    if m is None:
        return name, None
    try:
        return m.group(1), float(m.group(2))
    except ValueError:
        raise ValueError(
            f"scheme parameter in {name!r} must be a float") from None


def register_scheme(name: str, factory: Callable[..., PlacementScheme], *,
                    overwrite: bool = False) -> None:
    """Register a placement-scheme factory under ``name``.

    ``factory(frac=None)`` must return a ``PlacementScheme``; factories for
    unparameterized schemes should reject a non-None ``frac``.
    """
    if not overwrite and name in _SCHEMES and _SCHEMES[name] is not factory:
        raise ValueError(f"placement scheme {name!r} already registered; "
                         f"pass overwrite=True to replace it")
    _SCHEMES[name] = factory


def available_schemes() -> tuple[str, ...]:
    """Sorted names of registered placement schemes.

    Examples
    --------
    >>> set(available_schemes()) >= {"vanilla", "hybrid", "hybrid_partial"}
    True
    """
    return tuple(sorted(_SCHEMES))


def resolve_scheme(name: str, *, frac: float | None = None
                   ) -> PlacementScheme:
    """Instantiate the scheme registered under ``name``.

    ``name`` may carry an inline parameter (``"hybrid_partial(0.25)"``);
    an explicit ``frac`` keyword must agree with it when both are given.
    Raises ``KeyError`` listing the available names when unknown.
    """
    base, inline = parse_scheme_name(name)
    if inline is not None:
        if frac is not None and float(frac) != inline:
            raise ValueError(
                f"conflicting replication fractions: scheme name carries "
                f"{inline}, keyword gives {frac}")
        frac = inline
    try:
        factory = _SCHEMES[base]
    except KeyError:
        raise KeyError(f"unknown placement scheme {name!r}; "
                       f"available: {available_schemes()}") from None
    return factory(frac=frac)


def _unparameterized(cls):
    def factory(frac: float | None = None):
        if frac is not None:
            raise ValueError(
                f"scheme {cls.name!r} takes no replication fraction")
        return cls()
    return factory


register_scheme("vanilla", _unparameterized(VanillaScheme))
register_scheme("hybrid", _unparameterized(HybridScheme))
register_scheme("hybrid_partial",
                lambda frac=None: HybridPartialScheme(frac))


def plan_from_legacy(scheme: str, *, graph_replicated=None, offsets=None,
                     num_parts: int = 0) -> PlacementPlan:
    """Build a layout-free plan from the legacy (scheme string,
    graph_replicated) calling convention of ``worker.make_worker_step`` —
    enough to run the traced program; shard topology must come from the
    caller's ``WorkerShard``.  Parameterized schemes need a real plan:
    build one with ``resolve_scheme(...).build(layout)`` and pass it via
    ``plan=``.
    """
    base, frac = parse_scheme_name(scheme)
    if base == "vanilla":
        return PlacementPlan(scheme=resolve_scheme("vanilla"),
                             offsets=offsets, num_parts=num_parts)
    if base == "hybrid":
        if graph_replicated is None:
            raise ValueError("hybrid scheme needs the replicated topology")
        return HybridPlacementPlan(scheme=resolve_scheme("hybrid"),
                                   offsets=offsets, num_parts=num_parts,
                                   graph=graph_replicated)
    if frac is not None or base in _SCHEMES:
        raise ValueError(
            f"scheme {scheme!r} needs a layout-built plan; construct it "
            f"with resolve_scheme({scheme!r}).build(layout) and pass "
            f"plan=... (or use repro.pipeline.Pipeline)")
    raise ValueError(f"unknown scheme {scheme!r}; "
                     f"available: {available_schemes()}")
