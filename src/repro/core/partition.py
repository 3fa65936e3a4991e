"""Graph partitioning (§3.3) + the hybrid partitioning planner.

The paper uses METIS for edge-cut partitioning with three balance targets:
nodes, edges, and *labeled nodes* per partition (so every machine draws the
same number of seeds per epoch).  Partitioning is a ``Partitioner``
registry (``register_partitioner`` / ``resolve_partitioner``, mirroring
the placement-scheme and graph-source registries) selected by
``repro.pipeline.PlanSpec(partitioner=...)``:

  ``"ldg"``        BFS-ordered linear deterministic greedy — the default.
                   One entry covers both the in-memory pass
                   (``partition_graph``) and the single-pass edge-stream
                   variant (``partition_graph_streaming``) via
                   ``assign`` / ``assign_stream``.
  ``"metis"``      the paper's METIS, when the optional ``pymetis``
                   package is importable (a clean ``ImportError``
                   otherwise); caps repaired + a refinement sweep so the
                   labeled balance target holds.
  ``"labelprop"``  pure-numpy clustering fallback, no optional deps:
                   LDG-initialized capacity-constrained label propagation
                   accepting only strictly cut-reducing moves — edge cut
                   is monotonically non-increasing from the LDG start.
                   ``"labelprop(K)"`` sets the sweep budget.
  ``"random"`` / ``"hash"``   hash-shuffled round-robin baseline: the
                   locality floor every clustering claim is measured
                   against (perfect node + labeled balance, edge-cut
                   ≈ 1 - 1/P).

Every entry produces the same ``assign`` contract consumed by
``build_layout`` — ``(num_nodes,) int32`` in ``[0, num_parts)`` — and the
registry boundary (``Partitioner.assign`` / ``assign_stream``) enforces
the invariants the tests rely on:

  * every node assigned to exactly one partition,
  * node counts balanced within the slack cap,
  * labeled-node counts balanced best-effort (hard-capped where jointly
    feasible — see ``_LDGState.place``),
  * deterministic in ``(graph, num_parts, labeled_mask, seed)`` — a
    contract each entry keeps (pure numpy / seeded METIS), re-checked
    per entry by ``tests/test_partitioners.py``,
  * edge-cut reported (minimized best-effort, not optimality-guaranteed).

After partitioning we RELABEL nodes so partition p owns the contiguous id
range [offsets[p], offsets[p+1]).  Ownership then costs one searchsorted and
a local index is ``id - offsets[p]`` — the TPU-friendly replacement for
DistDGL's hash-map node maps.

Deployment plans live in ``repro.core.placement`` as a PlacementScheme
registry ("vanilla" | "hybrid" | "hybrid_partial" | third-party entries);
the legacy ``VanillaPlan`` / ``HybridPlan`` dataclasses and their
``build_vanilla`` / ``build_hybrid`` constructors remain here (the vanilla
slice builder is what the registry schemes use), but new code should select
placement by name through ``repro.pipeline.PlanSpec(scheme=...)``.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import (CSCGraph, csc_from_numpy_edges, csr_view,
                              mix64)


# --------------------------------------------------------------------------
# assignment
# --------------------------------------------------------------------------

class _LDGState:
    """Mutable state of the linear deterministic greedy placer, shared by
    the in-memory (``partition_graph``) and streaming
    (``partition_graph_streaming``) partitioners: per-partition loads,
    capacities, and the growing ``assign`` vector."""

    def __init__(self, num_nodes: int, num_parts: int,
                 labeled: np.ndarray, slack: float,
                 labeled_slack: float | None):
        if labeled_slack is None:
            labeled_slack = slack
        self.num_parts = num_parts
        self.labeled = labeled
        self.cap_nodes = slack * num_nodes / num_parts
        self.cap_labeled = max(1.0,
                               labeled_slack * labeled.sum() / num_parts)
        self.assign = np.full(num_nodes, -1, np.int32)
        self.load_nodes = np.zeros(num_parts)
        self.load_labeled = np.zeros(num_parts)

    def place(self, v: int, nb: np.ndarray) -> int:
        """Score node ``v`` against its (possibly partial) neighbor list
        ``nb`` and commit it to the winning partition.

        LDG gain: count of already-assigned neighbors per partition,
        discounted by fullness; over-capacity partitions are hard-
        forbidden (node capacity always, labeled capacity when ``v`` is
        labeled)."""
        score = np.zeros(self.num_parts)
        if nb.size:
            anb = self.assign[nb]
            anb = anb[anb >= 0]
            if anb.size:
                score = np.bincount(anb, minlength=self.num_parts
                                    ).astype(float)
        penalty = 1.0 - self.load_nodes / self.cap_nodes
        full = self.load_nodes >= self.cap_nodes
        if self.labeled[v]:
            full = full | (self.load_labeled >= self.cap_labeled)
        gain = np.where(full, -np.inf,
                        (score + 1e-3) * np.maximum(penalty, 1e-6))
        if np.isfinite(gain).any():
            p = int(np.argmax(gain))
        else:
            # the joint node+labeled caps can be infeasible for this
            # placement order (streaming orders especially: every
            # node-open partition may be labeled-full).  Node capacity
            # alone is always satisfiable (slack > 1 and loads sum to
            # fewer than n), so fall back to node-open partitions and
            # take the least labeled-loaded one — labeled overflow stays
            # minimal instead of silently piling onto partition 0.
            ok = self.load_nodes < self.cap_nodes
            p = int(np.argmin(np.where(ok, self.load_labeled, np.inf)))
        self.assign[v] = p
        self.load_nodes[p] += 1
        if self.labeled[v]:
            self.load_labeled[p] += 1
        return p


def partition_graph(graph: CSCGraph, num_parts: int,
                    labeled_mask: np.ndarray, seed: int = 0,
                    slack: float = 1.05,
                    labeled_slack: float | None = None) -> np.ndarray:
    """BFS-ordered LDG edge-cut partitioning.

    ``slack`` bounds per-partition node counts; ``labeled_slack`` bounds
    per-partition labeled-node counts (defaults to ``slack`` — the paper's
    third balance target, so every machine draws equal seeds per epoch).
    Returns ``assign`` (num_nodes,) int32 in [0, num_parts).
    """
    indptr = np.asarray(graph.indptr)
    indices = np.asarray(graph.indices)
    n = graph.num_nodes
    labeled = np.asarray(labeled_mask).astype(bool)

    # out-neighbors give better BFS locality for edge-cut
    view = csr_view(graph)
    out_indptr, out_indices = view.indptr, view.indices

    rng = np.random.default_rng(seed)
    order = _bfs_order(out_indptr, out_indices, n, rng)

    state = _LDGState(n, num_parts, labeled, slack, labeled_slack)
    for v in order:
        # count already-assigned neighbors (both directions) per partition
        nb = np.concatenate([indices[indptr[v]:indptr[v + 1]],
                             out_indices[out_indptr[v]:out_indptr[v + 1]]])
        state.place(v, nb)
    return state.assign


def partition_graph_streaming(edge_chunks, num_nodes: int, num_parts: int,
                              labeled_mask: np.ndarray,
                              slack: float = 1.05,
                              labeled_slack: float | None = None
                              ) -> np.ndarray:
    """Single-pass LDG partitioning over an *edge stream* — for graphs
    whose COO does not fit in memory as one array (the billion-edge ingest
    path; see ``repro.data.ingest``).

    ``edge_chunks`` yields ``(dst, src)`` int array pairs; each chunk is
    processed with the same LDG scorer as ``partition_graph``
    (``_LDGState.place``), but a node's neighbor evidence is limited to
    the edges of the chunk in which it first appears (plus everything
    already assigned) — the classic streaming trade-off.  Nodes never
    touched by any edge are placed last by pure load balancing.

    Same invariants as ``partition_graph``: every node assigned exactly
    once and node loads within the slack cap; labeled-node loads honor
    their cap whenever the placement order leaves it jointly feasible
    (otherwise the overflow is kept minimal — see ``_LDGState.place``).
    The result depends on chunk granularity (it is NOT bit-equal to the
    in-memory partitioner), but both feed the identical downstream
    ``build_layout``.
    """
    labeled = np.asarray(labeled_mask).astype(bool)
    state = _LDGState(num_nodes, num_parts, labeled, slack, labeled_slack)

    for dst, src in edge_chunks:
        dst = np.asarray(dst, np.int64)
        src = np.asarray(src, np.int64)
        # chunk-local bidirectional adjacency: one CSR over concat(edges)
        nodes = np.concatenate([dst, src])
        peers = np.concatenate([src, dst])
        order = np.argsort(nodes, kind="stable")
        nodes_s, peers_s = nodes[order], peers[order]
        uniq, starts = np.unique(nodes_s, return_index=True)
        bounds = np.append(starts, nodes_s.size)
        # place unassigned nodes in chunk first-appearance order
        first = np.full(uniq.size, nodes.size, np.int64)
        np.minimum.at(first, np.searchsorted(uniq, nodes), np.arange(nodes.size))
        for i in np.argsort(first, kind="stable"):
            v = int(uniq[i])
            if state.assign[v] >= 0:
                continue
            state.place(v, peers_s[starts[i]:bounds[i + 1]])

    empty = np.empty(0, np.int64)
    for v in np.flatnonzero(state.assign < 0):
        state.place(int(v), empty)       # isolated nodes: load balance only
    return state.assign


def _bfs_order(out_indptr, out_indices, n, rng):
    seen = np.zeros(n, bool)
    order = np.empty(n, np.int64)
    k = 0
    starts = rng.permutation(n)
    si = 0
    q: deque[int] = deque()
    while k < n:
        while si < n and seen[starts[si]]:
            si += 1
        if si < n and not q:
            q.append(starts[si])
            seen[starts[si]] = True
        while q:
            v = q.popleft()
            order[k] = v
            k += 1
            for u in out_indices[out_indptr[v]:out_indptr[v + 1]]:
                if not seen[u]:
                    seen[u] = True
                    q.append(u)
    return order


def edge_cut(graph: CSCGraph, assign: np.ndarray) -> int:
    """Number of edges whose endpoints live in different partitions."""
    indices = np.asarray(graph.indices)
    dsts = csr_view(graph).dsts
    return int(np.sum(assign[dsts] != assign[indices]))


# --------------------------------------------------------------------------
# partitioner registry
# --------------------------------------------------------------------------

def _validate_assign(assign: np.ndarray, num_nodes: int, num_parts: int,
                     slack: float, who: str) -> np.ndarray:
    """The registry-boundary half of the ``assign`` contract: totality,
    range, dtype, and the node balance cap.  (The labeled cap is
    best-effort by design — see the module docstring — and determinism is
    a per-entry contract re-checked by the test suite.)"""
    assign = np.asarray(assign)
    if assign.shape != (num_nodes,):
        raise ValueError(f"partitioner {who!r} returned shape "
                         f"{assign.shape}, expected ({num_nodes},)")
    if not np.issubdtype(assign.dtype, np.integer):
        raise ValueError(f"partitioner {who!r} returned dtype "
                         f"{assign.dtype}, expected an integer type")
    if assign.size and (assign.min() < 0 or assign.max() >= num_parts):
        raise ValueError(f"partitioner {who!r} assigned ids outside "
                         f"[0, {num_parts})")
    counts = np.bincount(assign, minlength=num_parts)
    cap = slack * num_nodes / num_parts + 1
    if counts.max() > cap:
        raise ValueError(
            f"partitioner {who!r} violated the node balance cap: max "
            f"partition holds {int(counts.max())} nodes, cap is {cap:.1f} "
            f"(slack={slack}, n={num_nodes}, P={num_parts})")
    return assign.astype(np.int32)


class Partitioner:
    """Base class of registry entries: one named edge-cut placement
    strategy producing the ``assign`` contract ``build_layout`` consumes.

    Subclasses implement ``_assign`` (in-memory) and optionally
    ``_assign_stream`` (single-pass over an edge-chunk iterable, for COO
    that never fits in memory — set ``supports_streaming = True``).  The
    public ``assign`` / ``assign_stream`` wrappers are the registry
    boundary: they normalize the labeled mask and validate the contract
    (totality, range, node balance cap) on every result, so a
    mis-behaving third-party entry fails loudly instead of corrupting the
    layout.  Entries must be deterministic in
    ``(graph, num_parts, labeled_mask, seed)``.
    """

    name: str = "?"
    supports_streaming: bool = False

    def assign(self, graph: CSCGraph, num_parts: int, labeled_mask,
               *, seed: int = 0, slack: float = 1.05,
               labeled_slack: float | None = None) -> np.ndarray:
        """Partition ``graph``; returns validated (n,) int32 in [0, P)."""
        labeled = np.asarray(labeled_mask).astype(bool)
        out = self._assign(graph, num_parts, labeled, seed=seed,
                           slack=slack, labeled_slack=labeled_slack)
        return _validate_assign(out, graph.num_nodes, num_parts, slack,
                                self.name)

    def assign_stream(self, edge_chunks, num_nodes: int, num_parts: int,
                      labeled_mask, *, seed: int = 0, slack: float = 1.05,
                      labeled_slack: float | None = None) -> np.ndarray:
        """Partition from an edge-chunk stream (``(dst, src)`` pairs, see
        ``repro.data.ingest``); same validated contract as ``assign``."""
        if not self.supports_streaming:
            raise NotImplementedError(
                f"partitioner {self.name!r} has no streaming variant; "
                f"materialize the graph (repro.data.csc_from_edge_stream) "
                f"and call assign, or use 'ldg'")
        labeled = np.asarray(labeled_mask).astype(bool)
        out = self._assign_stream(edge_chunks, num_nodes, num_parts,
                                  labeled, seed=seed, slack=slack,
                                  labeled_slack=labeled_slack)
        return _validate_assign(out, num_nodes, num_parts, slack, self.name)

    # -- subclass hooks -----------------------------------------------------
    def _assign(self, graph, num_parts, labeled, *, seed, slack,
                labeled_slack) -> np.ndarray:
        raise NotImplementedError

    def _assign_stream(self, edge_chunks, num_nodes, num_parts, labeled,
                       *, seed, slack, labeled_slack) -> np.ndarray:
        raise NotImplementedError


class LDGPartitioner(Partitioner):
    """The repo's default: BFS-ordered linear deterministic greedy, with
    the chunked single-pass variant behind the same entry (the streaming
    result depends on chunk granularity — NOT bit-equal to in-memory)."""

    name = "ldg"
    supports_streaming = True

    def _assign(self, graph, num_parts, labeled, *, seed, slack,
                labeled_slack):
        return partition_graph(graph, num_parts, labeled, seed=seed,
                               slack=slack, labeled_slack=labeled_slack)

    def _assign_stream(self, edge_chunks, num_nodes, num_parts, labeled,
                       *, seed, slack, labeled_slack):
        # the streaming pass is order-determined: seed has nothing to vary
        return partition_graph_streaming(edge_chunks, num_nodes, num_parts,
                                         labeled, slack=slack,
                                         labeled_slack=labeled_slack)


def _hash_assign(num_nodes: int, num_parts: int, labeled: np.ndarray,
                 seed: int) -> np.ndarray:
    """Hash-shuffled round-robin: labeled and unlabeled nodes are dealt
    separately, so BOTH balance targets hold within one node per
    partition — the locality-free baseline."""
    salt = np.uint64((int(seed) * 0x9E3779B97F4A7C15
                      + 0x632BE59BD9B4E019) % (2 ** 64))
    key = mix64(np.arange(num_nodes, dtype=np.uint64) + salt)
    order = np.argsort(key, kind="stable")
    assign = np.empty(num_nodes, np.int32)
    lab_order = order[labeled[order]]
    unlab_order = order[~labeled[order]]
    assign[lab_order] = np.arange(lab_order.size) % num_parts
    # deal the unlabeled remainder against per-partition quotas so TOTAL
    # counts stay within one of n/P even when labels are nearly all nodes
    sizes = np.full(num_parts, num_nodes // num_parts, np.int64)
    sizes[: num_nodes % num_parts] += 1
    lab_counts = np.bincount(assign[lab_order], minlength=num_parts) \
        if lab_order.size else np.zeros(num_parts, np.int64)
    quota = sizes - lab_counts
    while (quota < 0).any():         # labeled ceil landed on a floor slot
        quota[int(np.argmin(quota))] += 1
        quota[int(np.argmax(quota))] -= 1
    seq = np.repeat(np.arange(num_parts, dtype=np.int32), quota)
    assign[unlab_order] = seq[: unlab_order.size]
    return assign


class HashPartitioner(Partitioner):
    """``random`` / ``hash`` baseline: ignores topology entirely.  Its
    edge cut (≈ 1 - 1/P) is the floor every locality-aware entry is
    measured against; streaming is trivial (edges are never read)."""

    name = "random"
    supports_streaming = True

    def _assign(self, graph, num_parts, labeled, *, seed, slack,
                labeled_slack):
        return _hash_assign(graph.num_nodes, num_parts, labeled, seed)

    def _assign_stream(self, edge_chunks, num_nodes, num_parts, labeled,
                       *, seed, slack, labeled_slack):
        return _hash_assign(num_nodes, num_parts, labeled, seed)


def refine_partition(graph: CSCGraph, assign: np.ndarray, num_parts: int,
                     labeled_mask, *, slack: float = 1.05,
                     labeled_slack: float | None = None,
                     sweeps: int = 10) -> np.ndarray:
    """Capacity-constrained label-propagation refinement.

    Sweeps nodes in id order; a node moves to the partition holding the
    most of its (in + out) neighbors iff the move STRICTLY reduces the
    edge cut and the target partition is below both the node cap and
    (for labeled nodes) the labeled cap — so the refined assignment's
    edge cut is monotonically non-increasing from the start point and
    every balance invariant of the input is preserved.  Deterministic
    (fixed sweep order, ties keep the lowest partition id); stops early
    when a sweep moves nothing.
    """
    if labeled_slack is None:
        labeled_slack = slack
    n = graph.num_nodes
    indptr = np.asarray(graph.indptr)
    indices = np.asarray(graph.indices)
    view = csr_view(graph)
    out_indptr, out_indices = view.indptr, view.indices
    labeled = np.asarray(labeled_mask).astype(bool)
    assign = np.asarray(assign, np.int32).copy()
    cap_nodes = slack * n / num_parts
    cap_labeled = max(1.0, labeled_slack * labeled.sum() / num_parts)
    load_nodes = np.bincount(assign, minlength=num_parts).astype(float)
    load_labeled = np.bincount(assign[labeled],
                               minlength=num_parts).astype(float)
    for _ in range(int(sweeps)):
        moved = 0
        for v in range(n):
            nb = np.concatenate(
                [indices[indptr[v]:indptr[v + 1]],
                 out_indices[out_indptr[v]:out_indptr[v + 1]]])
            if nb.size == 0:
                continue
            cur = int(assign[v])
            score = np.bincount(assign[nb], minlength=num_parts)
            ok = load_nodes < cap_nodes
            if labeled[v]:
                ok &= load_labeled < cap_labeled
            ok[cur] = False
            gain = np.where(ok, score - score[cur], -1)
            best = int(np.argmax(gain))
            if gain[best] > 0:
                assign[v] = best
                load_nodes[cur] -= 1
                load_nodes[best] += 1
                if labeled[v]:
                    load_labeled[cur] -= 1
                    load_labeled[best] += 1
                moved += 1
        if moved == 0:
            break
    return assign


class LabelPropPartitioner(Partitioner):
    """Pure-numpy clustering entry, no optional deps: seed with the LDG
    placement, then run ``refine_partition`` sweeps.  Because refinement
    only accepts strictly cut-reducing, cap-respecting moves, this
    entry's edge cut is <= LDG's on every graph — the fallback that
    carries the "clustering beats streaming placement" claim when METIS
    is unavailable.  ``"labelprop(K)"`` sets the sweep budget."""

    name = "labelprop"

    def __init__(self, sweeps: float = 10, *extra):
        if extra:
            raise ValueError(
                f"labelprop takes at most one parameter (sweeps), got "
                f"{(sweeps,) + extra}")
        sweeps = int(sweeps)
        if sweeps < 1:
            raise ValueError(f"labelprop sweeps must be >= 1, got {sweeps}")
        self.sweeps = sweeps

    def _assign(self, graph, num_parts, labeled, *, seed, slack,
                labeled_slack):
        base = partition_graph(graph, num_parts, labeled, seed=seed,
                               slack=slack, labeled_slack=labeled_slack)
        return refine_partition(graph, base, num_parts, labeled,
                                slack=slack, labeled_slack=labeled_slack,
                                sweeps=self.sweeps)


class MetisPartitioner(Partitioner):
    """The paper's partitioner, importable only when the optional
    ``pymetis`` package is installed (the CI optional-deps leg; this
    container's tests skip).  METIS balances nodes but knows nothing of
    the labeled target, so its result is cap-repaired and then passed
    through one ``refine_partition`` budget with both caps active."""

    name = "metis"

    def __init__(self):
        try:
            import pymetis
        except ImportError:
            raise ImportError(
                "partitioner 'metis' needs the optional dependency "
                "pymetis (pip install pymetis); use 'labelprop' for a "
                "pure-numpy clustering partitioner") from None
        self._pymetis = pymetis

    def _assign(self, graph, num_parts, labeled, *, seed, slack,
                labeled_slack):
        n = graph.num_nodes
        indices = np.asarray(graph.indices, np.int64)
        dsts = csr_view(graph).dsts.astype(np.int64)
        # METIS wants a symmetric, loop-free adjacency
        u = np.concatenate([dsts, indices])
        w = np.concatenate([indices, dsts])
        keep = u != w
        pairs = np.unique(np.stack([u[keep], w[keep]], axis=1), axis=0)
        xadj = np.zeros(n + 1, np.int64)
        np.cumsum(np.bincount(pairs[:, 0], minlength=n), out=xadj[1:])
        kwargs = {}
        options = getattr(self._pymetis, "Options", None)
        if options is not None:
            try:
                kwargs["options"] = options(seed=int(seed))
            except TypeError:       # older pymetis: unseedable, still
                pass                # deterministic for fixed inputs
        try:
            _, membership = self._pymetis.part_graph(
                num_parts, xadj=xadj, adjncy=pairs[:, 1], **kwargs)
        except TypeError:           # build without the options kwarg
            _, membership = self._pymetis.part_graph(
                num_parts, xadj=xadj, adjncy=pairs[:, 1])
        assign = _repair_caps(graph, np.asarray(membership, np.int32),
                              num_parts, labeled, slack, labeled_slack)
        return refine_partition(graph, assign, num_parts, labeled,
                                slack=slack, labeled_slack=labeled_slack,
                                sweeps=2)


def _repair_caps(graph: CSCGraph, assign: np.ndarray, num_parts: int,
                 labeled: np.ndarray, slack: float,
                 labeled_slack: float | None) -> np.ndarray:
    """Evict lowest-degree nodes from over-cap partitions into the
    least-loaded open ones until both balance targets hold (used on
    partitioners, like METIS, whose native balancing ignores our caps)."""
    if labeled_slack is None:
        labeled_slack = slack
    n = graph.num_nodes
    assign = np.asarray(assign, np.int32).copy()
    deg = np.asarray(graph.degrees())
    cap_nodes = slack * n / num_parts
    cap_labeled = max(1.0, labeled_slack * labeled.sum() / num_parts)
    load_nodes = np.bincount(assign, minlength=num_parts).astype(float)
    load_labeled = np.bincount(assign[labeled],
                               minlength=num_parts).astype(float)

    def evict(p: int, need_labeled: bool) -> None:
        members = np.flatnonzero(assign == p)
        if need_labeled:
            members = members[labeled[members]]
        members = members[np.argsort(deg[members], kind="stable")]
        for v in members:
            ok = load_nodes < cap_nodes
            if labeled[v]:
                ok &= load_labeled < cap_labeled
            ok[p] = False
            if not ok.any():
                break
            q = int(np.argmin(np.where(ok, load_nodes, np.inf)))
            assign[v] = q
            load_nodes[p] -= 1
            load_nodes[q] += 1
            if labeled[v]:
                load_labeled[p] -= 1
                load_labeled[q] += 1
            over = load_labeled[p] > cap_labeled if need_labeled \
                else load_nodes[p] > cap_nodes
            if not over:
                break

    for p in range(num_parts):
        if load_nodes[p] > cap_nodes:
            evict(p, need_labeled=False)
    for p in range(num_parts):
        if load_labeled[p] > cap_labeled:
            evict(p, need_labeled=True)
    return assign


_PARTITIONERS: dict[str, Callable[..., Partitioner]] = {}


def register_partitioner(name: str, factory: Callable[..., Partitioner],
                         *, overwrite: bool = False) -> None:
    """Register ``factory(*params) -> Partitioner`` under ``name``
    (``params`` are the floats of the inline form ``"name(p1,p2)"``)."""
    if not overwrite and name in _PARTITIONERS \
            and _PARTITIONERS[name] is not factory:
        raise ValueError(f"partitioner {name!r} already registered; "
                         f"pass overwrite=True to replace it")
    _PARTITIONERS[name] = factory


def available_partitioners() -> tuple[str, ...]:
    """Sorted names of registered partitioners.

    Examples
    --------
    >>> set(available_partitioners()) >= {"ldg", "labelprop", "random"}
    True
    """
    return tuple(sorted(_PARTITIONERS))


def resolve_partitioner(name: str) -> Partitioner:
    """Instantiate the partitioner registered under ``name``.

    ``name`` may carry inline float parameters (``"labelprop(4)"``),
    parsed by the shared ``repro.data.naming`` grammar.  Raises
    ``KeyError`` listing the available names when unknown;
    ``"metis"`` raises ``ImportError`` when ``pymetis`` is absent.
    """
    from repro.data.naming import parse_param_name
    base, params = parse_param_name(name, "partitioner")
    try:
        factory = _PARTITIONERS[base]
    except KeyError:
        raise KeyError(f"unknown partitioner {name!r}; "
                       f"available: {available_partitioners()}") from None
    return factory(*params)


def _no_params(cls):
    def factory(*params):
        if params:
            raise ValueError(f"partitioner {cls.name!r} takes no "
                             f"parameters, got {params}")
        return cls()
    return factory


register_partitioner("ldg", _no_params(LDGPartitioner))
register_partitioner("labelprop", lambda *p: LabelPropPartitioner(*p))
register_partitioner("metis", _no_params(MetisPartitioner))
register_partitioner("random", _no_params(HashPartitioner))
register_partitioner("hash", _no_params(HashPartitioner))


# --------------------------------------------------------------------------
# deployment plans
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PartitionLayout:
    """Relabeled graph + ownership metadata shared by both plans.

    ``local_parts`` marks a **rank-local** build (multi-process executor):
    only feature rows for partitions in ``range(*local_parts)`` are
    materialized — the other rows of ``features`` are zero and must never
    be read by this rank (the global mesh places each partition's row on
    its owning process).  ``labels`` / ``node_valid`` stay full on every
    rank: the host seed draw's table (``labeled_ids``) lists every
    worker's labeled nodes.
    """
    graph: CSCGraph              # relabeled global topology
    offsets: jnp.ndarray         # (P+1,) ownership ranges
    perm: np.ndarray             # new id -> old id
    features: jnp.ndarray        # (P, n_max, D) per-owner feature shards
    labels: jnp.ndarray          # (P, n_max) int32, -1 where unlabeled/pad
    node_valid: jnp.ndarray      # (P, n_max) bool
    num_parts: int
    local_parts: tuple[int, int] | None = None   # rank-local [lo, hi)

    @property
    def n_max(self) -> int:
        return self.features.shape[1]

    def owner_of(self, ids: jnp.ndarray) -> jnp.ndarray:
        return (jnp.searchsorted(self.offsets, ids, side="right") - 1
                ).astype(jnp.int32)


@dataclasses.dataclass(frozen=True)
class VanillaPlan:
    """Paper baseline: each worker stores only its partition's in-edges.

    Legacy container — the registry equivalent is
    ``repro.core.placement.resolve_scheme("vanilla").build(layout)``.
    """
    layout: PartitionLayout
    local_indptr: jnp.ndarray    # (P, n_max+1)
    local_indices: jnp.ndarray   # (P, nnz_max) global src ids, -1 pad


@dataclasses.dataclass(frozen=True)
class HybridPlan:
    """The contribution: topology replicated, features partitioned.

    Legacy container — the registry equivalent is
    ``repro.core.placement.resolve_scheme("hybrid").build(layout)``.
    """
    layout: PartitionLayout


def build_layout(graph: CSCGraph, features: np.ndarray, labels: np.ndarray,
                 assign: np.ndarray, num_parts: int,
                 local_parts: tuple[int, int] | None = None
                 ) -> PartitionLayout:
    """Relabel so each partition owns a contiguous id range; shard features.

    ``local_parts=(lo, hi)`` builds a **rank-local** layout for the
    multi-process executor: only partitions in ``[lo, hi)`` get their
    feature rows filled (the rest of the ``(P, n_max, D)`` table stays
    zero — ``np.zeros`` is calloc-backed, so untouched remote pages are
    never committed to physical memory).  Topology, offsets, labels, and
    ``node_valid`` remain full: they are small relative to features and
    every rank needs them (sampling walks the global topology; the host
    seed draw's table, ``labeled_ids``, lists every worker's labeled
    nodes).
    """
    n = graph.num_nodes
    assign = np.asarray(assign)
    perm_new_to_old = np.argsort(assign, kind="stable")
    old_to_new = np.empty(n, np.int64)
    old_to_new[perm_new_to_old] = np.arange(n)

    counts = np.bincount(assign, minlength=num_parts)
    offsets = np.zeros(num_parts + 1, np.int64)
    np.cumsum(counts, out=offsets[1:])
    n_max = int(counts.max())

    # relabel edges
    indices = np.asarray(graph.indices)
    dsts_old = csr_view(graph).dsts
    new_dst = old_to_new[dsts_old].astype(np.int64)
    new_src = old_to_new[indices].astype(np.int64)
    new_graph = csc_from_numpy_edges(new_dst, new_src, n)

    if local_parts is not None:
        lo, hi = int(local_parts[0]), int(local_parts[1])
        if not (0 <= lo < hi <= num_parts):
            raise ValueError(
                f"local_parts {local_parts!r} out of range for "
                f"num_parts={num_parts}")
        local_parts = (lo, hi)
        feature_parts = range(lo, hi)
    else:
        feature_parts = range(num_parts)

    D = features.shape[1]
    feat = np.zeros((num_parts, n_max, D), features.dtype)
    lab = np.full((num_parts, n_max), -1, np.int32)
    valid = np.zeros((num_parts, n_max), bool)
    for p in range(num_parts):
        ids_old = perm_new_to_old[offsets[p]:offsets[p + 1]]
        k = ids_old.size
        if p in feature_parts:
            feat[p, :k] = features[ids_old]
        lab[p, :k] = labels[ids_old]
        valid[p, :k] = True

    return PartitionLayout(
        graph=new_graph,
        offsets=jnp.asarray(offsets, jnp.int32),
        perm=perm_new_to_old,
        features=jnp.asarray(feat),
        labels=jnp.asarray(lab),
        node_valid=jnp.asarray(valid),
        num_parts=num_parts,
        local_parts=local_parts,
    )


def build_vanilla(layout: PartitionLayout) -> VanillaPlan:
    """Slice each partition's in-edge lists out of the global CSC."""
    indptr = np.asarray(layout.graph.indptr)
    indices = np.asarray(layout.graph.indices)
    offsets = np.asarray(layout.offsets)
    P = layout.num_parts
    n_max = layout.n_max

    nnz = [int(indptr[offsets[p + 1]] - indptr[offsets[p]]) for p in range(P)]
    nnz_max = max(max(nnz), 1)
    li = np.zeros((P, n_max + 1), np.int32)
    lx = np.full((P, nnz_max), -1, np.int32)
    for p in range(P):
        lo, hi = offsets[p], offsets[p + 1]
        rows = indptr[lo:hi + 1] - indptr[lo]
        li[p, :rows.size] = rows
        li[p, rows.size:] = rows[-1]
        lx[p, :nnz[p]] = indices[indptr[lo]:indptr[hi]]
    return VanillaPlan(layout=layout,
                       local_indptr=jnp.asarray(li),
                       local_indices=jnp.asarray(lx))


def build_hybrid(layout: PartitionLayout) -> HybridPlan:
    return HybridPlan(layout=layout)


def labeled_ids(layout: PartitionLayout) -> tuple[np.ndarray, ...]:
    """The seed draw's table: per worker, the int64 global ids of its
    labeled nodes in ascending (column) order.  Built once per layout
    (``Pipeline`` builds it with the pipeline) and read by every
    ``seeds_per_worker_host`` call."""
    offsets = np.asarray(layout.offsets).astype(np.int64)
    labels = np.asarray(layout.labels)
    return tuple(offsets[p] + np.flatnonzero(labels[p] >= 0)
                 for p in range(layout.num_parts))


def seeds_per_worker_host(layout: PartitionLayout, batch: int,
                          epoch_salt: int,
                          labeled: tuple[np.ndarray, ...] | None = None
                          ) -> np.ndarray:
    """Pure-numpy host half of ``seeds_per_worker``: each worker's
    ``batch`` labeled nodes of lowest hash rank, as a host ``(P, batch)``
    int32 array, -1 padded.

    Only the labeled ids of ``labeled`` (``labeled_ids(layout)``, built
    here when not given) are hashed: ``np.argpartition`` keeps the lowest
    ``min(batch, count)`` keys and only those are sorted.  ``mix64`` is a
    bijection, so distinct ids never tie and key order alone is the order
    by (key, column).  No unlabeled node takes part, so a labeled node
    whose key is ``2**64 - 1`` ranks like any other (a draw that masked
    the unlabeled nodes to that key would tie them with it).

    This function touches no JAX state (no tracing, no device transfer),
    so the seed stager (``repro.pipeline.staging``) can run it on a
    background thread while the main thread traces or blocks on device
    work; ``seeds_per_worker`` is its device-array wrapper.
    """
    if labeled is None:
        labeled = labeled_ids(layout)
    # fold the salt in Python-int space (arbitrary precision, then wrap)
    salt64 = np.uint64((int(epoch_salt) * 0x9E3779B97F4A7C15) % (2 ** 64))
    out = np.full((layout.num_parts, batch), -1, np.int32)
    for p, ids in enumerate(labeled):
        m = min(batch, ids.size)
        if m == 0:
            continue
        key = mix64(ids.astype(np.uint64) + salt64)
        lowest = np.argpartition(key, m - 1)[:m]
        out[p, :m] = ids[lowest[np.argsort(key[lowest])]]
    return out


def seeds_per_worker(layout: PartitionLayout, batch: int, epoch_salt: int,
                     labeled: tuple[np.ndarray, ...] | None = None
                     ) -> jnp.ndarray:
    """Each worker draws its minibatch from ITS OWN labeled nodes (paper §4:
    'top level sampling seeds are drawn from the labeled nodes' of the local
    partition).  Deterministic given epoch_salt.  Returns (P, batch) global
    ids, -1 padded.

    Each labeled node gets a hash rank from (global id, epoch_salt) and
    every worker takes its ``batch`` lowest-ranked labeled nodes in rank
    order, without replacement.  The host draw itself lives in
    ``seeds_per_worker_host``; ``labeled`` is its prebuilt table.
    """
    return jnp.asarray(seeds_per_worker_host(layout, batch, epoch_salt,
                                             labeled))
