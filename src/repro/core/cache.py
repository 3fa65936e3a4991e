"""Remote-feature caching — the paper's §5 future-work item, implemented.

"we can combine our hybrid partitioning scheme with feature caching to
 cache frequently accessed remote node features in order to reduce
 communication volume"

Two registries live here.  The ``HotSetScorer`` registry
(``register_hot_scorer`` / ``resolve_hot_scorer``: "degree", "frequency",
"blend(w)") is THE shared "who's hot" ranking — the cache policies below,
``hybrid_partial``'s replication ranking, the ``pinned_hot`` pin set, the
serving recycler's admission filter, and the hot-set traffic generator
all resolve through it, with ``rank_by_score`` as the single tie-break.

Cache *construction* is a registry of ``CachePolicy`` entries (mirroring
``repro.core.placement`` / ``repro.core.sampler``), selected by
``PlanSpec(cache_policy=...)``:

  * ``"degree"``     — static top-K by in-degree: under uniform neighbor
                       sampling a node's access frequency is proportional
                       to its in-degree, so each worker caches the hottest
                       remote nodes it does NOT own.
  * ``"frequency"``  — top-K by *observed* access frequency: replays a
                       short trace of the actual deterministic sampler
                       hash stream (the same seeds/salts training will
                       draw) and caches the remote nodes each worker
                       actually fetched most often.

During the feature-fetch rounds, cache hits are served locally and only
misses ride the all_to_all — for ANY policy and ANY placement scheme.

Static shapes throughout: the cache is (K, D) with a sorted id vector, hits
resolved by searchsorted.  Communication volume accounting distinguishes
buffer capacity (static) from *utilized* bytes (valid rows), which is what
the fabric actually moves under sparsity-aware collectives; the benchmark
reports both plus the hit rate.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import dist
# cache-aware fetch now lives in dist (first-class stage of the feature
# fetch); re-exported here for backward compatibility
from repro.core.dist import fetch_features_cached  # noqa: F401
from repro.core.partition import PartitionLayout


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class FeatureCache:
    """Per-worker cache of hot remote features (stacked on worker axis)."""
    ids: jnp.ndarray      # (K,) sorted global ids, -1 padded at the END
    rows: jnp.ndarray     # (K, D)

    def tree_flatten(self):
        return (self.ids, self.rows), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def capacity(self) -> int:
        return self.ids.shape[0]


def _assemble_cache(layout: PartitionLayout, capacity: int,
                    per_worker_ids) -> FeatureCache:
    """Stack per-worker remote-id picks into a ``FeatureCache``.

    ``per_worker_ids[p]`` is a (<= capacity,) int array of *remote* global
    node ids worker p caches; rows are gathered from the owning worker's
    feature shard.  Ids are sorted ascending per worker with the -1 padding
    replaced by a large sentinel so lookup stays one searchsorted.
    """
    offsets = np.asarray(layout.offsets)
    feats = np.asarray(layout.features)
    P = layout.num_parts
    D = feats.shape[2]

    ids_out = np.full((P, capacity), -1, np.int32)
    rows_out = np.zeros((P, capacity, D), feats.dtype)
    for p in range(P):
        remote = np.sort(np.asarray(per_worker_ids[p])[:capacity])
        k = remote.size
        ids_out[p, :k] = remote
        own = np.searchsorted(offsets, remote, side="right") - 1
        rows_out[p, :k] = feats[own, remote - offsets[own]]
    # keep -1 padding AFTER valid ids for searchsorted: replace -1 with a
    # sentinel larger than any id
    sentinel = np.int32(2 ** 31 - 1)
    ids_sorted = np.where(ids_out < 0, sentinel, ids_out)
    return FeatureCache(ids=jnp.asarray(ids_sorted),
                        rows=jnp.asarray(rows_out))


# --------------------------------------------------------------------------
# hot-set scorer registry — THE shared "who's hot" ranking
# --------------------------------------------------------------------------
# Every consumer of a hot set resolves through here: the ``"degree"`` /
# ``"frequency"`` cache policies below, ``hybrid_partial``'s replication
# ranking (``repro.core.placement``), the ``pinned_hot`` store's pin set
# (the cache IS the pin set), the serving recycler's admission filter
# (``repro.serve.recycler``), and the hot-set-skewed traffic generator
# (``repro.serve.traffic``).  One ranking definition means "hot" can never
# drift between the training and serving sides.

def rank_by_score(scores, k: int | None = None) -> np.ndarray:
    """Node ids ranked hottest-first: score desc, ties broken by id asc.

    The single tie-break rule every scorer shares (``lexsort`` over
    ``(ids, -scores)``), bit-identical to the stable ``argsort(-deg)``
    the pre-registry call sites used.  Returns the top ``k`` ids (all
    nodes if ``k`` is None).
    """
    scores = np.asarray(scores)
    ids = np.arange(scores.shape[0])
    ranked = ids[np.lexsort((ids, -scores))].astype(np.int32)
    return ranked if k is None else ranked[:k]


class HotSetScorer:
    """Base class of registry entries: maps a graph to per-node hotness
    scores; ``top_ids`` applies the shared ``rank_by_score`` tie-break.

    ``observe`` folds an access batch into dynamic scorers (frequency /
    blend) and is a no-op for static ones, so serving loops can feed any
    scorer uniformly."""

    name: str = "?"

    def scores(self, graph) -> np.ndarray:
        """(num_nodes,) hotness scores, higher = hotter."""
        raise NotImplementedError

    def top_ids(self, graph, k: int | None = None) -> np.ndarray:
        """Top-``k`` hottest node ids (all nodes if ``k`` is None)."""
        return rank_by_score(self.scores(graph), k)

    def observe(self, ids) -> None:
        """Fold an access batch into the scorer (no-op when static)."""


class DegreeScorer(HotSetScorer):
    """Static: hotness = in-degree (under uniform neighbor sampling a
    node's access frequency is proportional to its in-degree)."""

    name = "degree"

    def scores(self, graph) -> np.ndarray:
        return np.asarray(graph.degrees())


class FrequencyScorer(HotSetScorer):
    """Dynamic: hotness = the ``FrequencyTracker``'s decayed observed
    access counts.  The tracker is created lazily on the first
    ``scores(graph)`` call (or pass one in to share it with a serving
    loop); with zero observations every score is 0 and ``top_ids`` falls
    back to plain id order."""

    name = "frequency"

    def __init__(self, tracker: "FrequencyTracker | None" = None, *,
                 decay: float = 1.0):
        self.tracker = tracker
        self._decay = float(decay)

    def _ensure(self, num_nodes: int) -> "FrequencyTracker":
        if self.tracker is None:
            self.tracker = FrequencyTracker(num_nodes, decay=self._decay)
        if self.tracker.num_nodes != num_nodes:
            raise ValueError(
                f"frequency scorer's tracker covers "
                f"{self.tracker.num_nodes} nodes, graph has {num_nodes}")
        return self.tracker

    def observe(self, ids) -> None:
        if self.tracker is None:
            raise ValueError(
                "frequency scorer has no tracker yet: call scores()/"
                "top_ids() once, or construct with FrequencyScorer("
                "FrequencyTracker(num_nodes))")
        self.tracker.observe(ids)

    def scores(self, graph) -> np.ndarray:
        return self._ensure(graph.num_nodes).counts


class BlendScorer(HotSetScorer):
    """Composable: ``w * degree + (1 - w) * frequency``, each normalized
    to [0, 1] by its max.  With no observations yet the frequency term is
    zero, so ``blend(w)`` for any ``w > 0`` starts at the degree ranking
    and drifts toward the observed distribution as accesses arrive."""

    name = "blend"

    def __init__(self, weight: float = 0.5, *extra,
                 tracker: "FrequencyTracker | None" = None,
                 decay: float = 1.0):
        if extra:
            raise ValueError(f"blend takes at most one parameter "
                             f"(the degree weight), got {(weight,) + extra}")
        weight = float(weight)
        if not 0.0 <= weight <= 1.0:
            raise ValueError(f"blend weight must be in [0, 1], got {weight}")
        self.weight = weight
        self.degree = DegreeScorer()
        self.frequency = FrequencyScorer(tracker, decay=decay)

    def observe(self, ids) -> None:
        self.frequency.observe(ids)

    def scores(self, graph) -> np.ndarray:
        d = self.degree.scores(graph).astype(np.float64)
        f = np.asarray(self.frequency.scores(graph), np.float64)
        if d.size and d.max() > 0:
            d = d / d.max()
        if f.size and f.max() > 0:
            f = f / f.max()
        return self.weight * d + (1.0 - self.weight) * f


_HOT_SCORERS: dict[str, Callable[..., HotSetScorer]] = {}


def register_hot_scorer(name: str, factory: Callable[..., HotSetScorer],
                        *, overwrite: bool = False) -> None:
    """Register ``factory(*params) -> HotSetScorer`` under ``name``
    (``params`` are the floats of the inline form ``"blend(0.7)"``)."""
    if not overwrite and name in _HOT_SCORERS \
            and _HOT_SCORERS[name] is not factory:
        raise ValueError(f"hot-set scorer {name!r} already registered; "
                         f"pass overwrite=True to replace it")
    _HOT_SCORERS[name] = factory


def available_hot_scorers() -> tuple[str, ...]:
    """Sorted names of registered hot-set scorers.

    Examples
    --------
    >>> set(available_hot_scorers()) >= {"degree", "frequency", "blend"}
    True
    """
    return tuple(sorted(_HOT_SCORERS))


def resolve_hot_scorer(name: str) -> HotSetScorer:
    """Instantiate the scorer registered under ``name`` (inline float
    parameters parse via the shared ``repro.data.naming`` grammar, e.g.
    ``"blend(0.7)"`` or ``"frequency(0.9)"`` for a decay).  Raises
    ``KeyError`` listing the available names when unknown."""
    from repro.data.naming import parse_param_name
    base, params = parse_param_name(name, "hot-set scorer")
    try:
        factory = _HOT_SCORERS[base]
    except KeyError:
        raise KeyError(f"unknown hot-set scorer {name!r}; "
                       f"available: {available_hot_scorers()}") from None
    return factory(*params)


def _degree_factory(*params):
    if params:
        raise ValueError(f"scorer 'degree' takes no parameters, "
                         f"got {params}")
    return DegreeScorer()


def _frequency_factory(*params):
    if len(params) > 1:
        raise ValueError(f"scorer 'frequency' takes at most one parameter "
                         f"(the decay), got {params}")
    return FrequencyScorer(decay=params[0] if params else 1.0)


register_hot_scorer("degree", _degree_factory)
register_hot_scorer("frequency", _frequency_factory)
register_hot_scorer("blend", lambda *p: BlendScorer(*p))


def degree_hot_ids(graph, k: int | None = None) -> np.ndarray:
    """Deprecated alias of the ``"degree"`` hot-set scorer — prefer
    ``resolve_hot_scorer("degree").top_ids(graph, k)`` (bit-identical
    ranking; same tie-break via ``rank_by_score``)."""
    warnings.warn(
        "repro.core.cache.degree_hot_ids is deprecated; use "
        "resolve_hot_scorer('degree').top_ids(graph, k) from the hot-set "
        "scorer registry",
        DeprecationWarning, stacklevel=2)
    return resolve_hot_scorer("degree").top_ids(graph, k)


class FrequencyTracker:
    """Online exponentially-decayed access counts over node ids.

    The dynamic counterpart of ``degree_hot_ids``: observe id batches as
    they arrive (serving requests, sampled sources, ...) and ask for the
    current hot set.  Counts decay by ``decay`` per ``observe`` call, so
    the hot set follows the recent access distribution instead of the
    all-time one — which is what the serving recycler needs to decide
    which seeds are worth keeping recycled entries for.

    Host-side numpy, O(num_nodes) memory; not a jit-traced object.
    """

    def __init__(self, num_nodes: int, *, decay: float = 1.0):
        if not 0.0 < decay <= 1.0:
            raise ValueError(f"decay must be in (0, 1], got {decay}")
        self.num_nodes = int(num_nodes)
        self.decay = float(decay)
        self.counts = np.zeros(self.num_nodes, np.float64)
        self.total_observed = 0

    def observe(self, ids) -> None:
        """Fold one batch of node ids into the decayed counts."""
        ids = np.asarray(ids).ravel()
        ids = ids[(ids >= 0) & (ids < self.num_nodes)]
        if self.decay < 1.0:
            self.counts *= self.decay
        np.add.at(self.counts, ids, 1.0)
        self.total_observed += ids.size

    def topk(self, k: int) -> np.ndarray:
        """Top-``k`` ids by decayed count desc, ties by id asc (the
        shared ``rank_by_score`` tie-break)."""
        return rank_by_score(self.counts, k)

    def is_hot(self, ids, k: int) -> np.ndarray:
        """Boolean mask: is each id currently in the top-``k`` set?"""
        hot = set(self.topk(k).tolist())
        return np.asarray([int(i) in hot for i in np.asarray(ids).ravel()])


def degree_caches(layout: PartitionLayout, capacity: int,
                  **_ignored) -> FeatureCache:
    """Host-side: per worker, cache the top-`capacity` highest-in-degree
    nodes owned by OTHER workers.  Returns stacked (P, K) / (P, K, D).

    Prefer ``repro.pipeline.PlanSpec(cache_capacity=K)`` — ``Pipeline.build``
    then constructs the cache and threads it through the feature fetch.
    """
    offsets = np.asarray(layout.offsets)
    P = layout.num_parts

    all_ids = resolve_hot_scorer("degree").top_ids(layout.graph)
    # loop-invariant: ownership of the degree-ranked ids
    owner = np.searchsorted(offsets, all_ids, side="right") - 1
    picks = [all_ids[owner != p][:capacity] for p in range(P)]
    return _assemble_cache(layout, capacity, picks)


def frequency_caches(layout: PartitionLayout, capacity: int, *,
                     fanouts, trace_steps: int = 4, trace_batch: int = 64,
                     seed: int = 0, **_ignored) -> FeatureCache:
    """Access-traced policy: replay ``trace_steps`` steps of the actual
    deterministic sampler hash stream (the same ``seeds_per_worker`` draws
    + per-step salts training uses) and cache, per worker, the remote
    nodes whose features it fetched most often.

    Because the sampler is a stateless hash of (node id, salt, slot), this
    short trace is an exact prefix of the access stream a ``"counter"``
    seed-stream training run with ``base_salt=seed`` would produce — not a
    proxy distribution.
    """
    from repro.core.partition import labeled_ids, seeds_per_worker
    from repro.core.sampler import sample_mfgs

    if fanouts is None:
        raise ValueError("frequency cache policy needs the sampler fanouts "
                         "(pass fanouts=... or use the pipeline API)")
    graph = layout.graph
    offsets = np.asarray(layout.offsets)
    P = layout.num_parts
    n = graph.num_nodes

    labeled = labeled_ids(layout)
    counts = np.zeros((P, n), np.int64)
    for s in range(trace_steps):
        salt = (seed + s) % (2 ** 32)
        seeds = np.asarray(seeds_per_worker(layout, trace_batch,
                                            epoch_salt=salt,
                                            labeled=labeled))
        for p in range(P):
            mfgs = sample_mfgs(graph, jnp.asarray(seeds[p]), fanouts,
                               jnp.uint32(salt))
            src = np.asarray(mfgs[-1].src_nodes)
            src = src[src >= 0]
            np.add.at(counts[p], src, 1)

    owner = np.searchsorted(offsets, np.arange(n), side="right") - 1
    picks = []
    for p in range(P):
        c = counts[p].copy()
        c[owner == p] = 0                      # local rows are free anyway
        # shared rank_by_score tie-break, restricted to accessed nodes
        ranked = rank_by_score(c)
        ranked = ranked[c[ranked] > 0]
        picks.append(ranked[:capacity])
    return _assemble_cache(layout, capacity, picks)


# --------------------------------------------------------------------------
# cache-policy registry
# --------------------------------------------------------------------------
# A *cache policy* is any ``policy(layout, capacity, *, fanouts=None, ...)
# -> FeatureCache``.  Registering by name lets ``PlanSpec(cache_policy=...)``
# select construction declaratively, and third-party policies plug in
# without touching the fetch path (which is policy-agnostic).

_CACHE_POLICIES: dict[str, Callable] = {}


def register_cache_policy(name: str, policy: Callable, *,
                          overwrite: bool = False) -> None:
    """Register ``policy(layout, capacity, *, fanouts=None, ...)`` under
    ``name`` (see ``resolve_cache_policy``)."""
    if not overwrite and name in _CACHE_POLICIES \
            and _CACHE_POLICIES[name] is not policy:
        raise ValueError(f"cache policy {name!r} already registered; "
                         f"pass overwrite=True to replace it")
    _CACHE_POLICIES[name] = policy


def available_cache_policies() -> tuple[str, ...]:
    """Sorted names of registered cache policies.

    Examples
    --------
    >>> set(available_cache_policies()) >= {"degree", "frequency"}
    True
    """
    return tuple(sorted(_CACHE_POLICIES))


def resolve_cache_policy(name: str) -> Callable:
    """Look up a cache policy by registry name (KeyError lists names)."""
    try:
        return _CACHE_POLICIES[name]
    except KeyError:
        raise KeyError(f"unknown cache policy {name!r}; "
                       f"available: {available_cache_policies()}") from None


register_cache_policy("degree", degree_caches)
register_cache_policy("frequency", frequency_caches)


def build_degree_caches(layout: PartitionLayout, capacity: int
                        ) -> FeatureCache:
    """Deprecated alias of ``degree_caches`` — prefer the pipeline API
    (``repro.pipeline.PlanSpec(cache_capacity=...)``)."""
    warnings.warn(
        "repro.core.cache.build_degree_caches is deprecated; use "
        "repro.pipeline.PlanSpec(cache_capacity=...) with Pipeline.build, "
        "or repro.core.cache.degree_caches",
        DeprecationWarning, stacklevel=2)
    return degree_caches(layout, capacity)


def make_cached_worker_step(*, graph_replicated, offsets, num_parts,
                            fanouts, loss_fn, level_fn=None,
                            counter: dist.RoundCounter | None = None):
    """Hybrid-scheme worker step with the feature cache in the fetch path.

    step(params, shard, seeds, salt, cache) — cache is the per-worker slice
    (use ``run_stacked_cached`` for the vmap simulation).
    """
    from repro.core.sampler import sample_level
    level_fn = level_fn or sample_level

    def step(params, shard: dist.WorkerShard, seeds, salt,
             cache: FeatureCache):
        mfgs = dist.hybrid_sample(graph_replicated, seeds, fanouts, salt,
                                  level_fn=level_fn)
        me = lax.axis_index(dist.AXIS)
        h_src, hits = fetch_features_cached(
            mfgs[-1].src_nodes, offsets, num_parts, shard.features,
            cache, counter)

        local_seed = jnp.clip(seeds - offsets[me], 0,
                              shard.labels.shape[0] - 1)
        seed_labels = shard.labels[local_seed]
        seed_valid = seeds >= 0

        def objective(p):
            return loss_fn(p, mfgs, h_src, seed_labels, seed_valid)

        loss, grads = jax.value_and_grad(objective)(params)
        # ordered reductions so this legacy step stays bit-aligned with the
        # pipeline path (test_extensions compares them array-equal)
        grads = dist.pmean_ordered(grads)
        loss = dist.pmean_ordered(loss)
        hit_rate = hits / jnp.maximum(jnp.sum(mfgs[-1].src_nodes >= 0), 1)
        return loss, grads, hit_rate

    return step


def run_stacked_cached(step, params, shards, seeds, salt,
                       cache: FeatureCache):
    """vmap simulation with per-worker cache slices (cf. dist.run_stacked)."""
    vstep = jax.vmap(step, in_axes=(None, shards.axes(0, None), 0, None, 0),
                     axis_name=dist.AXIS)
    loss, grads, hit_rate = vstep(params, shards, seeds, salt, cache)
    return loss[0], jax.tree.map(lambda g: g[0], grads), jnp.mean(hit_rate)
