"""Pluggable ``FeatureStore`` — how a worker obtains its frontier's rows.

FastSample's accounting (and this repo's benchmarks) show the feature
rounds are the largest remaining stream in every step: ``fetch_features``
ships (N, D) rows through two ``all_to_all`` rounds per step.  This
module makes *how those rows are served* a registry axis on ``PlanSpec``
— exactly like placement schemes, cache policies, sampler backends, and
executors — so serving strategies land as entries, not forks of
``dist.fetch_features``:

  ``"exchange"``    the paper's two-round ``all_to_all`` path
                    (``dist.fetch_features`` / ``fetch_features_cached``)
                    — bit-identical to the historical behavior, the
                    default.
  ``"pinned_hot"``  the ``CachePolicy``'s hot rows stay pinned in device
                    memory across steps (the same ``degree``/``frequency``
                    hot-set machinery builds them — cache policy and
                    store share one "who's hot" abstraction); hits are
                    served by the double-buffered Pallas row gather
                    (``repro.kernels.gather``) and *never ride the
                    all_to_all*.  Requires ``cache_capacity > 0``.
  ``"staged"``      cold rows stream in asynchronously ahead of the
                    consume half: a ``FeatureStager`` ring
                    (``repro.pipeline.staging``) replays the
                    deterministic sampler on the host, pre-gathers the
                    frontier's rows, and starts their H2D transfer so
                    the device program performs **no feature exchange at
                    all** (feature rounds: 0).  Composes with a pinned
                    cache (hot rows from device memory, cold rows from
                    the staged buffer) and requires prefetch depth >= 1.

Every store returns rows bit-identical to ``dist.fetch_features`` —
asserted across {vanilla, hybrid, hybrid_partial} x {vmap, shard_map,
multiprocess} in ``tests/test_feature_store.py``.  This interface is
also the seam a future disaggregated/remote feature server plugs into
(a store whose ``fetch`` issues RPCs instead of collectives).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from repro.core import dist


class FeatureStore:
    """How a worker serves its sampled frontier's feature rows.

    Subclasses implement ``fetch`` — called inside the traced per-worker
    program (under the named axis ``dist.AXIS``) from the *prepare* half
    of the step.  Three contract flags drive the plumbing:

    ``needs_cache``     the store serves hits from the pinned device
                        cache, so ``PlanSpec.cache_capacity > 0`` is
                        required (validated at spec construction).
    ``external_rows``   ``fetch`` consumes a ``staged_rows`` array
                        produced *outside* the traced program (the
                        ``FeatureStager`` ring); executors then thread
                        one extra ``(src_capacity, D)`` per-worker input
                        through the prefetch binding, and the store is
                        only reachable at prefetch depth >= 1.
    ``uses_exchange``   the fetch rides the feature ``all_to_all`` (so
                        utilized-byte accounting attributes miss traffic
                        to it; False means feature rounds are 0).
    """

    name: str = "?"
    needs_cache: bool = False
    external_rows: bool = False
    uses_exchange: bool = True

    def fetch(self, src_nodes: jnp.ndarray, shard, cache, *,
              offsets: jnp.ndarray, num_parts: int,
              counter=None, staged_rows=None):
        """Serve ``src_nodes``'s rows -> ``(h (N, D), hit_count ())``.

        ``src_nodes`` is the last level's frontier (global ids, -1
        padded); ``cache`` is the stacked per-worker ``FeatureCache`` or
        ``None``; ``staged_rows`` is only non-None for
        ``external_rows`` stores.
        """
        raise NotImplementedError

    def utilized_bytes(self, src_nodes, hits, row_bytes):
        """Utilized feature-exchange volume for the step's accounting:
        ids out + rows back for every valid frontier slot that was not
        served locally (stores that bypass the exchange report 0)."""
        if not self.uses_exchange:
            return jnp.zeros((), jnp.float32)
        misses = (jnp.sum((src_nodes >= 0).astype(jnp.float32))
                  - hits.astype(jnp.float32))
        return misses * row_bytes


def _cache_lookup(cache, src_nodes):
    """Shared hot-set probe: one searchsorted over the cache's sorted id
    table -> ``(is_hit (N,), pos_c (N,))``."""
    K = cache.capacity
    pos = jnp.searchsorted(cache.ids, src_nodes)
    pos_c = jnp.clip(pos, 0, K - 1)
    is_hit = (cache.ids[pos_c] == src_nodes) & (src_nodes >= 0)
    return is_hit, pos_c


class ExchangeStore(FeatureStore):
    """The paper's two-round ``all_to_all`` fetch — the default store.

    Exactly ``dist.fetch_features`` (or ``fetch_features_cached`` when a
    cache is attached): bit-identical to the pre-store behavior by
    construction.
    """

    name = "exchange"

    def fetch(self, src_nodes, shard, cache, *, offsets, num_parts,
              counter=None, staged_rows=None):
        if cache is not None:
            return dist.fetch_features_cached(
                src_nodes, offsets, num_parts, shard.features, cache,
                counter)
        h = dist.fetch_features(src_nodes, offsets, num_parts,
                                shard.features, counter)
        return h, jnp.zeros((), jnp.int32)


class PinnedHotStore(FeatureStore):
    """Hot rows pinned in device memory, served by the Pallas gather.

    The ``CachePolicy``-built ``FeatureCache`` (already device-resident
    and threaded through every executor) *is* the pinned store state —
    cache policy and feature store share the one "who's hot"
    abstraction.  Hits gather straight from the pinned (K, D) table via
    ``repro.kernels.gather`` (double-buffered row DMAs on TPU); only
    misses ride the two exchange rounds.  Rows are bit-identical to
    ``fetch_features_cached`` (the gather is bit-identical to its
    ``jnp.take`` oracle).

    ``gather`` selects the hit-row path: ``"kernel"`` always uses the
    Pallas kernel, ``"jnp"`` the oracle, ``"auto"`` (default) the kernel
    on an accelerator and the oracle on the CPU backend (interpret-mode
    Pallas is correct but slow; the kernel itself is covered by tier-1
    interpret tests).
    """

    name = "pinned_hot"
    needs_cache = True

    def __init__(self, gather: str = "auto"):
        if gather not in ("auto", "kernel", "jnp"):
            raise ValueError(f"gather must be auto|kernel|jnp, "
                             f"got {gather!r}")
        self.gather = gather

    def _gather_hits(self, rows, hit_pos):
        from repro.kernels.gather import gather_rows, gather_rows_reference
        if self.gather == "jnp":
            return gather_rows_reference(rows, hit_pos)
        if self.gather == "kernel":
            return gather_rows(rows, hit_pos)
        if jax.default_backend() == "cpu":
            return gather_rows_reference(rows, hit_pos)
        return gather_rows(rows, hit_pos)

    def fetch(self, src_nodes, shard, cache, *, offsets, num_parts,
              counter=None, staged_rows=None):
        if cache is None:
            raise ValueError(
                "pinned_hot feature store needs a built cache "
                "(PlanSpec.cache_capacity > 0)")
        is_hit, pos_c = _cache_lookup(cache, src_nodes)
        hit_pos = jnp.where(is_hit, pos_c, -1)
        hit_rows = self._gather_hits(cache.rows, hit_pos)
        miss_ids = jnp.where(is_hit, -1, src_nodes)
        h_miss = dist.fetch_features(miss_ids, offsets, num_parts,
                                     shard.features, counter)
        h = jnp.where(is_hit[:, None], hit_rows.astype(h_miss.dtype),
                      h_miss)
        return h, jnp.sum(is_hit)


class StagedStore(FeatureStore):
    """Cold rows pre-gathered on the host and staged ahead of the step.

    The device program never runs a feature exchange: a ``FeatureStager``
    (``repro.pipeline.staging``) replays the deterministic sampler for
    step *k* on the host (same ``(seeds, salt)`` -> bit-identical
    frontier, paper §4.2), gathers the frontier's rows from the full
    feature table with one numpy fancy-index, and starts their H2D
    transfer ``lead`` steps early.  ``fetch`` then just consumes the
    already-resident ``staged_rows`` — with a pinned cache attached and
    the ``"device"`` combine, hot rows come from device memory via the
    Pallas gather and only the *cold* remainder rides the staged H2D
    stream (the stager zeroes hot slots); the ``"host"`` combine stages
    hot rows too and keeps only the hit accounting (bit-identical
    either way — see ``hot_rows_from_cache`` for when each wins).
    Feature rounds per step: 0.

    Requires prefetch depth >= 1 (the ring rides ahead of the consume
    half) and a full feature layout (``local_parts=None``) — both
    validated at spec/build time.
    """

    name = "staged"
    external_rows = True
    uses_exchange = False

    def __init__(self, gather: str = "auto", combine: str = "auto"):
        if combine not in ("auto", "device", "host"):
            raise ValueError(f"combine must be auto|device|host, "
                             f"got {combine!r}")
        self._pinned = PinnedHotStore(gather=gather)
        self.combine = combine

    @property
    def hot_rows_from_cache(self) -> bool:
        """Whether cache hits are served by the device-side pinned
        gather (``True``) or staged with the cold rows (``False``).

        The pinned rows are copies of the same feature table, so both
        paths produce bit-identical values — the choice is pure
        dataflow.  Serving hits from device memory pays off when it cuts
        real H2D bytes (accelerators); on hosts where the staging
        transfer is already zero-copy (CPU dlpack) it buys nothing and
        costs an (N, D) hit/miss combine pass XLA cannot fuse away, so
        ``"auto"`` stages hot rows too and keeps only the hit
        accounting.  ``"device"``/``"host"`` force either path (the
        bit-equivalence tests run both)."""
        if self.combine != "auto":
            return self.combine == "device"
        return jax.default_backend() != "cpu"

    def fetch(self, src_nodes, shard, cache, *, offsets, num_parts,
              counter=None, staged_rows=None):
        if staged_rows is None:
            raise ValueError(
                "staged feature store needs staged_rows from a "
                "FeatureStager ring; drive it through a prefetch driver "
                "with depth >= 1 (PrefetchSpec(depth=1))")
        if cache is None:
            return staged_rows, jnp.zeros((), jnp.int32)
        is_hit, pos_c = _cache_lookup(cache, src_nodes)
        if not self.hot_rows_from_cache:
            # hits ride the staged buffer (see hot_rows_from_cache);
            # the lookup runs only for the hit-rate accounting
            return staged_rows, jnp.sum(is_hit)
        # gather with the *clamped* positions (no -1 masking): the where
        # below discards non-hit lanes anyway, so the gather can skip
        # its own zeroing pass — one fewer sweep over (N, D)
        hit_rows = self._pinned._gather_hits(cache.rows, pos_c)
        h = jnp.where(is_hit[:, None],
                      hit_rows.astype(staged_rows.dtype), staged_rows)
        return h, jnp.sum(is_hit)


# --------------------------------------------------------------------------
# registry
# --------------------------------------------------------------------------

_FEATURE_STORES: dict[str, Callable[[], FeatureStore]] = {}


def register_feature_store(name: str, factory: Callable[[], FeatureStore],
                           *, overwrite: bool = False) -> None:
    """Register a feature-store factory under ``name``.

    ``factory()`` must return a ``FeatureStore``.  Third parties add
    stores (e.g. a remote feature-server client) without touching
    ``dist.fetch_features``.
    """
    if not overwrite and name in _FEATURE_STORES \
            and _FEATURE_STORES[name] is not factory:
        raise ValueError(f"feature store {name!r} already registered")
    _FEATURE_STORES[name] = factory


def available_feature_stores() -> tuple[str, ...]:
    """Sorted names of registered feature stores.

    Examples
    --------
    >>> set(available_feature_stores()) >= {"exchange", "pinned_hot",
    ...                                     "staged"}
    True
    """
    return tuple(sorted(_FEATURE_STORES))


def resolve_feature_store(name: str) -> FeatureStore:
    """Instantiate the feature store registered under ``name``.

    Examples
    --------
    >>> resolve_feature_store("exchange").name
    'exchange'
    """
    try:
        return _FEATURE_STORES[name]()
    except KeyError:
        raise KeyError(f"unknown feature store {name!r}; "
                       f"available: {available_feature_stores()}") from None


register_feature_store("exchange", ExchangeStore)
register_feature_store("pinned_hot", PinnedHotStore)
register_feature_store("staged", StagedStore)
