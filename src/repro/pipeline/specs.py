"""Declarative configuration for the ``repro.pipeline`` API.

The seed repo conflated three independent axes in one scheme string:
*where data lives* ("vanilla" vs "hybrid" placement), *which kernel builds
a sampling level* (reference / unfused / fused Pallas), and *how the
per-worker program executes* (vmap simulation vs shard_map).  These specs
pull them apart:

  * ``PlanSpec``     — partitioning & placement (+ optional feature cache);
  * ``SamplerSpec``  — fanouts + level-backend name (registry lookup);
  * ``PrefetchSpec`` — double-buffered prefetch: how many steps of
                       minibatch preparation run ahead of model compute;
  * ``DataSpec``     — which graph to train on (source-registry name or
                       on-disk path + generation knobs; defined in
                       ``repro.data.spec``, consumed by
                       ``Pipeline.build_from_source``);
  * ``PipelineSpec`` — all of the above + the executor name.

``PipelineSpec.from_scheme`` parses the legacy
``"vanilla" | "hybrid" | "hybrid+fused"`` strings for callers migrating
from the old ``dist.make_worker_step`` API.
"""
from __future__ import annotations

import dataclasses

from repro.data.spec import DataSpec

LEGACY_SCHEMES = ("vanilla", "hybrid", "hybrid+fused")
SEED_STREAMS = ("counter", "fold")


@dataclasses.dataclass(frozen=True)
class PlanSpec:
    """Partitioning & placement plan (paper §3.3 + the §5 cache).

    scheme:         placement-scheme registry name
                    (``repro.core.placement``): "vanilla" (topology +
                    features partitioned), "hybrid" (topology replicated,
                    features partitioned), "hybrid_partial" (top-``frac``
                    highest-degree in-edge lists replicated, vanilla
                    exchange fallback for the cold rest), or any
                    third-party entry.  The inline parameterized form
                    ``"hybrid_partial(0.25)"`` normalizes to
                    ``scheme="hybrid_partial", replicate_frac=0.25``.
    replicate_frac: replication fraction for parameterized schemes
                    (required by "hybrid_partial"; must be None otherwise).
    cache_capacity: per-worker hot-remote-feature cache entries; 0 = off.
                    The cache composes with EVERY scheme (it is a stage of
                    the feature fetch, not a fork of the sampler).
    cache_policy:   cache-construction registry name
                    (``repro.core.cache``): "degree" (static top-K by
                    in-degree) or "frequency" (top-K by observed access
                    frequency over a short trace of the actual sampler
                    hash stream).
    feature_store:  feature-store registry name
                    (``repro.core.feature_store``): "exchange" (the
                    two-round all_to_all fetch, the default),
                    "pinned_hot" (the cache's hot rows pinned in device
                    memory, served by the Pallas row gather — requires
                    ``cache_capacity > 0``), or "staged" (cold rows
                    pre-gathered on the host and streamed ahead of the
                    step by a ``FeatureStager`` — requires prefetch
                    depth >= 1).  Like the scheme, a registry axis: all
                    stores serve bit-identical rows.
    partitioner:    partitioner registry name
                    (``repro.core.partition``): "ldg" (streaming greedy,
                    the default), "labelprop" (LDG + label-propagation
                    refinement — lower edge cut, same caps), "metis"
                    (requires the optional ``pymetis``), or "random" /
                    "hash" (locality-free baseline).  Parameterized
                    forms like ``"labelprop(20)"`` set entry-specific
                    knobs (sweep count).
    node_slack / labeled_slack: partitioner balance targets (labeled_slack
                    defaults to node_slack when None).
    """
    num_parts: int
    scheme: str = "hybrid"
    cache_capacity: int = 0
    node_slack: float = 1.05
    labeled_slack: float | None = None
    partition_seed: int = 0
    cache_policy: str = "degree"
    replicate_frac: float | None = None
    feature_store: str = "exchange"
    partitioner: str = "ldg"

    def __post_init__(self):
        from repro.core.cache import available_cache_policies
        from repro.core.placement import available_schemes, parse_scheme_name

        base, inline = parse_scheme_name(self.scheme)
        if inline is not None:
            if self.replicate_frac is not None \
                    and float(self.replicate_frac) != inline:
                raise ValueError(
                    f"conflicting replication fractions: scheme "
                    f"{self.scheme!r} vs replicate_frac="
                    f"{self.replicate_frac}")
            object.__setattr__(self, "scheme", base)
            object.__setattr__(self, "replicate_frac", inline)
        if base not in available_schemes():
            raise ValueError(
                f"unknown scheme {self.scheme!r}; valid: "
                f"{available_schemes()} (legacy 'hybrid+fused' = scheme "
                f"'hybrid' + backend 'fused_pallas'; see "
                f"PipelineSpec.from_scheme)")
        # instantiating validates scheme-specific parameters (e.g.
        # hybrid_partial requires replicate_frac in [0, 1]; vanilla/hybrid
        # reject one)
        from repro.core.placement import resolve_scheme
        resolve_scheme(base, frac=self.replicate_frac)
        if self.num_parts < 1:
            raise ValueError(f"num_parts must be >= 1, got {self.num_parts}")
        if self.cache_capacity < 0:
            raise ValueError("cache_capacity must be >= 0")
        if self.cache_policy not in available_cache_policies():
            raise ValueError(
                f"unknown cache policy {self.cache_policy!r}; valid: "
                f"{available_cache_policies()}")
        from repro.core.feature_store import (available_feature_stores,
                                              resolve_feature_store)
        if self.feature_store not in available_feature_stores():
            raise ValueError(
                f"unknown feature store {self.feature_store!r}; valid: "
                f"{available_feature_stores()}")
        if resolve_feature_store(self.feature_store).needs_cache \
                and self.cache_capacity == 0:
            raise ValueError(
                f"feature store {self.feature_store!r} serves hits from "
                f"the pinned device cache; set cache_capacity > 0 (and a "
                f"cache_policy) or use the 'exchange' store")
        # instantiating validates the name, its parameters, and (for
        # "metis") that the optional dependency is importable — all at
        # spec-construction time rather than mid-build
        from repro.core.partition import resolve_partitioner
        resolve_partitioner(self.partitioner)


@dataclasses.dataclass(frozen=True)
class SamplerSpec:
    """Layered-sampling config: fanouts + level-backend registry name.

    fanouts: (N_L, ..., N_1) — top level first (paper notation).
    backend: name registered with ``repro.core.sampler.register_backend``;
             built-ins are "reference", "unfused", "fused_pallas".
    """
    fanouts: tuple[int, ...]
    backend: str = "reference"

    def __post_init__(self):
        fanouts = tuple(int(f) for f in self.fanouts)
        if not fanouts or any(f < 1 for f in fanouts):
            raise ValueError(f"fanouts must be positive ints, got {fanouts}")
        object.__setattr__(self, "fanouts", fanouts)

    @property
    def num_layers(self) -> int:
        return len(self.fanouts)


@dataclasses.dataclass(frozen=True)
class PrefetchSpec:
    """Double-buffered prefetch: overlap minibatch *preparation* (sampling +
    ``pack_by_owner`` + feature ``exchange``/cache lookup) of step *k* with
    the *consume* half (MFG forward/backward + update) of step *k-1*.

    Parameters
    ----------
    depth : int, default 0
        Number of prepared minibatches kept in flight ahead of compute.
        ``0`` selects the ``"sync"`` driver — bit-identical to the plain
        synchronous ``Pipeline.train_step`` path.  ``depth >= 1`` selects
        the ``"double_buffer"`` driver (see ``repro.pipeline.prefetch``).
    seed_stream : str, default "counter"
        How the per-step sampling salt is derived from the step index so
        lookahead and restarts replay the identical seed sequence:
        ``"counter"`` (salt = base_salt + k) or ``"fold"`` (a Knuth
        multiplicative hash of k — decorrelates neighbouring steps).
    sampling : bool, default True
        Run the multi-level sampling stage in the prepare half.
    features : bool, default True
        Run the feature exchange / cache lookup in the prepare half; when
        False the feature fetch stays in the consume half (only sampling
        is prefetched).
    staging : bool, default False
        Host-side async seed staging (``repro.pipeline.staging``): a
        background thread computes future steps' seed draws and starts
        their H2D transfers off the critical path, so drivers consume
        already-resident device arrays.  Composes with any depth (0
        included) and both executors; bit-identical to unstaged runs.
    lead : int, default 1
        How many slots the stager rides ahead of the driver's own
        lookahead (ring size = ``depth + lead``).  Must be >= 1; only
        consulted when ``staging`` is on.

    Examples
    --------
    >>> PrefetchSpec(depth=2).mode
    'double_buffer'
    >>> PrefetchSpec().mode          # depth 0 -> the synchronous driver
    'sync'
    >>> PrefetchSpec(depth=1, staging=True, lead=2).lead
    2
    """
    depth: int = 0
    seed_stream: str = "counter"
    sampling: bool = True
    features: bool = True
    staging: bool = False
    lead: int = 1

    def __post_init__(self):
        if self.depth < 0:
            raise ValueError(f"prefetch depth must be >= 0, got {self.depth}")
        if self.lead < 1:
            raise ValueError(
                f"staging lead must be >= 1, got {self.lead} (the staging "
                f"ring holds depth + lead slots; lead 0 stages nothing "
                f"ahead of the driver)")
        if self.seed_stream not in SEED_STREAMS:
            raise ValueError(
                f"unknown seed_stream {self.seed_stream!r}; "
                f"valid: {SEED_STREAMS}")
        if self.features and not self.sampling:
            raise ValueError(
                "cannot prefetch features without sampling: the feature "
                "fetch consumes the sampled frontier")
        if self.depth > 0 and not self.sampling:
            raise ValueError(
                "prefetch depth > 0 with every stage disabled prefetches "
                "nothing; set sampling=True (and optionally features=True) "
                "or use depth=0")

    @property
    def mode(self) -> str:
        """Prefetch-driver registry name: ``"sync"`` when ``depth == 0``,
        else ``"double_buffer"`` (see
        ``repro.pipeline.prefetch.resolve_prefetcher``)."""
        return "sync" if self.depth == 0 else "double_buffer"


@dataclasses.dataclass(frozen=True)
class PipelineSpec:
    """Everything ``Pipeline.build`` needs: plan + sampler + executor
    (+ optional prefetch and data source).

    Parameters
    ----------
    plan : PlanSpec
        Partitioning & placement (+ optional feature cache).
    sampler : SamplerSpec
        Fanouts + level-backend registry name.
    executor : str, default "vmap"
        Executor registry name: ``"vmap"`` (single-device simulation),
        ``"shard_map"`` (device mesh), or ``"multiprocess"`` (shard_map
        over the global mesh of real OS processes — see
        ``repro.launch.multihost``).
    prefetch : PrefetchSpec, default PrefetchSpec()
        Double-buffering config; the default (depth 0) is the synchronous
        path.
    data : DataSpec, optional
        Graph-source config consumed by ``Pipeline.build_from_source``
        (``repro.data``): source-registry name or on-disk dataset path +
        synthetic generation knobs.  ``None`` (the default) means the
        caller supplies arrays to ``Pipeline.build`` directly.

    Examples
    --------
    >>> spec = PipelineSpec(
    ...     plan=PlanSpec(num_parts=4, scheme="hybrid"),
    ...     sampler=SamplerSpec(fanouts=(10, 5), backend="reference"),
    ...     prefetch=PrefetchSpec(depth=1))
    >>> spec.expected_rounds
    2
    >>> PipelineSpec(plan=PlanSpec(num_parts=2),
    ...              sampler=SamplerSpec(fanouts=(3, 3)),
    ...              data=DataSpec(source="rmat(0.57,0.19,0.19,0.05)",
    ...                            num_nodes=500)).data.num_nodes
    500
    """
    plan: PlanSpec
    sampler: SamplerSpec
    executor: str = "vmap"   # "vmap" | "shard_map" | "multiprocess"
    prefetch: PrefetchSpec = dataclasses.field(default_factory=PrefetchSpec)
    data: DataSpec | None = None

    def __post_init__(self):
        from repro.core.feature_store import resolve_feature_store
        store = resolve_feature_store(self.plan.feature_store)
        if store.external_rows:
            if self.prefetch.depth < 1:
                raise ValueError(
                    f"feature store {self.plan.feature_store!r} streams "
                    f"rows ahead of the step through the prefetch ring; "
                    f"it needs PrefetchSpec(depth >= 1), got depth="
                    f"{self.prefetch.depth}")
            if not self.prefetch.features:
                raise ValueError(
                    f"feature store {self.plan.feature_store!r} needs the "
                    f"feature stage inside the prefetched prepare half "
                    f"(PrefetchSpec(features=True))")

    @property
    def expected_rounds(self) -> int:
        """Structural (trace-time) round count from the placement scheme's
        own accounting: hybrid = 2 (features only); vanilla = 2(L-1)
        sampling rounds + 2 feature rounds = 2L; hybrid_partial keeps the
        vanilla structure unless the replication is complete.  (For the
        data-dependent *utilized*-round estimate see
        ``Pipeline.expected_rounds_estimate``.)"""
        from repro.core.placement import resolve_scheme

        scheme = resolve_scheme(self.plan.scheme,
                                frac=self.plan.replicate_frac)
        return scheme.trace_sampling_rounds(self.sampler.num_layers) + 2

    @classmethod
    def from_scheme(cls, scheme: str, *, num_parts: int,
                    fanouts, cache_capacity: int = 0,
                    executor: str = "vmap",
                    fused_backend: str = "fused_pallas",
                    unfused_backend: str = "unfused",
                    partition_seed: int = 0,
                    partitioner: str = "ldg",
                    prefetch_depth: int = 0,
                    staging: bool = False,
                    staging_lead: int = 1,
                    cache_policy: str = "degree",
                    feature_store: str = "exchange",
                    data: DataSpec | None = None) -> "PipelineSpec":
        """Parse a legacy scheme string — or any registered placement-scheme
        name — into a spec.

          vanilla                -> scheme=vanilla, backend=unfused_backend
          hybrid                 -> scheme=hybrid,  backend=unfused_backend
          hybrid+fused           -> scheme=hybrid,  backend=fused_backend
          hybrid_partial(0.25)   -> scheme=hybrid_partial,
                                    replicate_frac=0.25,
                                    backend=unfused_backend
          <registered name>      -> passed through to ``PlanSpec``

        ``fused_backend`` defaults to the Pallas kernel; benchmarks that
        time the *algorithm* rather than the interpret-mode kernel pass
        ``fused_backend="reference"``.  ``prefetch_depth`` attaches a
        default ``PrefetchSpec`` (0 = synchronous); ``staging`` turns on
        host-side async seed staging (``repro.pipeline.staging``) with
        ``staging_lead`` ring slots beyond the prefetch depth.
        ``feature_store`` selects the feature-serving strategy
        (``repro.core.feature_store`` registry: exchange | pinned_hot |
        staged); ``partitioner`` selects the node-placement algorithm
        (``repro.core.partition`` registry: ldg | labelprop | metis |
        random).
        """
        from repro.core.placement import available_schemes, parse_scheme_name

        if scheme in LEGACY_SCHEMES:
            placement = "hybrid" if scheme.startswith("hybrid") \
                else "vanilla"
            backend = fused_backend if scheme == "hybrid+fused" \
                else unfused_backend
        else:
            base, _ = parse_scheme_name(scheme)
            if base not in available_schemes():
                extras = tuple(s for s in available_schemes()
                               if s not in LEGACY_SCHEMES)
                raise ValueError(f"unknown scheme {scheme!r}; "
                                 f"valid: {LEGACY_SCHEMES + extras}")
            placement = scheme          # PlanSpec parses any inline frac
            backend = unfused_backend
        return cls(
            plan=PlanSpec(num_parts=num_parts, scheme=placement,
                          cache_capacity=cache_capacity,
                          cache_policy=cache_policy,
                          partition_seed=partition_seed,
                          partitioner=partitioner,
                          feature_store=feature_store),
            sampler=SamplerSpec(fanouts=tuple(fanouts), backend=backend),
            executor=executor,
            prefetch=PrefetchSpec(depth=prefetch_depth, staging=staging,
                                  lead=staging_lead),
            data=data)
