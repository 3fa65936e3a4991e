"""Double-buffered prefetch: overlap minibatch preparation with compute.

FastSample (§3–5) removes communication rounds; this module hides the
rounds that remain.  Following "Accelerating Training and Inference of
GNNs with Fast Sampling and Pipelining" (arXiv 2110.08450), the per-step
program splits at a *prefetch boundary* into two halves:

  prepare(shard, seeds, salt, cache) -> PreparedBatch
      multi-level sampling (``dist.hybrid_sample`` / ``dist.vanilla_sample``,
      including ``pack_by_owner`` + ``exchange`` rounds for the vanilla
      scheme), the seed-label gather, and — unless
      ``PrefetchSpec(features=False)`` — the feature ``exchange`` / cache
      lookup.  No model parameters are read, so step *k*'s prepare can run
      concurrently with step *k-1*'s compute.

  consume(params, shard, batch, cache) -> (loss, grads, metrics)
      the MFG forward/backward + worker-axis pmean (and the feature fetch,
      when it was left out of the prepare half).

Drivers resolve by registry name from ``PrefetchSpec.mode``:

  * ``"sync"``          — depth 0: one fused program per step, bit-identical
                          to the plain ``Pipeline.train_step`` path.
  * ``"double_buffer"`` — depth >= 1: a FIFO of prepared batches.  The vmap
                          executor overlaps via async JAX dispatch (prepare
                          of step k+depth is dispatched *before* blocking on
                          step k's consume); the shard_map executor rotates
                          donated double buffers inside one jitted program
                          (see ``ShardMapExecutor.bind_prefetch``).

Determinism: a ``SeedStream`` derives step *k*'s minibatch seeds and salt
from the step index alone, so any prefetch depth — and any restart — replays
the identical sample sequence, which is what makes ``depth > 0`` bit-identical
to ``"sync"`` (asserted in ``tests/test_prefetch.py``).

The remaining *host*-side serial segment — the seed draw (a hash rank over
each worker's labeled ids, ``repro.core.partition.seeds_per_worker_host``)
+ its H2D transfer — moves off the critical path with ``PrefetchSpec(staging=True)``
(or ``train_driver(staging=True)``): both drivers then consume
already-resident device seeds from a ``repro.pipeline.staging.SeedStager``
ring, again bit-identically (``tests/test_staging.py``).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import dist
from repro.core.sampler import resolve_backend
from repro.obs import scopes as _scopes
from repro.obs import trace as _trace
from repro.pipeline.specs import SEED_STREAMS


# --------------------------------------------------------------------------
# the prepared minibatch crossing the prefetch boundary
# --------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class PreparedBatch:
    """Everything the consume half needs, as one pytree.

    Attributes
    ----------
    mfgs : tuple[MFG, ...]
        The L sampled message-flow graphs, top level first.
    h_src : jnp.ndarray | None
        (src_capacity, D) gathered input features, or ``None`` when the
        feature stage was not prefetched (``PrefetchSpec(features=False)``)
        — the consume half then performs the fetch itself.
    seed_labels : jnp.ndarray
        (batch,) labels of the seed nodes (gathered from the local shard).
    seed_valid : jnp.ndarray
        (batch,) bool mask of non-padding seeds.
    hits : jnp.ndarray
        () int32 feature-cache hit count (0 when no cache / not prefetched).
    comm : dict
        Utilized communication bytes this worker contributed, per round
        category: ``{"sampling_utilized_bytes": f32,
        "feature_utilized_bytes": f32}`` (the valid-payload counterpart of
        the ``RoundCounter``'s capacity accounting; feature bytes are
        filled in the consume half when the fetch was not prefetched).
    staged : jnp.ndarray | None
        (src_capacity, D) host pre-gathered feature rows from a
        ``FeatureStager`` ring (``external_rows`` stores only).  These
        deliberately do NOT pass through the prepare program: a
        large array that merely crosses a jit boundary is copied at the
        boundary (~tens of ms for (P, N, D) on CPU), so the executor
        attaches the rows to the batch *outside* the traced prepare and
        the consume half fetches from them directly — the buffer enters
        exactly one program, as a zero-copy input.

    Examples
    --------
    >>> prepare, consume = pipe.make_prepare_consume(loss_fn)  # doctest: +SKIP
    >>> batch = prepare(shard, seeds, salt, cache)             # doctest: +SKIP
    >>> loss, grads, metrics = consume(params, shard, batch, cache)  # doctest: +SKIP
    """
    mfgs: tuple
    h_src: Any
    seed_labels: jnp.ndarray
    seed_valid: jnp.ndarray
    hits: jnp.ndarray
    comm: Any = None
    staged: Any = None

    def tree_flatten(self):
        return (self.mfgs, self.h_src, self.seed_labels, self.seed_valid,
                self.hits, self.comm, self.staged), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


# --------------------------------------------------------------------------
# the split per-worker program
# --------------------------------------------------------------------------

def make_prepare_consume(*, offsets: jnp.ndarray, num_parts: int,
                         fanouts: Sequence[int], loss_fn: Callable,
                         scheme: str = "hybrid",
                         graph_replicated=None,
                         backend: str | None = None,
                         level_fn: Callable | None = None,
                         counter: dist.RoundCounter | None = None,
                         vanilla_fused: bool | None = None,
                         features: bool = True,
                         plan=None,
                         store=None):
    """Build the per-worker *prepare* / *consume* halves of the step program.
    (``make_prepare_fetch_consume`` additionally exposes the feature
    stage between them; this is its 2-tuple form.)

    This is the prefetch boundary: ``consume(params, shard,
    prepare(shard, seeds, salt, cache), cache)`` is op-for-op the fused
    program ``repro.pipeline.worker.make_worker_step`` builds (which is
    implemented as exactly that composition).

    Sampling dispatches through the placement-scheme registry
    (``repro.core.placement``): ``plan`` is a ``PlacementPlan`` whose
    scheme owns the per-level program; when ``plan`` is omitted, one is
    built from the legacy ``(scheme, graph_replicated)`` pair.

    Parameters
    ----------
    offsets, num_parts, fanouts, loss_fn, scheme, graph_replicated, backend,
    level_fn, counter, vanilla_fused
        As in ``repro.pipeline.worker.make_worker_step``.
    features : bool, default True
        Whether the feature ``exchange`` / cache lookup belongs to the
        prepare half (True) or stays in the consume half (False).
    plan : repro.core.placement.PlacementPlan, optional
        Pre-built placement plan (takes precedence over ``scheme`` /
        ``graph_replicated``).
    store : repro.core.feature_store.FeatureStore, optional
        How frontier feature rows are served (``None`` = the default
        ``"exchange"`` store, bit-identical to the historical
        ``dist.fetch_features`` path).  Stores with ``external_rows``
        (the ``"staged"`` store) move the fetch into the *consume* half:
        the executor attaches the ``FeatureStager``-produced rows to
        ``PreparedBatch.staged`` outside the traced prepare (see the
        ``PreparedBatch.staged`` docs for why), and ``consume`` serves
        ``h_src`` from them.  ``prepare`` still accepts the rows as its
        fifth argument for callers that want the attach inside the
        traced program (the shard_map fused step, whose donated FIFO
        rotates the buffer in place).

    Returns
    -------
    (prepare, consume)
        ``prepare(shard, seeds, salt, cache=None, staged=None) ->
        PreparedBatch`` and
        ``consume(params, shard, batch, cache) -> (loss, grads, metrics)``.
        Both must run under the named worker axis ``dist.AXIS`` (vmap or
        shard_map); ``cache`` is ``None`` when no feature cache is attached.
    """
    prepare, _, consume = make_prepare_fetch_consume(
        offsets=offsets, num_parts=num_parts, fanouts=fanouts,
        loss_fn=loss_fn, scheme=scheme, graph_replicated=graph_replicated,
        backend=backend, level_fn=level_fn, counter=counter,
        vanilla_fused=vanilla_fused, features=features, plan=plan,
        store=store)
    return prepare, consume


def make_prepare_fetch_consume(*, offsets: jnp.ndarray, num_parts: int,
                               fanouts: Sequence[int], loss_fn: Callable,
                               scheme: str = "hybrid",
                               graph_replicated=None,
                               backend: str | None = None,
                               level_fn: Callable | None = None,
                               counter: dist.RoundCounter | None = None,
                               vanilla_fused: bool | None = None,
                               features: bool = True,
                               plan=None,
                               store=None):
    """``make_prepare_consume`` with the feature stage exposed as its own
    callable.

    Returns ``(prepare, fetch, consume)`` where ``fetch(shard, batch,
    cache=None) -> PreparedBatch`` fills ``h_src``/``hits``/feature bytes
    for a batch prepared without its feature stage (``features=False``)
    and is the identity on a batch that already carries ``h_src``.
    ``consume`` starts by calling ``fetch``, so the 2-tuple composition
    is unchanged op-for-op; the 3-tuple form exists for the stage
    profiler (``repro.obs.profile``), which jits sampling / feature /
    compute as three separately-fenced programs.
    """
    from repro.core.placement import plan_from_legacy

    if plan is None:
        plan = plan_from_legacy(scheme, graph_replicated=graph_replicated,
                                offsets=offsets, num_parts=num_parts)
    if backend is not None and level_fn is not None:
        raise ValueError("pass either backend or level_fn, not both")
    if level_fn is None:
        backend = backend or "reference"
        level_fn = resolve_backend(backend)
    if vanilla_fused is None:
        vanilla_fused = backend is not None and backend != "unfused"
    if store is None:
        from repro.core.feature_store import ExchangeStore
        store = ExchangeStore()
    if store.external_rows and not features:
        raise ValueError(
            f"feature store {store.name!r} serves the feature stage from "
            f"staged rows; it cannot run with features=False")

    row_bytes_of = lambda feats: 4.0 + feats.shape[1] * feats.dtype.itemsize

    def _fetch(src, shard, cache, staged=None):
        with jax.named_scope("fetch"):
            return store.fetch(src, shard, cache, offsets=offsets,
                               num_parts=num_parts, counter=counter,
                               staged_rows=staged)

    def _feature_bytes(src, hits, shard):
        # utilized feature volume: ids out + rows back for every valid
        # source node served over the exchange (stores that bypass the
        # all_to_all — pinned hits, staged rows — report 0 for the part
        # they serve locally)
        return store.utilized_bytes(src, hits,
                                    row_bytes_of(shard.features))

    # overflow observability: the fused level backend counts frontier
    # nodes whose degree exceeded its neighbor window; backends that
    # support it append the per-level traced count to a sink list so the
    # step surfaces total truncation instead of discarding it
    sink_backend = getattr(level_fn, "supports_overflow_sink", False)

    def prepare(shard: dist.WorkerShard, seeds, salt, cache=None,
                staged=None):
        sink: list = []
        lf = level_fn
        if sink_backend:
            def lf(graph, frontier, fanout, level_salt):
                return level_fn(graph, frontier, fanout, level_salt,
                                overflow_sink=sink)
        with jax.named_scope("sample"):
            mfgs, samp_bytes = plan.sample(shard, seeds, fanouts, salt,
                                           level_fn=lf,
                                           fused=vanilla_fused,
                                           counter=counter)
        # per-level attribution: the sink receives one count per level_fn
        # call in sampling order (one per level for every scheme; any
        # extra calls land on the last level)
        L = len(fanouts)
        overflow_per_level = jnp.zeros((L,), jnp.int32)
        for i, o in enumerate(sink):
            overflow_per_level = overflow_per_level.at[
                min(i, L - 1)].add(o.astype(jnp.int32))
        overflow = jnp.sum(overflow_per_level)
        me = lax.axis_index(dist.AXIS)
        local_seed = jnp.clip(seeds - offsets[me], 0,
                              shard.labels.shape[0] - 1)
        seed_labels = shard.labels[local_seed]
        seed_valid = seeds >= 0
        if features and not store.external_rows:
            h_src, hits = _fetch(mfgs[-1].src_nodes, shard, cache)
            feat_bytes = _feature_bytes(mfgs[-1].src_nodes, hits, shard)
        else:
            # external_rows stores fetch in the consume half, where the
            # staged rows enter the program directly (threading them
            # through prepare would copy the whole (N, D) buffer at the
            # prepare -> consume jit boundary)
            h_src, hits = None, jnp.zeros((), jnp.int32)
            feat_bytes = jnp.zeros((), jnp.float32)
        comm = {"sampling_utilized_bytes": samp_bytes,
                "feature_utilized_bytes": feat_bytes,
                "sampler_window_overflow": overflow,
                "sampler_window_overflow_per_level": overflow_per_level}
        return PreparedBatch(mfgs=tuple(mfgs), h_src=h_src,
                             seed_labels=seed_labels, seed_valid=seed_valid,
                             hits=hits, comm=comm, staged=staged)

    def fetch(shard: dist.WorkerShard, batch: PreparedBatch, cache=None):
        """Fill the feature stage of a batch prepared without it
        (``features=False`` / staged rows); identity when ``h_src`` is
        already present."""
        if batch.h_src is not None:
            return batch
        src = batch.mfgs[-1].src_nodes
        h_src, hits = _fetch(src, shard, cache, batch.staged)
        comm = dict(batch.comm,
                    feature_utilized_bytes=_feature_bytes(src, hits,
                                                          shard))
        return dataclasses.replace(batch, h_src=h_src, hits=hits,
                                   comm=comm)

    def consume(params, shard: dist.WorkerShard, batch: PreparedBatch,
                cache=None):
        batch = fetch(shard, batch, cache)
        mfgs = list(batch.mfgs)
        comm = dict(batch.comm)
        h_src, hits = batch.h_src, batch.hits

        def objective(p):
            # the backward pass lands under transpose(jvp(forward))
            with jax.named_scope("forward"):
                return loss_fn(p, mfgs, h_src, batch.seed_labels,
                               batch.seed_valid)

        loss, grads = jax.value_and_grad(objective)(params)
        # order-deterministic reductions (all_gather + local reduce): the
        # summation order is part of the program, so every executor — vmap,
        # shard_map, and the cross-process gloo collectives behind
        # "multiprocess" — produces bit-identical loss/grads
        grads = dist.pmean_ordered(grads)
        loss = dist.pmean_ordered(loss)
        live = jnp.stack([jnp.sum(m.src_nodes >= 0) for m in mfgs])
        hit_rate = hits / jnp.maximum(live[-1], 1)
        metrics = {
            "cache_hit_rate": dist.pmean_ordered(
                hit_rate.astype(jnp.float32)),
            # totals across the worker axis (the fabric-wide volume)
            "sampling_utilized_bytes": dist.psum_ordered(
                comm["sampling_utilized_bytes"]),
            "feature_utilized_bytes": dist.psum_ordered(
                comm["feature_utilized_bytes"]),
            # total frontier slots truncated by the fused kernel's
            # neighbor window this step (0 for backends without windows)
            "sampler_window_overflow": dist.psum_ordered(
                comm.get("sampler_window_overflow",
                         jnp.zeros((), jnp.int32)).astype(jnp.float32)),
            # the same truncation attributed per sampler level, (L,) —
            # what the metrics registry's warn-once overflow watch names
            "sampler_window_overflow_per_level": dist.psum_ordered(
                comm.get("sampler_window_overflow_per_level",
                         jnp.zeros((len(fanouts),), jnp.int32)
                         ).astype(jnp.float32)),
            # frontier slots per level (top first) holding a node after
            # dedup, against the static slots relabel keeps, fabric-wide
            "frontier_live": dist.psum_ordered(live.astype(jnp.float32)),
            "frontier_slots": jnp.asarray(
                [m.src_nodes.shape[0] * num_parts for m in mfgs],
                jnp.float32),
        }
        return loss, grads, metrics

    return prepare, fetch, consume


def make_update_fn(*, lr: float = 1e-3, optimizer: str = "adamw",
                   grad_clip: float | None = 1.0):
    """Gradient-clip + optimizer apply, shared by the sync and prefetch
    paths (same ops as ``Pipeline.train_step`` — the bit-equivalence of
    the two paths depends on it).

    Returns
    -------
    update(params, opt_state, grads, metrics)
        -> (params, opt_state, metrics) with ``grad_norm`` added to
        ``metrics`` when ``grad_clip`` is set.
    """
    from repro.optim import apply_updates
    from repro.optim.optimizers import clip_by_global_norm

    def update(params, opt_state, grads, metrics):
        with jax.named_scope("optimizer"):
            if grad_clip is not None:
                grads, gnorm = clip_by_global_norm(grads, grad_clip)
                metrics = dict(metrics, grad_norm=gnorm)
            params, opt_state = apply_updates(params, grads, opt_state,
                                              kind=optimizer, lr=lr)
        return params, opt_state, metrics

    return update


# --------------------------------------------------------------------------
# deterministic seed streams
# --------------------------------------------------------------------------

class SeedStream:
    """Derive step *k*'s minibatch seeds and sampling salt from *k* alone.

    A stream constructed with the same ``(pipeline spec, batch, strategy,
    base_salt)`` produces identical ``seeds(k)`` / ``salt(k)`` for every
    *k* — across prefetch depths, restarts, and processes.  That property
    is what lets the double-buffer driver look ``depth`` steps ahead and
    still replay the synchronous path bit-for-bit.

    Parameters
    ----------
    pipeline : repro.pipeline.Pipeline
        Supplies ``pipeline.seeds`` (per-worker labeled-node draws).
    batch : int
        Per-worker minibatch size.
    strategy : str, default "counter"
        ``"counter"``: salt_k = base_salt + k.
        ``"fold"``:    salt_k = Knuth-hash(k) ^ mixed base_salt —
        decorrelates neighbouring steps' hash streams.
    base_salt : int, default 0

    Examples
    --------
    >>> a = SeedStream(pipe, batch=64)                       # doctest: +SKIP
    >>> b = SeedStream(pipe, batch=64)                       # doctest: +SKIP
    >>> bool((a.seeds(7) == b.seeds(7)).all())               # doctest: +SKIP
    True
    """

    def __init__(self, pipeline, batch: int, strategy: str = "counter",
                 base_salt: int = 0):
        if strategy not in SEED_STREAMS:
            raise ValueError(f"unknown seed-stream strategy {strategy!r}; "
                             f"valid: {SEED_STREAMS}")
        self._pipeline = pipeline
        self.batch = int(batch)
        self.strategy = strategy
        self.base_salt = int(base_salt)

    def salt_int(self, k: int) -> int:
        """Python-int sampling salt for step ``k`` (deterministic)."""
        if self.strategy == "counter":
            return (self.base_salt + int(k)) % (2 ** 32)
        # "fold": Knuth multiplicative hash of the step index, mixed with
        # the base salt — pure Python so restarts agree exactly
        return ((int(k) * 2654435761) ^ (self.base_salt * 40503)) % (2 ** 32)

    def salt(self, k: int) -> jnp.ndarray:
        """uint32 device salt for step ``k`` (feeds the sampling hash)."""
        return jnp.uint32(self.salt_int(k))

    def seeds_host(self, k: int):
        """(P, batch) seed ids for step ``k`` as a host numpy array.

        The pure host half of ``seeds`` — no JAX tracing or device state
        is touched, so the seed stager (``repro.pipeline.staging``) can
        call it from its background thread.
        """
        return self._pipeline.seeds_host(self.batch,
                                         epoch_salt=self.salt_int(k))

    def seeds(self, k: int) -> jnp.ndarray:
        """(P, batch) per-worker seed node ids for step ``k``."""
        return self._pipeline.seeds(self.batch, epoch_salt=self.salt_int(k))


# --------------------------------------------------------------------------
# prefetch drivers (registry)
# --------------------------------------------------------------------------

class SyncDriver:
    """Depth-0 driver: one fused synchronous program per step.

    ``step(params, opt_state, k)`` calls the exact jitted function
    ``Pipeline.train_step`` returns, with seeds/salt from the
    ``SeedStream`` — bit-identical to driving that function by hand.
    With ``staging`` on, a ``SeedStager`` computes the seed draw and
    starts the H2D transfer for upcoming steps on a background thread;
    the step then consumes already-resident device arrays (same values —
    the stream is a pure function of the step index).
    """

    mode = "sync"

    def __init__(self, pipeline, loss_fn, *, batch: int, lr: float = 1e-3,
                 optimizer: str = "adamw", grad_clip: float | None = 1.0,
                 executor=None, base_salt: int = 0, staging=None):
        from repro.pipeline.executor import resolve_executor
        from repro.pipeline.staging import make_stager

        if executor is None:
            executor = resolve_executor(pipeline.spec.executor)
        self.pipeline = pipeline
        self.depth = 0
        self._fn = pipeline.train_step(loss_fn, lr=lr, optimizer=optimizer,
                                       grad_clip=grad_clip,
                                       executor=executor)
        self.stream = SeedStream(pipeline, batch,
                                 strategy=pipeline.spec.prefetch.seed_stream,
                                 base_salt=base_salt)
        self.stager, self._owns_stager = make_stager(
            staging, self.stream, depth=0, spec=pipeline.spec,
            executor=executor, pipeline=pipeline)
        # see DoubleBufferDriver: a recycling stager's buffer reuse is
        # only sound with per-step materialization
        self._fence = getattr(self.stager, "recycles_buffers", False)
        self._next = 0
        self._profiled = False

    def _seeds_salt(self, k: int):
        if self.stager is not None:
            return self.stager.get(k)
        return self.stream.seeds(k), self.stream.salt(k)

    def step(self, params, opt_state, step_idx: int | None = None):
        """Run step ``step_idx`` (defaults to the next sequential index).

        Returns ``(params, opt_state, loss, metrics)``; while a profiler
        session collects, the metrics are also noted in
        ``repro.obs.trace.profiled()``.
        """
        k = self._next if step_idx is None else int(step_idx)
        with _trace.span("driver/step", cat="driver", step=k,
                         mode=self.mode):
            with _trace.span("driver/seeds", cat="driver"):
                seeds, salt = self._seeds_salt(k)
            with _trace.span("driver/train_step", cat="driver"):
                out = self._fn(params, opt_state, seeds, salt)
        self._next = k + 1
        if _trace.collecting():
            self._profiled = True
            _trace.profiled().add_step(out[3])
        if self._fence:
            jax.block_until_ready(out[2])
        return out

    def op_scopes(self, top_level: bool = False) -> dict[str, str]:
        """``{HLO instruction name: op_name}`` of the program each step
        runs (``repro.obs.scopes``): what a device trace's op names mean
        in the program's named scopes.  Empty before the first step."""
        return _scopes.merge(p.op_scopes(top_level)
                             for p in getattr(self._fn, "programs", ()))

    def reset(self) -> None:
        """Restart the sequential step counter at 0 (draining and
        refilling the staging ring when staging is on)."""
        self._next = 0
        if self.stager is not None:
            self.stager.seek(0)

    def close(self) -> None:
        """Release the staging thread if this driver built it (a stager
        adopted from the caller is left running).  A driver that stepped
        while a profiler session collected first publishes its top-level
        ``op_scopes`` in ``repro.obs.trace.profiled()``.
        """
        if self._profiled:
            _trace.profiled().add_scopes(self.op_scopes(top_level=True))
            self._profiled = False
        if self.stager is not None and self._owns_stager:
            self.stager.close()

    def __enter__(self) -> "SyncDriver":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class DoubleBufferDriver:
    """Depth-``d`` driver: a FIFO of ``d`` prepared batches rides ahead of
    compute.

    On ``step(k)`` the driver (1) hands the executor's runner the seeds for
    step ``k + depth`` so its prepare is dispatched *before* step ``k``'s
    consume blocks, and (2) consumes the oldest queued batch.  The queue is
    (re)filled whenever the requested step index breaks the sequence —
    restarting at any ``k`` therefore reproduces the continuous run exactly
    (the ``SeedStream`` is a pure function of ``k``).

    The executor supplies the overlap mechanism via ``bind_prefetch``:
    async JAX dispatch for ``"vmap"``; donated, explicitly rotated double
    buffers inside one jitted shard_map program for ``"shard_map"``.
    """

    mode = "double_buffer"

    def __init__(self, pipeline, loss_fn, *, batch: int, lr: float = 1e-3,
                 optimizer: str = "adamw", grad_clip: float | None = 1.0,
                 executor=None, base_salt: int = 0, staging=None):
        from repro.pipeline.executor import resolve_executor
        from repro.pipeline.staging import make_stager

        spec = pipeline.spec
        self.depth = spec.prefetch.depth
        if self.depth < 1:
            raise ValueError(
                "double_buffer driver needs prefetch depth >= 1 "
                f"(got {self.depth}); depth 0 is the 'sync' driver")
        prepare, consume = pipeline.make_prepare_consume(loss_fn)
        # an uncounted twin for warmup-only traces, so the RoundCounter
        # reflects one steady-state step, not warmup + steady state
        prepare_warm, _ = pipeline.make_prepare_consume(loss_fn,
                                                        counted=False)
        update = make_update_fn(lr=lr, optimizer=optimizer,
                                grad_clip=grad_clip)
        if executor is None:
            executor = resolve_executor(spec.executor)
        bind = getattr(executor, "bind_prefetch", None)
        if bind is None:
            raise TypeError(
                f"executor {getattr(executor, 'name', executor)!r} does not "
                f"support prefetch (no bind_prefetch method)")
        self.pipeline = pipeline
        self._runner = bind(pipeline, prepare, prepare_warm, consume, update)
        self.stream = SeedStream(pipeline, batch,
                                 strategy=spec.prefetch.seed_stream,
                                 base_salt=base_salt)
        self.stager, self._owns_stager = make_stager(
            staging, self.stream, depth=self.depth, spec=spec,
            executor=executor, pipeline=pipeline)
        # a recycling stager (FeatureStager) reuses the row buffers it
        # handed out a few steps ago; materializing each step's loss
        # before returning bounds how long device reads stay in flight,
        # which is what makes that reuse sound (its docstring has the
        # pool-distance argument)
        self._fence = getattr(self.stager, "recycles_buffers", False)
        self._queue = None
        self._next = 0
        self._profiled = False

    def _seeds_salt(self, k: int):
        if self.stager is not None:
            return self.stager.get(k)
        return self.stream.seeds(k), self.stream.salt(k)

    def _warmup(self, k: int) -> None:
        # an out-of-sequence k drains and refills both the prepared-batch
        # FIFO and (via the stager's index-checked get) the staging ring
        self._queue = tuple(
            self._runner.prepare(*self._seeds_salt(k + i))
            for i in range(self.depth))

    def step(self, params, opt_state, step_idx: int | None = None):
        """Run step ``step_idx`` (defaults to the next sequential index).

        Returns ``(params, opt_state, loss, metrics)``; internally rotates
        the prepared-batch FIFO and dispatches the prepare for step
        ``step_idx + depth``.  While a profiler session collects, the
        metrics are also noted in ``repro.obs.trace.profiled()``.
        """
        k = self._next if step_idx is None else int(step_idx)
        with _trace.span("driver/step", cat="driver", step=k,
                         mode=self.mode, depth=self.depth):
            if self._queue is None or k != self._next:
                with _trace.span("driver/warmup", cat="driver"):
                    self._warmup(k)
            with _trace.span("driver/seeds", cat="driver"):
                nxt = self._seeds_salt(k + self.depth)
            with _trace.span("driver/runner_step", cat="driver"):
                params, opt_state, loss, metrics, self._queue = \
                    self._runner.step(params, opt_state, self._queue,
                                      *nxt)
        self._next = k + 1
        if _trace.collecting():
            self._profiled = True
            _trace.profiled().add_step(metrics)
        if self._fence:
            jax.block_until_ready(loss)
        return params, opt_state, loss, metrics

    def op_scopes(self, top_level: bool = False) -> dict[str, str]:
        """``{HLO instruction name: op_name}`` of the program(s) each
        step runs (``repro.obs.scopes``); an instruction name two of them
        give different scopes is left out.  Empty before the first
        step."""
        return _scopes.merge(p.op_scopes(top_level)
                             for p in getattr(self._runner, "programs", ()))

    def reset(self) -> None:
        """Drop in-flight batches and restart the step counter at 0
        (draining and refilling the staging ring when staging is on)."""
        self._queue = None
        self._next = 0
        if self.stager is not None:
            self.stager.seek(0)

    def close(self) -> None:
        """Release the staging thread if this driver built it (a stager
        adopted from the caller is left running).  A driver that stepped
        while a profiler session collected first publishes its top-level
        ``op_scopes`` in ``repro.obs.trace.profiled()``.
        """
        if self._profiled:
            _trace.profiled().add_scopes(self.op_scopes(top_level=True))
            self._profiled = False
        if self.stager is not None and self._owns_stager:
            self.stager.close()

    def __enter__(self) -> "DoubleBufferDriver":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


_PREFETCHERS: dict[str, Callable] = {}


def register_prefetcher(name: str, driver_cls: Callable, *,
                        overwrite: bool = False) -> None:
    """Register a prefetch-driver class under ``name``.

    ``driver_cls(pipeline, loss_fn, *, batch, lr, optimizer, grad_clip,
    executor, base_salt, staging)`` must yield an object with
    ``step(params, opt_state, step_idx=None)`` and ``reset()``
    (``staging`` is ``None`` | bool | ``SeedStager`` — see
    ``repro.pipeline.staging``; drivers that cannot stage may reject
    truthy values).
    """
    if not overwrite and name in _PREFETCHERS \
            and _PREFETCHERS[name] is not driver_cls:
        raise ValueError(f"prefetcher {name!r} already registered")
    _PREFETCHERS[name] = driver_cls


def available_prefetchers() -> tuple[str, ...]:
    """Sorted names of registered prefetch drivers."""
    return tuple(sorted(_PREFETCHERS))


def resolve_prefetcher(name: str) -> Callable:
    """Look up a prefetch-driver class by registry name.

    Examples
    --------
    >>> sorted(available_prefetchers())
    ['double_buffer', 'sync']
    """
    try:
        return _PREFETCHERS[name]
    except KeyError:
        raise KeyError(f"unknown prefetcher {name!r}; "
                       f"available: {available_prefetchers()}") from None


register_prefetcher("sync", SyncDriver)
register_prefetcher("double_buffer", DoubleBufferDriver)
