"""Executor abstraction: how the per-worker program runs.

The seed repo duplicated "vmap simulation vs shard_map mesh" dispatch in
every launcher; here it is a registry of executors sharing one contract:

    executor.bind(pipeline, step) -> run(params, seeds, salt)
        -> (loss, grads, metrics)

where ``step`` is a ``repro.pipeline.worker`` step.  Every executor
places the pipeline's data (shards, replicated topology, cache) on its
devices once (``Pipeline.place_data``) and binds it, so callers only
supply the per-call arguments; ``run.with_data`` / ``run.data`` let an
outer jit take that data as an argument instead of baking it into the
compiled program as a constant.

  * ``"vmap"``         — single-device simulation: vmap over the stacked
                         worker axis; bit-identical collective semantics.
  * ``"shard_map"``    — production path on a device mesh (one worker per
                         device along ``dist.AXIS``).  Requires the
                         process to expose >= num_parts devices.
  * ``"multiprocess"`` — the same shard_map program over the **global**
                         mesh spanning real OS processes
                         (``jax.distributed.initialize``); see
                         ``MultiprocessExecutor`` and
                         ``repro.launch.multihost``.  Bit-identical to
                         both of the above.

Executors additionally implement ``bind_prefetch`` — the double-buffered
execution mode behind ``repro.pipeline.prefetch.DoubleBufferDriver``.  It
binds the *prepare* / *consume* halves of the step program and returns a
runner whose ``step`` overlaps step *k*'s prepare with step *k-1*'s
consume:

  * ``VmapExecutor``     keeps prepare and consume as two separate jitted
    programs and relies on JAX's async dispatch — the next prepare is
    enqueued on the device stream *before* the consume's results are
    blocked on, so no host-side ``block_until_ready`` sits between them.
  * ``ShardMapExecutor`` fuses consume(k-1) + update + prepare(k) into ONE
    jitted program whose prepared-batch FIFO argument is donated
    (``donate_argnums``): XLA reuses the rotation's buffers as true double
    buffers and its scheduler can overlap the prepare's all_to_all traffic
    with the consume's compute.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax

from repro.core import dist
from repro.obs import trace as _trace
from repro.obs.scopes import Recorded


_EXECUTORS: dict[str, Callable] = {}


def register_executor(name: str, factory: Callable, *,
                      overwrite: bool = False) -> None:
    """Register an executor factory under ``name``.

    Parameters
    ----------
    name : str
        Registry key, e.g. ``"vmap"``.
    factory : Callable
        Zero-argument callable returning an executor (an object with a
        ``bind(pipeline, step)`` method, optionally ``bind_prefetch``).
    overwrite : bool, default False
        Allow replacing an existing entry.

    Examples
    --------
    >>> register_executor("vmap", VmapExecutor)   # idempotent re-register
    """
    if not overwrite and name in _EXECUTORS \
            and _EXECUTORS[name] is not factory:
        raise ValueError(f"executor {name!r} already registered")
    _EXECUTORS[name] = factory


def available_executors() -> tuple[str, ...]:
    """Sorted names of registered executors.

    Examples
    --------
    >>> set(available_executors()) >= {"shard_map", "vmap"}
    True
    """
    return tuple(sorted(_EXECUTORS))


def resolve_executor(name: str):
    """Instantiate the executor registered under ``name``.

    Raises ``KeyError`` (listing the available names) when unknown.

    Examples
    --------
    >>> resolve_executor("vmap").name
    'vmap'
    """
    try:
        return _EXECUTORS[name]()
    except KeyError:
        raise KeyError(f"unknown executor {name!r}; "
                       f"available: {available_executors()}") from None


class _AsyncDispatchRunner:
    """Prefetch runner for ``VmapExecutor``: two jitted halves + JAX async
    dispatch.  ``step`` enqueues the next prepare *before* consuming the
    oldest queued batch, so on an async backend the two execute
    concurrently without any host-side synchronisation.  The prepare
    arguments ``(seeds, salt)`` may be pre-staged device arrays
    (``repro.pipeline.staging``); the jitted prepare consumes them
    as-is, keeping the host work off this critical path.

    Staged feature rows (``external_rows`` stores) are attached to the
    prepared batch HERE, on the host, after ``prepare_j`` returns — not
    threaded through the traced prepare.  A (P, N, D) array that merely
    passes through a jitted program is copied into a fresh output buffer
    at the boundary; attaching outside means the stager's buffer enters
    exactly one program (the consume, which fetches from it) as a
    zero-copy input.

    ``programs`` lists the jitted programs each step runs
    (``repro.obs.scopes.Recorded``)."""

    def __init__(self, prepare_j, consume_j, programs):
        self._prep = prepare_j
        self._cons = consume_j
        self.programs = tuple(programs)

    @staticmethod
    def _attach(batch, rows):
        if rows is None:
            return batch
        return dataclasses.replace(batch, staged=rows)

    def prepare(self, seeds, salt, rows=None):
        """Dispatch one prepare (used by the driver to fill the queue)."""
        with _trace.span("prefetch/prepare", cat="prefetch"):
            nxt = self._attach(self._prep(seeds, salt), rows)
        return nxt

    def step(self, params, opt_state, queue, seeds, salt, rows=None):
        # these spans time *dispatch* — prepare(k+depth) and consume(k)
        # still overlap on the device (the profiler trace shows each
        # stage's device time)
        with _trace.span("prefetch/prepare", cat="prefetch"):
            nxt = self._attach(self._prep(seeds, salt), rows)  # async ...
        with _trace.span("prefetch/consume", cat="prefetch"):
            # ... and only the caller blocks on device values
            params, opt_state, loss, metrics = self._cons(params,
                                                          opt_state,
                                                          queue[0])
        return params, opt_state, loss, metrics, queue[1:] + (nxt,)


class _RotatingBufferRunner:
    """Prefetch runner for ``ShardMapExecutor``: consume + update +
    prepare fused in one jitted program with the batch FIFO donated, so
    XLA rotates the prepared-batch double buffers in place.
    ``seeds_next`` may arrive pre-staged and pre-sharded along the worker
    axis (``ShardMapExecutor.seed_sharding``), in which case the fused
    program starts from already-resident per-device rows.

    ``programs`` lists the jitted programs each step runs (the fused one;
    the warm-up prepare runs only to fill the queue)."""

    def __init__(self, warm_j, fused_j, programs):
        self._warm = warm_j
        self._fused = fused_j
        self.programs = tuple(programs)

    def prepare(self, *extras):
        """Warmup-only prepare (separate jit; its trace does not tick the
        pipeline's RoundCounter)."""
        return self._warm(*extras)

    def step(self, params, opt_state, queue, *extras):
        return self._fused(params, opt_state, queue, *extras)


def _require_full_layout(executor, pipeline):
    """Rank-local pipelines (``local_parts``) hold zero rows for remote
    partitions; only the multi-process executor (whose global mesh places
    each partition's row on its owning process) may bind them."""
    if getattr(pipeline.layout, "local_parts", None) is not None \
            and not getattr(executor, "handles_local_parts", False):
        raise ValueError(
            f"executor {executor.name!r} cannot bind a rank-local "
            f"pipeline (layout.local_parts="
            f"{pipeline.layout.local_parts!r}): remote partitions' "
            f"feature rows were never materialized.  Use the "
            f"'multiprocess' executor, or build with local_parts=None.")


def _bound(with_data, data):
    """``run(params, seeds, salt)`` over the placed ``data``, carrying
    the ``with_data(params, seeds, salt, data)`` twin that outer jits
    (``Pipeline.train_step``) call so the data enters the program as an
    argument: closed over, it would be baked into the compiled program
    as a constant (gigabytes at the paper's graph sizes)."""
    def run(params, seeds, salt):
        return with_data(params, seeds, salt, data)

    run.with_data = with_data
    run.data = data
    return run


def _take0(tree):
    return jax.tree.map(lambda x: x[0], tree)


class VmapExecutor:
    """Single-device simulation: vmap over the stacked worker axis.

    Examples
    --------
    >>> run = VmapExecutor().bind(pipe, step)                # doctest: +SKIP
    >>> loss, grads, metrics = run(params, seeds, salt)      # doctest: +SKIP
    """

    name = "vmap"
    handles_local_parts = False

    def seed_sharding(self, pipeline):
        """Placement for pre-staged seed arrays
        (``repro.pipeline.staging.SeedStager``): the vmap executor runs
        the whole stacked worker axis on the default device, so ``None``
        (commit to the default device) is already optimal."""
        return None

    def _vmapped(self, pipeline, fn):
        """vmap ``fn(params, shard, seeds, salt[, cache])`` over the
        worker axis after placing the pipeline's data on the default
        device: returns ``(call(params, seeds, salt, data), data)``."""
        _require_full_layout(self, pipeline)
        pipeline.place_data(None)
        use_cache = pipeline.cache is not None
        in_axes = (None, pipeline.shards.axes(0, None), 0, None) \
            + ((0,) if use_cache else ())
        vfn = jax.vmap(fn, in_axes=in_axes, axis_name=dist.AXIS)

        def call(params, seeds, salt, data):
            shards, cache = data
            args = (params, shards, seeds, salt)
            return vfn(*(args + ((cache,) if use_cache else ())))

        return call, (pipeline.shards, pipeline.cache)

    def bind(self, pipeline, step):
        """Bind ``step`` (a ``repro.pipeline.worker`` program) to the
        pipeline's shards/cache under ``jax.vmap``.

        Returns ``run(params, seeds, salt) -> (loss, grads, metrics)``
        with the worker axis already reduced (worker 0's pmean-ed copy).
        """
        call, data = self._vmapped(pipeline, step)

        def with_data(params, seeds, salt, data):
            loss, grads, metrics = call(params, seeds, salt, data)
            # pmean makes every worker's copy identical; take worker 0's
            return loss[0], _take0(grads), _take0(metrics)

        return _bound(with_data, data)

    def bind_infer(self, pipeline, infer_step):
        """Bind an inference step (``repro.pipeline.infer``) under vmap.

        Unlike ``bind``, the primary output stays **per-worker**:
        ``run(params, seeds, salt) -> (logits, metrics)`` with ``logits``
        stacked (P, batch, C) — serving routes each request to its seed's
        owning worker's row.  ``metrics`` is already pmean/psum-reduced
        inside the step, so worker 0's copy is returned.
        """
        call, data = self._vmapped(pipeline, infer_step)

        def with_data(params, seeds, salt, data):
            logits, metrics = call(params, seeds, salt, data)
            return logits, _take0(metrics)

        return _bound(with_data, data)

    def bind_prefetch(self, pipeline, prepare, prepare_warm, consume,
                      update):
        """Bind the split step program for double-buffered execution.

        ``prepare``/``consume`` are the halves from
        ``Pipeline.make_prepare_consume``; ``update`` applies
        grad-clip + optimizer (``repro.pipeline.prefetch.make_update_fn``).
        Returns a runner whose ``step(params, opt_state, queue, seeds_next,
        salt_next)`` dispatches the next prepare asynchronously before
        consuming ``queue[0]``.  ``prepare_warm`` is unused here — the
        same jitted prepare serves warmup and steady state (it traces,
        and therefore ticks the round counter, exactly once).
        """
        _require_full_layout(self, pipeline)
        pipeline.place_data(None)
        use_cache = pipeline.cache is not None
        cache_ax = 0 if use_cache else None
        shard_ax = pipeline.shards.axes(0, None)
        # feature stores with external_rows (the "staged" store) do NOT
        # thread their (P, src_capacity, D) rows through this prepare —
        # the runner attaches them to the batch host-side and the consume
        # fetches from them (see _AsyncDispatchRunner)
        vprep = jax.vmap(prepare, in_axes=(shard_ax, 0, None, cache_ax),
                         axis_name=dist.AXIS)
        vcons = jax.vmap(consume, in_axes=(None, shard_ax, 0, cache_ax),
                         axis_name=dist.AXIS)
        data = (pipeline.shards, pipeline.cache)

        @Recorded
        @jax.jit
        def prepare_raw(seeds, salt, data):
            shards, cache = data
            return vprep(shards, seeds, salt, cache)

        @Recorded
        @jax.jit
        def consume_raw(params, opt_state, batch, data):
            shards, cache = data
            loss, grads, metrics = vcons(params, shards, batch, cache)
            params, opt_state, metrics = update(params, opt_state,
                                                _take0(grads),
                                                _take0(metrics))
            return params, opt_state, loss[0], metrics

        return _AsyncDispatchRunner(
            lambda seeds, salt: prepare_raw(seeds, salt, data),
            lambda params, opt_state, batch: consume_raw(
                params, opt_state, batch, data),
            (prepare_raw, consume_raw))


class ShardMapExecutor:
    """Production path: the same per-worker program under shard_map.

    ``mesh`` defaults to a fresh 1-D mesh of ``num_parts`` devices along
    ``dist.AXIS`` (pass an existing mesh to embed the worker axis in a
    larger topology).  The pipeline's data is placed on the mesh once at
    bind time — per-worker arrays sharded along the worker axis, the
    replicated topology copied to every device — and enters each jitted
    program as an argument.
    """

    name = "shard_map"
    handles_local_parts = False

    def __init__(self, mesh=None):
        self.mesh = mesh

    def seed_sharding(self, pipeline):
        """Placement for pre-staged seed arrays
        (``repro.pipeline.staging.SeedStager``): shard the ``(P, batch)``
        seeds along the worker axis of the executor's mesh, so the staged
        H2D transfer already lands each worker's row on its device and
        the jitted program neither reshards nor re-transfers."""
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        return NamedSharding(self._resolve_mesh(pipeline), P(dist.AXIS))

    def _resolve_mesh(self, pipeline):
        _require_full_layout(self, pipeline)
        num_parts = pipeline.spec.plan.num_parts
        mesh = self.mesh
        if mesh is None:
            if len(jax.devices()) < num_parts:
                raise RuntimeError(
                    f"shard_map executor needs >= {num_parts} devices, "
                    f"found {len(jax.devices())} (set "
                    f"--xla_force_host_platform_device_count for a CPU "
                    f"placeholder mesh)")
            mesh = jax.make_mesh((num_parts,), (dist.AXIS,),
                                 (jax.sharding.AxisType.Auto,))
            self.mesh = mesh
        return mesh

    def _placed(self, pipeline):
        """The mesh, after placing the pipeline's data on it."""
        mesh = self._resolve_mesh(pipeline)
        pipeline.place_data(mesh)
        return mesh

    def _smap(self, pipeline, fn, *, per_worker_out):
        """shard_map ``fn(params, shard, seeds, salt[, cache])`` over the
        worker axis, with the data as arguments: returns ``call(params,
        seeds, salt, data)``.  With ``per_worker_out`` the first output
        keeps the worker axis (the inference logits), else every output
        is replicated."""
        from jax.sharding import PartitionSpec as P

        mesh = self._placed(pipeline)
        use_cache = pipeline.cache is not None
        A = dist.AXIS

        def wrapper(params, shards, seeds, salt, cache):
            cache_ = (_take0(cache),) if use_cache else ()
            out = fn(params, shards.worker(0), seeds[0], salt, *cache_)
            if per_worker_out:
                out = (out[0][None],) + tuple(out[1:])
            return out

        out_specs = (P(A), P()) if per_worker_out else (P(), P(), P())
        smap = jax.shard_map(
            wrapper, mesh=mesh,
            in_specs=(P(), pipeline.shards.axes(P(A), P()), P(A), P(),
                      P(A)),
            out_specs=out_specs, check_vma=False)

        def call(params, seeds, salt, data):
            shards, cache = data
            return smap(params, shards, seeds, salt, cache)

        return call

    def bind(self, pipeline, step):
        """Bind ``step`` to the pipeline's shards/cache under ``shard_map``
        on the executor's mesh (built lazily when not supplied).

        Returns ``run(params, seeds, salt) -> (loss, grads, metrics)``
        with replicated (pmean-ed) outputs.
        """
        call = self._smap(pipeline, step, per_worker_out=False)
        return _bound(call, (pipeline.shards, pipeline.cache))

    def bind_infer(self, pipeline, infer_step):
        """Bind an inference step (``repro.pipeline.infer``) under
        shard_map on the executor's mesh.

        ``run(params, seeds, salt) -> (logits, metrics)``: ``logits`` is
        (P, batch, C), sharded along the worker axis (each device holds
        its own seeds' logits); ``metrics`` is replicated (the step
        pmean/psums it over ``dist.AXIS``).
        """
        call = self._smap(pipeline, infer_step, per_worker_out=True)
        return _bound(call, (pipeline.shards, pipeline.cache))

    def bind_prefetch(self, pipeline, prepare, prepare_warm, consume,
                      update):
        """Bind the split step program as ONE jitted shard_map pipeline.

        The returned runner's ``step`` executes::

            loss, grads, metrics = consume(queue[0])        # step k-1
            params, opt_state    = update(grads)
            queue                = queue[1:] + (prepare(seeds_next),)  # k

        in a single XLA program with ``queue`` donated
        (``donate_argnums``), i.e. the prepared-batch FIFO rotates through
        donated double buffers and the prepare's all_to_all rounds can be
        scheduled against the consume's compute.  ``prepare_warm`` (an
        uncounted twin of ``prepare``) fills the queue initially from a
        separate jit so warmup traces don't inflate the pipeline's
        RoundCounter.  The placed data is passed after the donated queue,
        so only the queue's buffers rotate.
        """
        from functools import partial

        smap_prep, smap_prep_warm, smap_cons = self._build_prefetch_smaps(
            pipeline, prepare, prepare_warm, consume)
        data = (pipeline.shards, pipeline.cache)

        @Recorded
        @partial(jax.jit, donate_argnums=(2,))
        def fused_raw(params, opt_state, queue, seeds_next, salt_next,
                      rest, data):
            loss, grads, metrics = smap_cons(params, queue[0], data)
            params, opt_state, metrics = update(params, opt_state, grads,
                                                metrics)
            nxt = smap_prep(seeds_next, salt_next, rest, data)
            return params, opt_state, loss, metrics, queue[1:] + (nxt,)

        @jax.jit
        def warm_raw(seeds, salt, rest, data):
            return smap_prep_warm(seeds, salt, rest, data)

        def warm_j(seeds, salt, *rest):
            return warm_raw(seeds, salt, tuple(rest), data)

        def fused_j(params, opt_state, queue, seeds_next, salt_next,
                    *rest):
            params, opt_state = pipeline.replicate((params, opt_state))
            return fused_raw(params, opt_state, queue, seeds_next,
                             salt_next, tuple(rest), data)

        return _RotatingBufferRunner(warm_j, fused_j, (fused_raw,))

    def _build_prefetch_smaps(self, pipeline, prepare, prepare_warm,
                              consume):
        """shard_map programs for the split step with the placed data as
        an argument: ``(smap_prep, smap_prep_warm, smap_cons)`` where the
        prepares take ``(seeds, salt, rest, data)`` — ``rest`` holding
        the staged feature rows of ``external_rows`` stores, else empty —
        and the consume ``(params, batch, data)``."""
        from jax.sharding import PartitionSpec as P

        mesh = self._placed(pipeline)
        use_cache = pipeline.cache is not None
        ext = bool(getattr(getattr(pipeline, "feature_store", None),
                           "external_rows", False))
        expand = lambda t: jax.tree.map(lambda a: a[None], t)
        A = dist.AXIS
        shard_spec = pipeline.shards.axes(P(A), P())

        def _smap_prepare(fn):
            def wrapper(shards, cache, seeds, staged, salt):
                cache_ = _take0(cache) if use_cache else None
                staged_ = staged[0] if ext else None
                return expand(fn(shards.worker(0), seeds[0], salt, cache_,
                                 staged_))

            smap = jax.shard_map(
                wrapper, mesh=mesh,
                in_specs=(shard_spec, P(A), P(A), P(A), P()),
                out_specs=P(A), check_vma=False)

            def call(seeds, salt, rest, data):
                shards, cache = data
                staged = rest[0] if ext else None
                return smap(shards, cache, seeds, staged, salt)

            return call

        def cons_wrapper(params, batch, shards, cache):
            cache_ = _take0(cache) if use_cache else None
            return consume(params, shards.worker(0), _take0(batch), cache_)

        smap_cons = jax.shard_map(
            cons_wrapper, mesh=mesh,
            in_specs=(P(), P(A), shard_spec, P(A)),
            out_specs=(P(), P(), P()), check_vma=False)

        def call_cons(params, batch, data):
            shards, cache = data
            return smap_cons(params, batch, shards, cache)

        return (_smap_prepare(prepare), _smap_prepare(prepare_warm),
                call_cons)


class MultiprocessExecutor(ShardMapExecutor):
    """Multi-host path: the same per-worker program under shard_map over
    the **global** mesh spanning every JAX process.

    Each rank must have called ``jax.distributed.initialize`` (see
    ``repro.launch.multihost.init_from_env``) before any JAX work; the
    executor then builds a 1-D mesh over ALL processes' devices — sorted
    ``(process_index, id)`` so partition ``p`` lands on the process that
    built it — and binds the identical step program ``ShardMapExecutor``
    binds.  Placement schemes, cache policies, prefetch drivers, and seed
    staging therefore compose unchanged.

    Two things differ from single-process shard_map:

    * the mesh spans processes, so ``Pipeline.place_data`` builds global
      arrays in which each process contributes only its **addressable**
      rows — which is what makes rank-local builds
      (``Pipeline.build(local_parts=...)``) safe: a rank never
      materializes (or ships) partitions it doesn't own.  Params, opt
      state, seeds, and salt stay ordinary uncommitted host arrays; JAX
      replicates/auto-shards them per the program's specs.
    * cross-process collectives run on the CPU backend's gloo
      implementation.  ``lax.all_to_all`` (the paper's communication
      primitive) is pure data movement and bit-exact everywhere; the
      loss/grad reductions go through ``dist.pmean_ordered`` /
      ``dist.psum_ordered`` (all_gather + program-fixed local reduce), so
      results are bit-identical to ``vmap`` and ``shard_map``
      (``tests/test_multihost.py`` asserts the full matrix).
    """

    name = "multiprocess"
    handles_local_parts = True

    def _resolve_mesh(self, pipeline):
        import numpy as np

        num_parts = pipeline.spec.plan.num_parts
        mesh = self.mesh
        if mesh is None:
            devices = sorted(jax.devices(),
                             key=lambda d: (d.process_index, d.id))
            if len(devices) != num_parts:
                raise RuntimeError(
                    f"multiprocess executor needs exactly {num_parts} "
                    f"global devices (one per worker/partition), found "
                    f"{len(devices)} across {jax.process_count()} "
                    f"process(es); set "
                    f"--xla_force_host_platform_device_count="
                    f"{num_parts // max(jax.process_count(), 1)} per "
                    f"process")
            if num_parts % jax.process_count() != 0:
                raise RuntimeError(
                    f"num_parts={num_parts} must divide evenly across "
                    f"{jax.process_count()} processes")
            mesh = jax.sharding.Mesh(np.asarray(devices), (dist.AXIS,))
            self.mesh = mesh
        self._check_local_parts(pipeline, mesh)
        return mesh

    def _check_local_parts(self, pipeline, mesh):
        """A rank-local layout must cover exactly the partitions whose
        mesh rows this process addresses — otherwise the global array
        assembly would read never-materialized zero rows."""
        lp = getattr(pipeline.layout, "local_parts", None)
        if lp is None:
            return
        me = jax.process_index()
        rows = [i for i, d in enumerate(mesh.devices.flat)
                if d.process_index == me]
        want = (min(rows), max(rows) + 1)
        if tuple(lp) != want or len(rows) != want[1] - want[0]:
            raise ValueError(
                f"rank-local layout covers partitions {tuple(lp)!r} but "
                f"process {me} addresses mesh rows {want!r}; build with "
                f"local_parts={want!r}")


register_executor("vmap", VmapExecutor)
register_executor("shard_map", ShardMapExecutor)
register_executor("multiprocess", MultiprocessExecutor)
