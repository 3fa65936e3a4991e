"""Fenced per-stage step profiling: sampling / feature / compute.

The production step programs are deliberately fused — prepare overlaps
consume, the feature exchange hides behind the MFG backward — so a span
around any one jitted call cannot say where the time went.  This module
answers the paper's Figure-1 question ("what share of a step is
sampling?") the only honest way: it rebuilds the step as **three
separately-jitted stages** at the natural seams the prefetch boundary
already exposes —

  sampling : ``prepare`` built with ``features=False`` — the multi-level
             sampling program (including its pack/exchange rounds) and
             the seed-label gather, nothing else.
  feature  : the standalone ``fetch`` stage — frontier feature rows via
             the pipeline's feature store (exchange / cache lookup).
  compute  : ``consume`` on the fetched batch — MFG forward/backward +
             the worker-axis gradient pmean.

— and runs each under ``jax.block_until_ready`` fencing inside a
cat-tagged span.  The decomposition is of the *unoverlapped* step: the
stage sum is what a depth-0 no-staging step costs, and is >= the
overlapped steps/s the drivers achieve (that gap IS the overlap; the
overhead arm of ``benchmarks/bench_obs.py`` measures it separately).

Spans land in the installed tracer (``repro.obs.trace``) with cats
``sampling`` / ``feature`` / ``compute`` and an ``arm`` tag, which is
exactly what ``repro.obs.report`` aggregates into the share table.
"""
from __future__ import annotations

import time

import jax

from repro.core import dist
from repro.obs import trace as _trace

#: stage names, in step order; also the Chrome trace cats the report
#: CLI aggregates
STAGES = ("sampling", "feature", "compute")


def profile_stages(pipeline, loss_fn, params, *, batch: int,
                   steps: int = 4, warmup: int = 1, base_salt: int = 0,
                   arm: str | None = None) -> dict:
    """Measure the sampling / feature / compute split of one step.

    Parameters
    ----------
    pipeline : repro.pipeline.Pipeline
        The built pipeline.  Its feature store must fetch inside the
        traced program (``exchange`` / ``pinned_hot``); the ``staged``
        store serves rows from a host ring and has no in-program feature
        stage to time.
    loss_fn, params
        The training objective and model parameters (`consume` runs the
        real forward/backward).
    batch : int
        Per-worker minibatch size (drives the deterministic seed
        stream, so two profiles of the same spec sample identically).
    steps, warmup : int
        Measured steps (median taken) and untimed warmup steps
        (compilation).
    arm : str, optional
        Label stamped on the emitted spans' ``args`` (e.g. the placement
        scheme) — the report CLI groups rows by it.

    Returns
    -------
    dict
        ``{"arm", "steps", "sampling_s", "feature_s", "compute_s",
        "step_s", "share": {stage: fraction}}`` — per-stage median
        seconds and their share of the summed (unoverlapped) step.

    Examples
    --------
    >>> prof = profile_stages(pipe, loss_fn, params,
    ...                       batch=256, arm="hybrid")   # doctest: +SKIP
    >>> prof["share"]["sampling"]                        # doctest: +SKIP
    0.31
    """
    from repro.pipeline.prefetch import SeedStream

    store = pipeline.feature_store
    if getattr(store, "external_rows", False):
        raise ValueError(
            f"feature store {store.name!r} serves rows from a host-side "
            f"staging ring; there is no in-program feature stage to "
            f"profile.  Profile with the 'exchange' or 'pinned_hot' "
            f"store (the staged store's host cost shows up on the "
            f"stager thread's trace track instead)")
    if steps < 1:
        raise ValueError(f"steps must be >= 1, got {steps}")

    prepare, fetch, consume = pipeline.make_prepare_fetch_consume(
        loss_fn, counted=False)
    # manual vmap binding, mirroring VmapExecutor.bind_prefetch — the
    # profiler needs the three stages as three separate programs, which
    # no executor runner exposes (their whole point is fusing/overlapping
    # them)
    pipeline.place_data(None)
    use_cache = pipeline.cache is not None
    cache_ax = 0 if use_cache else None
    shard_ax = pipeline.shards.axes(0, None)
    vprep = jax.vmap(prepare, in_axes=(shard_ax, 0, None, cache_ax),
                     axis_name=dist.AXIS)
    vfetch = jax.vmap(fetch, in_axes=(shard_ax, 0, cache_ax),
                      axis_name=dist.AXIS)
    vcons = jax.vmap(consume, in_axes=(None, shard_ax, 0, cache_ax),
                     axis_name=dist.AXIS)
    # the data enters each program as an argument, not a baked constant
    data = (pipeline.shards, pipeline.cache)

    @jax.jit
    def sample_raw(seeds, salt, data):
        return vprep(data[0], seeds, salt, data[1])

    @jax.jit
    def fetch_raw(batch, data):
        return vfetch(data[0], batch, data[1])

    @jax.jit
    def compute_raw(params, batch, data):
        take0 = lambda x: x[0]
        loss, grads, metrics = vcons(params, data[0], batch, data[1])
        return loss[0], jax.tree.map(take0, grads), \
            jax.tree.map(take0, metrics)

    sample_j = lambda seeds, salt: sample_raw(seeds, salt, data)
    fetch_j = lambda batch: fetch_raw(batch, data)
    compute_j = lambda params, batch: compute_raw(params, batch, data)

    stream = SeedStream(pipeline, batch,
                        strategy=pipeline.spec.prefetch.seed_stream,
                        base_salt=base_salt)
    tags = {"arm": arm} if arm is not None else {}

    def staged_call(name, fn, record):
        # fence INSIDE the span: device time lands on the stage that
        # caused it, regardless of the tracer's fenced flag
        t0 = time.perf_counter()
        if record:
            with _trace.span(f"profile/{name}", cat=name, **tags):
                out = jax.block_until_ready(fn())
        else:
            out = jax.block_until_ready(fn())
        return out, time.perf_counter() - t0

    times: dict[str, list[float]] = {s: [] for s in STAGES}
    for k in range(warmup + steps):
        seeds = jax.block_until_ready(stream.seeds(k))
        salt = stream.salt(k)
        record = k >= warmup          # warmup spans would skew the
        #                               report's shares with compile time
        batch_k, dt_s = staged_call("sampling", lambda: sample_j(seeds,
                                                                 salt),
                                    record)
        fetched, dt_f = staged_call("feature", lambda: fetch_j(batch_k),
                                    record)
        _, dt_c = staged_call("compute", lambda: compute_j(params,
                                                           fetched),
                              record)
        if record:
            times["sampling"].append(dt_s)
            times["feature"].append(dt_f)
            times["compute"].append(dt_c)

    med = {s: sorted(times[s])[steps // 2] for s in STAGES}
    total = sum(med.values())
    return {"arm": arm, "steps": steps,
            "sampling_s": med["sampling"], "feature_s": med["feature"],
            "compute_s": med["compute"], "step_s": total,
            "share": {s: (med[s] / total if total > 0 else 0.0)
                      for s in STAGES}}
