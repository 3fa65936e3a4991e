"""Feature-store sweep: steps/s and feature-fetch wall time per store.

The feature rounds are the largest remaining stream in every step (the
paper's accounting, Fig. 4): ``fetch_features`` ships (N, D) rows
through two ``all_to_all`` rounds.  This benchmark measures what the
pluggable stores (``repro.core.feature_store``) buy on a skewed graph
with wide rows, at prefetch depth >= 1, through the same
``Pipeline.train_driver`` path training uses — rows are bit-identical
across stores (``tests/test_feature_store.py``), only where they come
from changes:

  exchange        the two-round all_to_all baseline
  exchange+cache  the same exchange with the FeatureCache attached —
                  the matched-cache baseline for the pinned arms
  pinned_hot      hot rows pinned in device memory (cache hits skip the
                  exchange payload)
  staged          a ``FeatureStager`` ring pre-gathers the frontier's
                  rows on the host and streams them ahead of the consume
                  half — the device program runs *no* feature exchange
                  at all
  staged+pinned   staged cold rows + pinned hot rows

Each arm also times the *fetch path alone* (the jitted per-worker fetch
on a fixed replayed frontier) so the steps/s delta can be attributed.
One JSON record per store lands in ``experiments/feature_staging`` for
the ``benchmarks.report`` feature-store table.

Reading the numbers on a single-core CPU host: the staged arms win by
replacing the traced exchange (which must sweep capacity-sized (N, D)
buffers) with an incremental host gather over only the *live* frontier
slots plus a zero-copy (dlpack, 64-byte-aligned pooled buffers) handoff.
The pinned arms' gain is structurally understated here: their hit/miss
combine is an extra (N, D) pass reading a jit input, which XLA cannot
fuse away on CPU, while exchange+cache's combine fuses into the
exchange's existing output pass for free.  On a real accelerator the
combine is a cheap HBM pass and pinning wins by cutting H2D bytes; the
per-arm ``fetch_wall_s`` column is what transfers.

  PYTHONPATH=src python -m benchmarks.run feature_staging
"""
import json
import os
import time

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import (dataset_columns, emit, stage_breakdown,
                               time_driver)
from repro.core import dist
from repro.core.partition import build_layout, partition_graph
from repro.data.synthetic_graph import make_power_law_graph
from repro.models.gnn import GNNConfig, gnn_loss, init_gnn_params
from repro.optim import init_opt_state
from repro.pipeline import Pipeline, PipelineSpec

# (store, cache_capacity) arms; exchange at cache 0 is the baseline row
CAP = 4096
ARMS = (("exchange", 0), ("exchange", CAP), ("pinned_hot", CAP),
        ("staged", 0), ("staged", CAP))
EXECUTOR = "vmap"
DEPTH = 1
OUT_DIR = os.path.join("experiments", "feature_staging")


def _time_fetch(pipe, frontier, staged_rows, repeats=30):
    """Median wall time of the per-worker fetch program alone, on a
    fixed pre-sampled frontier (what the store changes about the step)."""
    store = pipe.feature_store
    offsets, P = pipe.layout.offsets, pipe.spec.plan.num_parts
    cache = pipe.cache

    def worker(shard, ids, cache_, staged):
        h, _ = store.fetch(ids, shard, cache_, offsets=offsets,
                           num_parts=P, staged_rows=staged)
        return h

    cache_ax = None if cache is None else 0
    staged_ax = None if staged_rows is None else 0
    shard_ax = pipe.shards.axes(0, None)
    fetch_j = jax.jit(jax.vmap(worker,
                               in_axes=(shard_ax, 0, cache_ax, staged_ax),
                               axis_name=dist.AXIS))
    out = fetch_j(pipe.shards, frontier, cache, staged_rows)
    jax.block_until_ready(out)
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(
            fetch_j(pipe.shards, frontier, cache, staged_rows))
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def run(ds, P=4, batch=512, steps=6):
    assign = partition_graph(ds.graph, P, ds.labeled_mask, seed=0)
    layout = build_layout(ds.graph, ds.features, ds.labels, assign, P)
    cfg = GNNConfig(in_dim=ds.features.shape[1], hidden_dim=16,
                    num_classes=ds.num_classes, num_layers=2,
                    fanouts=(5, 5), dropout=0.0)
    ds_cols = dataset_columns(ds)

    def loss_fn(p, mfgs, h_src, labels, valid):
        return gnn_loss(p, mfgs, h_src, labels, valid, cfg)

    # one fixed frontier for the fetch micro-timing: replay the
    # deterministic sampler on the host (same path the stager uses)
    from repro.core.sampler import sample_mfgs
    from repro.pipeline.prefetch import SeedStream

    os.makedirs(OUT_DIR, exist_ok=True)
    base_dt = None
    for store, cap in ARMS:
        spec = PipelineSpec.from_scheme(
            "hybrid", num_parts=P, fanouts=cfg.fanouts,
            cache_capacity=cap, executor=EXECUTOR,
            fused_backend="reference", prefetch_depth=DEPTH,
            feature_store=store)
        pipe = Pipeline.from_layout(layout, spec)

        params = init_gnn_params(jax.random.key(0), cfg)
        with pipe.train_driver(loss_fn, batch=batch, lr=6e-3) as driver:
            opt = init_opt_state(params, kind="adamw")
            dt, metrics = time_driver(driver, params, opt, steps=steps)
        breakdown = stage_breakdown(pipe, loss_fn, params, batch=batch,
                                    arm=store)

        stream = SeedStream(pipe, batch=batch)
        seeds_np = np.asarray(stream.seeds(0))
        salt = int(np.asarray(stream.salt(0)))
        frontier = jnp.asarray(np.stack([
            np.asarray(sample_mfgs(layout.graph, seeds_np[p], cfg.fanouts,
                                   np.uint32(salt))[-1].src_nodes)
            for p in range(P)]))
        staged_rows = None
        if pipe.feature_store.external_rows:
            from repro.pipeline.staging import FeatureStager
            stager = FeatureStager(stream, pipeline=pipe, depth=DEPTH)
            try:
                _, _, staged_rows = stager.get(0)
                jax.block_until_ready(staged_rows)
            finally:
                stager.close()
        fetch_s = _time_fetch(pipe, frontier, staged_rows)

        suffix = {"staged": "+pinned", "exchange": "+cache"}
        tag = f"{store}{suffix.get(store, '') if cap else ''}"
        if base_dt is None:
            base_dt = dt
        speedup = base_dt / dt
        emit(f"feature_staging/P{P}/{tag}/steps_per_s", 1.0 / dt,
             f"store={store} cache={cap} prefetch={DEPTH}")
        emit(f"feature_staging/P{P}/{tag}/fetch_ms", fetch_s * 1e3,
             f"per-worker fetch wall time, fixed frontier")
        emit(f"feature_staging/P{P}/{tag}/speedup", speedup,
             "vs exchange baseline")
        rec = {
            "workload": "feature-staging-sweep", "store": store,
            "arm": tag, "cache_capacity": cap, "executor": EXECUTOR,
            "prefetch_depth": DEPTH, "workers": P, "batch": batch,
            "steps_per_s": 1.0 / dt, "speedup_vs_exchange": speedup,
            "fetch_wall_s": fetch_s,
            "cache_hit_rate": float(metrics.get("cache_hit_rate", 0.0)),
            "stage_breakdown": breakdown,
            **ds_cols,
        }
        with open(os.path.join(
                OUT_DIR, f"feature_staging__{tag}__c{cap}.json"),
                "w") as f:
            json.dump(rec, f, indent=1)


def main() -> None:
    # skewed sparse graph, wide rows: the regime where the feature
    # stream dominates the step (paper Fig. 4) — heavy hubs (low alpha)
    # concentrate the hot set, low average degree leaves the padded
    # frontier mostly dead so the staged host gather touches few bytes
    ds = make_power_law_graph(30_000, 3, num_features=512, num_classes=16,
                              alpha=1.2, seed=0)
    run(ds)


if __name__ == "__main__":
    main()
