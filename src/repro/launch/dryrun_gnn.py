import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Pod-scale dry-run of the PAPER'S OWN workload: distributed
sampling-based GNN training under shard_map with 256 (pod) or 512
(multipod) workers along the data axis.

Proves the hybrid/vanilla protocols lower and compile at production worker
counts (the host run in train_gnn.py uses 4-8 workers), and reports the
collective schedule of each scheme — the 2L-vs-2 round structure shows up
directly as all-to-all op counts in the compiled HLO.

The per-worker program is the unified ``repro.pipeline.worker`` step (the
same one ``Pipeline`` executes); data here is abstract ShapeDtypeStructs,
so the full ``Pipeline.build`` (which partitions a concrete graph) is
bypassed and the step is bound to the mesh directly.

  PYTHONPATH=src python -m repro.launch.dryrun_gnn --workers 256 \
      --scheme hybrid
"""
import argparse
import json

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workers", type=int, default=256,
                    choices=[256, 512])
    ap.add_argument("--scheme", default="both",
                    choices=["vanilla", "hybrid", "both"])
    ap.add_argument("--partitioner", default="ldg",
                    help="partitioner registry name recorded with the "
                         "dry-run (validated against "
                         "repro.core.partition; the abstract-shapes "
                         "trace itself is partition-independent)")
    ap.add_argument("--nodes-per-worker", type=int, default=2000)
    ap.add_argument("--batch", type=int, default=1000)   # paper's batch
    ap.add_argument("--features", type=int, default=128) # papers100M width
    ap.add_argument("--out", default="experiments/dryrun_gnn")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro import roofline
    from repro.core import dist
    from repro.models.gnn import GNNConfig, gnn_loss, init_gnn_params
    from repro.pipeline import PipelineSpec
    from repro.pipeline.worker import make_worker_step

    W = args.workers
    n_max = args.nodes_per_worker
    n_total = W * n_max
    cfg = GNNConfig(in_dim=args.features, hidden_dim=256, num_classes=172,
                    num_layers=3, fanouts=(15, 10, 5), dropout=0.0)

    # abstract per-worker shards (ShapeDtypeStructs — no allocation);
    # topology stand-in: average degree 29 (papers100M-like)
    avg_deg = 29
    nnz_local = n_max * avg_deg
    sds = jax.ShapeDtypeStruct
    shards = dist.WorkerShard(
        features=sds((W, n_max, args.features), jnp.float32),
        labels=sds((W, n_max), jnp.int32),
        local_indptr=sds((W, n_max + 1), jnp.int32),
        local_indices=sds((W, nnz_local), jnp.int32),
    )
    seeds = sds((W, args.batch), jnp.int32)
    offsets = jnp.arange(W + 1, dtype=jnp.int32) * n_max

    # replicated topology for the hybrid scheme
    from repro.core.graph import CSCGraph
    graph = CSCGraph(indptr=sds((n_total + 1,), jnp.int32),
                     indices=sds((n_total * avg_deg,), jnp.int32))

    params = init_gnn_params(jax.random.key(0), cfg)

    def loss_fn(p, mfgs, h_src, labels, valid):
        return gnn_loss(p, mfgs, h_src, labels, valid, cfg)

    mesh = jax.make_mesh((W,), (dist.AXIS,),
                         (jax.sharding.AxisType.Auto,))

    schemes = ["vanilla", "hybrid"] if args.scheme == "both" \
        else [args.scheme]
    for scheme in schemes:
        spec = PipelineSpec.from_scheme(scheme, num_parts=W,
                                        fanouts=cfg.fanouts,
                                        partitioner=args.partitioner)
        counter = dist.RoundCounter()
        # hybrid needs concrete replicated topology at trace time only for
        # shapes — pass structs through a wrapper that treats it as arg
        def worker(params, shards1, seeds1, graph_indptr, graph_indices):
            g = CSCGraph(indptr=graph_indptr, indices=graph_indices)
            step = make_worker_step(
                offsets=offsets, num_parts=W, fanouts=cfg.fanouts,
                loss_fn=loss_fn, scheme=spec.plan.scheme,
                graph_replicated=g if spec.plan.scheme == "hybrid" else None,
                backend=spec.sampler.backend, counter=counter)
            return step(params, shards1, seeds1, jnp.uint32(1))

        def wrapper(params, shards_, seeds_, gi, gx):
            sq = lambda a: a[0]
            loss, grads, _metrics = worker(params, jax.tree.map(sq, shards_),
                                           seeds_[0], gi, gx)
            return loss, grads

        smap = jax.shard_map(
            wrapper, mesh=mesh,
            in_specs=(P(), P(dist.AXIS), P(dist.AXIS), P(), P()),
            out_specs=(P(), P()),
            check_vma=False)

        with mesh:
            lowered = jax.jit(smap).lower(params, shards, seeds,
                                          graph.indptr, graph.indices)
            compiled = lowered.compile()
        mem = compiled.memory_analysis()
        coll = roofline.collective_bytes(compiled.as_text())
        rec = {
            "workload": "gnn-distributed-train",
            "scheme": scheme, "workers": W,
            "partitioner": spec.plan.partitioner,
            "executor": "shard_map", "prefetch_depth": 0,
            "rounds_traced": counter.rounds,
            "sampling_rounds_traced": counter.sampling_rounds,
            "feature_rounds_traced": counter.feature_rounds,
            "expected_rounds": spec.expected_rounds,
            "collective_counts": coll["counts"],
            "collective_bytes_per_device": coll["total_bytes"],
            "peak_estimate_bytes": (mem.argument_size_in_bytes
                                    + mem.output_size_in_bytes
                                    + mem.temp_size_in_bytes
                                    - mem.alias_size_in_bytes),
            "status": "ok",
        }
        print(json.dumps(rec))
        os.makedirs(args.out, exist_ok=True)
        with open(os.path.join(args.out,
                               f"gnn__{scheme}__w{W}.json"), "w") as f:
            json.dump(rec, f, indent=1)


if __name__ == "__main__":
    main()
