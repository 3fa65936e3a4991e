"""Distributed GNN training launcher — the paper's workload through the
``repro.pipeline`` API, under vmap simulation, shard_map on real (or
host-placeholder) devices, or real OS processes (``multiprocess``).

  PYTHONPATH=src python -m repro.launch.train_gnn --devices 8 \
      --scheme hybrid+fused --epochs 3
  PYTHONPATH=src python -m repro.launch.train_gnn --devices 4 \
      --scheme hybrid --cache-capacity 4096 --shard-map --prefetch-depth 1
  PYTHONPATH=src python -m repro.launch.train_gnn --devices 4 \
      --scheme "hybrid_partial(0.25)" --cache-policy frequency
  PYTHONPATH=src python -m repro.launch.train_gnn --devices 4 \
      --dataset "rmat(0.57,0.19,0.19,0.05)" --scheme "hybrid_partial(0.1)"
  PYTHONPATH=src python -m repro.launch.train_gnn --devices 4 \
      --dataset datasets/ogbn-arxiv.npz
  PYTHONPATH=src python -m repro.launch.train_gnn --devices 4 \
      --executor multiprocess --num-procs 2 --scheme hybrid

With ``--executor multiprocess`` the parent re-execs itself as
``--num-procs`` coordinated worker processes (``repro.launch.multihost``)
and each rank materializes only its own partitions' feature arrays
(``Pipeline.build_from_source(local_parts=...)``).
"""
import argparse


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8,
                    help="workers (host placeholder devices on CPU)")
    ap.add_argument("--dataset", default="powerlaw(1.8)",
                    help="graph source: a registry name from repro.data "
                         "(uniform | powerlaw(alpha) | rmat(a,b,c,d) | "
                         "sbm(k,p_in,p_out)) or a path to a dataset saved "
                         "with repro.data.save_dataset (.npz)")
    ap.add_argument("--split", default="random(0.3)",
                    help="labeled-node split policy (random(frac) | "
                         "degree_stratified(frac)); ignored for on-disk "
                         "datasets")
    ap.add_argument("--scheme", default="hybrid+fused",
                    help="legacy string (vanilla | hybrid | hybrid+fused) "
                         "or any registered placement scheme, e.g. "
                         "'hybrid_partial(0.25)' for degree-aware partial "
                         "replication")
    ap.add_argument("--partitioner", default="ldg",
                    help="partitioner registry name "
                         "(repro.core.partition): ldg (streaming "
                         "greedy, the default) | labelprop (LDG + "
                         "label-propagation refinement, lower edge "
                         "cut) | metis (needs pymetis) | random "
                         "(locality-free baseline); parameterized "
                         "forms like 'labelprop(20)' set the sweep "
                         "count")
    ap.add_argument("--cache-capacity", type=int, default=0,
                    help="per-worker hot-remote-feature cache entries "
                         "(0 = off); composes with any scheme")
    ap.add_argument("--cache-policy", default="degree",
                    help="cache-construction policy registry name "
                         "(degree | frequency)")
    ap.add_argument("--feature-store", default="exchange",
                    help="feature-store registry name "
                         "(repro.core.feature_store): exchange (two-round "
                         "all_to_all fetch, the default) | pinned_hot "
                         "(cache's hot rows pinned in device memory, "
                         "needs --cache-capacity > 0) | staged (host "
                         "pre-gathered rows streamed ahead of the step, "
                         "needs --prefetch-depth >= 1); rows are "
                         "bit-identical across stores")
    ap.add_argument("--prefetch-depth", type=int, default=0,
                    help="double-buffered prefetch depth: overlap step "
                         "k's sampling/feature all_to_all with step k-1's "
                         "compute (0 = synchronous; results are "
                         "bit-identical at any depth)")
    ap.add_argument("--staging", action="store_true",
                    help="host-side async seed staging: compute future "
                         "steps' seed argsorts and start their H2D "
                         "transfers on a background thread "
                         "(repro.pipeline.staging; bit-identical results, "
                         "composes with any scheme/executor/depth)")
    ap.add_argument("--staging-lead", type=int, default=1,
                    help="staging ring slots beyond the prefetch depth "
                         "(how far the host runs ahead of the device)")
    ap.add_argument("--nodes", type=int, default=20000)
    ap.add_argument("--avg-degree", type=int, default=10)
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--steps-per-epoch", type=int, default=10)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--lr", type=float, default=0.006)   # paper §4
    ap.add_argument("--shard-map", action="store_true",
                    help="run under shard_map on a device mesh instead of "
                         "the vmap single-device simulation "
                         "(legacy alias for --executor shard_map)")
    ap.add_argument("--executor", default=None,
                    choices=["vmap", "shard_map", "multiprocess"],
                    help="executor registry name (default: vmap, or "
                         "shard_map when --shard-map is set)")
    ap.add_argument("--num-procs", type=int, default=2,
                    help="worker processes for --executor multiprocess "
                         "(must divide --devices; each process hosts "
                         "devices/num-procs placeholder devices)")
    ap.add_argument("--mh-timeout", type=float, default=600.0,
                    help="multiprocess launcher wall-clock timeout in "
                         "seconds (hang detection)")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="record a Chrome trace-event timeline of the run "
                         "(repro.obs): driver/prefetch/stager spans, "
                         "viewable in Perfetto; with --executor "
                         "multiprocess each rank writes OUT.json.rankR "
                         "and the parent merges them into OUT.json "
                         "(rank-as-pid).  Render the span summary with "
                         "'python -m repro.obs.report OUT.json --summary'")
    args = ap.parse_args()

    executor = args.executor or ("shard_map" if args.shard_map else "vmap")

    import os
    import sys

    from repro.launch import multihost

    if executor == "multiprocess" and not multihost.is_worker():
        # parent: re-exec this command line as the worker fleet, then
        # surface rank 0's captured stdout
        if args.devices % args.num_procs != 0:
            ap.error(f"--devices {args.devices} must be divisible by "
                     f"--num-procs {args.num_procs}")
        log_dir = multihost.launch(
            [sys.executable, "-m", "repro.launch.train_gnn"]
            + sys.argv[1:],
            num_procs=args.num_procs,
            local_devices=args.devices // args.num_procs,
            timeout=args.mh_timeout)
        with open(os.path.join(log_dir, "rank0.out")) as f:
            sys.stdout.write(f.read())
        if args.trace:
            multihost.merge_rank_traces(args.trace, args.num_procs)
            print(f"merged fleet trace written to {args.trace}")
        print(f"multiprocess run complete ({args.num_procs} CPU "
              f"processes over gloo; the chip path is --executor "
              f"shard_map); per-rank logs in {log_dir}")
        return

    rank, local_parts = 0, None
    if executor == "multiprocess":
        # worker: join the jax.distributed job BEFORE any backend use,
        # then build only this rank's partitions' feature arrays
        rank, num_procs = multihost.init_from_env()
        per = args.devices // num_procs
        # rank-local feature builds save memory but preclude stages that
        # read remote rows: the cache copies remote hot rows, and the
        # staged store's host gather walks the full table
        if args.cache_capacity == 0 and args.feature_store != "staged":
            local_parts = (rank * per, (rank + 1) * per)
    elif executor == "shard_map":
        # placeholder devices for a CPU mesh, merged into any XLA_FLAGS
        # the caller set (multihost.rank_env merges the same way)
        flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
                 if not f.startswith(multihost.DEVICE_FLAG)]
        os.environ["XLA_FLAGS"] = " ".join(
            flags + [f"{multihost.DEVICE_FLAG}={args.devices}"])

    import jax

    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()

    from repro.obs import trace as obs_trace

    if args.trace:
        # each rank records (and exports) its own trace; the supervisor
        # merges the rank files after the fleet exits
        path = args.trace if executor != "multiprocess" \
            else multihost.rank_trace_path(args.trace, rank)
        obs_trace.start(path, pid=rank,
                        process_name=f"rank{rank}" if executor
                        == "multiprocess" else "train_gnn")

    from repro.data import DataSpec, dataset_stats, stats_label
    from repro.models.gnn import GNNConfig, gnn_loss, init_gnn_params
    from repro.optim import init_opt_state
    from repro.pipeline import Pipeline, PipelineSpec

    data = DataSpec(source=args.dataset, num_nodes=args.nodes,
                    avg_degree=args.avg_degree, num_features=100,
                    num_classes=47, split=args.split, seed=0)
    fanouts = (10, 10, 5)               # paper §4 defaults
    spec = PipelineSpec.from_scheme(
        args.scheme, num_parts=args.devices, fanouts=fanouts,
        cache_capacity=args.cache_capacity,
        cache_policy=args.cache_policy,
        partitioner=args.partitioner,
        executor=executor,
        prefetch_depth=args.prefetch_depth, staging=args.staging,
        staging_lead=args.staging_lead,
        feature_store=args.feature_store, data=data)
    pipe = Pipeline.build_from_source(spec=spec, local_parts=local_parts)
    ds = pipe.dataset
    say = print if rank == 0 else (lambda *a, **k: None)
    say(f"dataset: {stats_label(dataset_stats(ds))}")

    cfg = GNNConfig(in_dim=ds.features.shape[1], hidden_dim=256,
                    num_classes=ds.num_classes, num_layers=len(fanouts),
                    fanouts=fanouts, dropout=0.0)
    say(f"partitioned into {args.devices} by {args.partitioner!r}: "
        f"edge-cut {pipe.edge_cut_fraction:.1%}")
    if local_parts is not None:
        say(f"rank-local build: each rank materializes "
            f"{args.devices // args.num_procs} of {args.devices} "
            f"feature partitions")
    if pipe.placement is not None \
            and hasattr(pipe.placement, "replicated_edge_fraction"):
        say(f"partial replication: "
            f"{pipe.placement.replicated_edge_fraction:.1%} of edges "
            f"replicated, expected rounds/step "
            f"{pipe.expected_rounds_estimate:.2f} "
            f"(hybrid=2, vanilla={2 * cfg.num_layers})")

    def loss_fn(p, mfgs, h_src, labels, valid):
        return gnn_loss(p, mfgs, h_src, labels, valid, cfg)

    params = init_gnn_params(jax.random.key(0), cfg)
    opt_state = init_opt_state(params, kind="adamw")

    import time

    from repro.obs.metrics import get_registry

    registry = get_registry()
    # the driver context guarantees the staging thread is released even
    # when an epoch raises
    with pipe.train_driver(loss_fn, batch=args.batch, lr=args.lr,
                           optimizer="adamw", grad_clip=1.0) as driver:
        for epoch in range(args.epochs):
            t0 = time.time()
            for s in range(args.steps_per_epoch):
                params, opt_state, loss, metrics = driver.step(params,
                                                               opt_state)
                if epoch == 0 and s == 0:
                    # the round counter fills at first trace — report it
                    # only once a step has actually traced
                    say(f"scheme={args.scheme} executor={spec.executor} "
                        f"prefetch={args.prefetch_depth} "
                        f"staging={'on' if args.staging else 'off'}: "
                        f"{pipe.counter.rounds} comm rounds/step "
                        f"({pipe.counter.sampling_rounds} sampling + "
                        f"{pipe.counter.feature_rounds} feature; "
                        f"vanilla=2L={2*cfg.num_layers}, hybrid=2)")
            jax.block_until_ready(loss)
            # the epoch end already materializes metrics for the log
            # line; absorbing them here also runs the warn-once
            # sampler-overflow watch without adding a per-step sync
            registry.observe_step(
                metrics, step=(epoch + 1) * args.steps_per_epoch - 1)
            msg = (f"epoch {epoch}: loss {float(loss):.4f} "
                   f"rounds/step {pipe.counter.rounds} "
                   f"utilized-KB/step "
                   f"{float(metrics['sampling_utilized_bytes'])/1024:.0f}s+"
                   f"{float(metrics['feature_utilized_bytes'])/1024:.0f}f "
                   f"time {time.time()-t0:.2f}s")
            if args.cache_capacity:
                msg += f" cache-hit {float(metrics['cache_hit_rate']):.1%}"
            say(msg)
    if args.trace:
        tracer = obs_trace.stop()
        say(f"trace written to {args.trace} "
            f"({tracer.num_recorded} spans, {tracer.dropped} dropped); "
            f"view at https://ui.perfetto.dev or render with "
            f"python -m repro.obs.report {args.trace} --summary")


if __name__ == "__main__":
    main()
