"""Smoke run of FastSample's training path on a TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # four chips: the shard_map path only

One chip: trains the paper's products configuration at full width
(``repro.configs.graphsage_paper.PRODUCTS``: 100 features, hidden 256, 47
classes, 3 layers, fanouts 15/10/5; dropout is off, as in
``launch/train_gnn.py``) for a few steps at 1,000 seeds per batch, P=1,
scheme ``hybrid+fused``, feature store ``exchange``, on a products-shaped
graph generated from a seed (powerlaw, 2.5M nodes, ~124M edges).  It goes
through ``Pipeline.build_from_source`` and ``Pipeline.train_driver``, the
calls ``launch/train_gnn.py`` makes, and checks on the chip that:

  * every loss is finite;
  * the fused Pallas sampler's MFGs for the first batch are bit-identical
    to the ``reference`` backend's on the window-truncated graph, and its
    overflow count matches the hubs above the window;
  * one ``gather_rows`` call at real width is bit-identical to its oracle.

Four chips (``--four-chips``): ``shard_map`` at P=4 under ``vanilla`` and
``hybrid+fused`` at the same widths.  The two loss trajectories must be
bit-identical, with 6 and 2 traced ``all_to_all`` rounds, and the feature
shards and the staged seeds must span 4 distinct devices.

The script exits non-zero unless JAX's first device is a TPU.  Times it
prints are smoke timings, not benchmarks.  Its last line is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import functools
import json
import math
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# ogbn-products (PAPER_TABLE1): 2.5M nodes, 124M edges -> average degree
# ~50; its 8% training split is what the labeled seeds are drawn from
NODES = 2_500_000
AVG_DEGREE = 50
SPLIT = "random(0.08)"
BATCH = 1_000
# heaviest in-degree near the real graph's (~17k): Chung-Lu weights
# Pareto(2.3) + 1 give an expected maximum of ~50 * 2.5M**(1/2.3) / 1.77
PRODUCTS_ALPHA = 2.3
# the four-chip comparison needs every in-degree within the fused kernel's
# window (above it the kernel draws from the first 2048 neighbors and
# vanilla from all, by design): Pareto(5) puts the expected maximum
# near 800
FOUR_CHIP_ALPHA = 5.0
# the pinned-cache gather at the paper's sizes: a 262,144-row table of
# products features, one bottom-level frontier of ids
GATHER_ROWS, GATHER_IDS = 262_144, 1_056_000
SEED = 0


def _pipeline(*, nodes, avg_degree, alpha, num_parts, scheme,
              staging=False, layout=None):
    """The products-shaped training pipeline (``layout`` reuses another
    pipeline's partitioning instead of generating the graph again)."""
    from repro.configs.graphsage_paper import PRODUCTS
    from repro.data import DataSpec
    from repro.pipeline import Pipeline, PipelineSpec

    data = DataSpec(source=f"powerlaw({alpha:g})", num_nodes=nodes,
                    avg_degree=avg_degree, num_features=PRODUCTS.in_dim,
                    num_classes=PRODUCTS.num_classes, split=SPLIT,
                    seed=SEED)
    # every partitioner gives P=1 one part; at P=4 the hash baseline
    # keeps the host build within the run's time limit
    spec = PipelineSpec.from_scheme(
        scheme, num_parts=num_parts, fanouts=PRODUCTS.fanouts,
        partitioner="random", executor="shard_map",
        feature_store="exchange", staging=staging, data=data)
    if layout is not None:
        return Pipeline.from_layout(layout, spec)
    return Pipeline.build_from_source(spec=spec)


def train(pipe, *, steps, batch):
    """A few optimizer steps through ``pipe.train_driver``.  Returns the
    losses, per-step wall seconds, traced rounds and the staged seeds'
    sharding (None without staging)."""
    import jax

    from repro.configs.graphsage_paper import PRODUCTS
    from repro.models.gnn import gnn_loss, init_gnn_params
    from repro.optim import init_opt_state

    def loss_fn(p, mfgs, h_src, labels, valid):
        return gnn_loss(p, mfgs, h_src, labels, valid, PRODUCTS)

    params = init_gnn_params(jax.random.key(SEED), PRODUCTS)
    opt_state = init_opt_state(params, kind="adamw")
    losses, seconds = [], []
    with pipe.train_driver(loss_fn, batch=batch, lr=0.006,
                           optimizer="adamw", grad_clip=1.0) as driver:
        for _ in range(steps):
            t0 = time.perf_counter()
            params, opt_state, loss, _ = driver.step(params, opt_state)
            losses.append(float(loss))          # waits for the step
            seconds.append(time.perf_counter() - t0)
        seed_sharding = getattr(driver.stager, "sharding", None)
    return {"losses": losses, "seconds": seconds,
            "rounds": pipe.counter.rounds, "seed_sharding": seed_sharding}


def check_fused_mfgs(pipe, *, batch):
    """The fused kernel's MFGs for the first batch against the
    ``reference`` backend's on the window-truncated graph: every field
    bit-identical, and the kernel's overflow count equal to the frontier
    nodes above the window.  Returns (bottom frontier size, overflow)."""
    import jax
    import jax.numpy as jnp

    from repro.core import dist
    from repro.core.sampler import resolve_backend
    from repro.kernels.fused_sample import MAX_DEG_WINDOW
    from repro.kernels.ops import fused_sample_level
    from repro.kernels.ref import window_truncated_graph

    fanouts = pipe.spec.sampler.fanouts
    graph = pipe.shards.replicated
    truncated = window_truncated_graph(graph, MAX_DEG_WINDOW)

    @jax.jit
    def fused(g, seeds, salt):
        sink = []
        level = functools.partial(fused_sample_level, overflow_sink=sink)
        mfgs = dist.hybrid_sample(g, seeds, fanouts, salt, level_fn=level)
        return mfgs, sum(sink)

    @jax.jit
    def reference(g, seeds, salt):
        return dist.hybrid_sample(g, seeds, fanouts, salt,
                                  level_fn=resolve_backend("reference"))

    # the first batch the training driver draws: step 0's seeds and salt
    seeds, salt = pipe.seeds(batch, 0)[0], jnp.uint32(0)
    got, overflow = fused(graph, seeds, salt)
    want = reference(truncated, seeds, salt)
    for level, (a, b) in enumerate(zip(got, want)):
        for name, x, y in zip(("dst_nodes", "src_nodes", "num_src", "edges",
                               "edge_mask", "indptr"),
                              jax.tree.leaves(a), jax.tree.leaves(b)):
            if not np.array_equal(np.asarray(x), np.asarray(y)):
                raise AssertionError(
                    f"fused MFG level {level} {name} differs from the "
                    f"reference backend's")
    deg = np.diff(np.asarray(graph.indptr))
    hubs = 0
    for mfg in got:
        frontier = np.asarray(mfg.dst_nodes)
        hubs += int((deg[frontier[frontier >= 0]] > MAX_DEG_WINDOW).sum())
    if int(overflow) != hubs:
        raise AssertionError(f"fused overflow count {int(overflow)} != "
                             f"{hubs} frontier nodes above the window")
    return int(np.sum(np.asarray(got[-1].src_nodes) >= 0)), hubs


def check_gather(pipe, *, rows, ids):
    """One ``gather_rows`` call on a (rows, D) slice of the feature table
    against ``gather_rows_reference``, bit for bit; ``ids`` includes
    padding (-1) and out-of-range ids."""
    import jax.numpy as jnp

    from repro.kernels.gather import gather_rows, gather_rows_reference

    table = pipe.shards.features[0, :rows]
    rng = np.random.default_rng(SEED)
    idx = jnp.asarray(rng.integers(-1, rows + rows // 64, ids), jnp.int32)
    got = np.asarray(gather_rows(table, idx))
    if not np.array_equal(got, np.asarray(gather_rows_reference(table,
                                                                 idx))):
        raise AssertionError("gather_rows differs from its oracle")
    return got.shape


def one_chip(*, nodes=NODES, avg_degree=AVG_DEGREE, batch=BATCH, steps=5,
             gather_rows=GATHER_ROWS, gather_ids=GATHER_IDS, say=print):
    """Train, then check the fused sampler and the row gather; raises on
    any failed check."""
    from repro.configs.graphsage_paper import PRODUCTS

    t0 = time.perf_counter()
    pipe = _pipeline(nodes=nodes, avg_degree=avg_degree,
                     alpha=PRODUCTS_ALPHA, num_parts=1,
                     scheme="hybrid+fused")
    graph = pipe.layout.graph
    cut = "none" if nodes == NODES else f"nodes {NODES:,} -> {nodes:,}"
    say(f"graph: powerlaw({PRODUCTS_ALPHA:g}) {graph.num_nodes:,} nodes, "
        f"{graph.num_edges:,} edges, max in-degree "
        f"{int(np.diff(np.asarray(graph.indptr)).max()):,} "
        f"(published 2.5M nodes / 124M edges; cut: {cut}); host build "
        f"{time.perf_counter() - t0:.1f} s")
    say(f"model: in {PRODUCTS.in_dim}, hidden {PRODUCTS.hidden_dim}, "
        f"classes {PRODUCTS.num_classes}, layers {PRODUCTS.num_layers}, "
        f"fanouts {PRODUCTS.fanouts}, batch {batch}, P=1, hybrid+fused, "
        f"feature store exchange")
    out = train(pipe, steps=steps, batch=batch)
    if not all(math.isfinite(x) for x in out["losses"]):
        raise AssertionError(f"non-finite loss: {out['losses']}")
    say(f"losses (finite): {out['losses']}")
    say(f"smoke timing, not a benchmark: first step {out['seconds'][0]:.2f}"
        f" s (compile included), later steps "
        f"{[round(s, 4) for s in out['seconds'][1:]]} s")
    frontier, hubs = check_fused_mfgs(pipe, batch=batch)
    say(f"fused MFGs of the first batch bit-identical to reference "
        f"(bottom frontier {frontier:,} nodes, {hubs} above the window)")
    shape = check_gather(pipe, rows=gather_rows, ids=gather_ids)
    say(f"gather_rows {shape} from a ({gather_rows:,}, "
        f"{PRODUCTS.in_dim}) table bit-identical to its oracle")
    return out


def four_chips(*, nodes=NODES, avg_degree=AVG_DEGREE, batch=BATCH, steps=3,
               say=print):
    """shard_map at P=4: vanilla and hybrid+fused must agree bit for bit,
    with 6 and 2 traced rounds, over 4 distinct devices."""
    from repro.kernels.fused_sample import MAX_DEG_WINDOW

    t0 = time.perf_counter()
    runs = {}
    layout = None
    for scheme, rounds in (("vanilla", 6), ("hybrid+fused", 2)):
        pipe = _pipeline(nodes=nodes, avg_degree=avg_degree,
                         alpha=FOUR_CHIP_ALPHA, num_parts=4, scheme=scheme,
                         staging=True, layout=layout)
        if layout is None:
            layout = pipe.layout
            max_deg = int(np.diff(np.asarray(layout.graph.indptr)).max())
            say(f"graph: powerlaw({FOUR_CHIP_ALPHA:g}) "
                f"{layout.graph.num_nodes:,} nodes, "
                f"{layout.graph.num_edges:,} edges, max in-degree "
                f"{max_deg:,}; host build {time.perf_counter() - t0:.1f} s")
            if max_deg > MAX_DEG_WINDOW:
                raise AssertionError(
                    f"max in-degree {max_deg} exceeds the fused window "
                    f"{MAX_DEG_WINDOW}: vanilla and hybrid+fused would "
                    f"differ by design")
        out = train(pipe, steps=steps, batch=batch)
        devices = {
            "features": pipe.shards.features.sharding.device_set,
            "seeds": out["seed_sharding"].device_set}
        for what, devs in devices.items():
            if len(devs) != 4:
                raise AssertionError(f"{scheme}: {what} on {len(devs)} "
                                     f"devices, want 4")
        if out["rounds"] != rounds:
            raise AssertionError(f"{scheme}: {out['rounds']} traced rounds,"
                                 f" want {rounds}")
        say(f"{scheme}: losses {out['losses']}, {out['rounds']} rounds, "
            f"features and seeds on 4 devices; smoke timing, not a "
            f"benchmark: steps {[round(s, 4) for s in out['seconds']]} s")
        runs[scheme] = out["losses"]
    if runs["vanilla"] != runs["hybrid+fused"]:
        raise AssertionError(f"loss trajectories differ: {runs}")
    say("vanilla and hybrid+fused loss trajectories bit-identical")
    return runs


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip shard_map comparison")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    want = 4 if args.four_chips else 1
    if devices[0].platform != "tpu" or len(devices) < want:
        print(f"chip_smoke: needs {want} TPU chip(s); JAX sees "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 1

    from repro.launch.cache import enable_compile_cache

    compile_s = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **_: compile_s.append(secs)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    print(f"jax {jax.__version__}, {len(devices)} x "
          f"{devices[0].device_kind}, compile cache "
          f"{enable_compile_cache()}")
    if args.four_chips:
        four_chips()
    else:
        one_chip()
    stats = devices[0].memory_stats() or {}
    print(f"backend compile {sum(compile_s):.1f} s over {len(compile_s)} "
          f"programs; peak device memory "
          f"{stats.get('peak_bytes_in_use', 0) / 2**30:.2f} GiB")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
