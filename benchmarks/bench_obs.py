"""Observability arms: tracing overhead + the Figure-1 stage breakdown.

Two questions about ``repro.obs`` itself, answered with records under
``experiments/obs``:

  overhead   Is tracing cheap enough to leave on?  The same
             ``Pipeline.train_driver`` loop is timed with the tracer
             off and with an *unfenced* tracer recording driver /
             prefetch spans; the acceptance budget is <= 2% steps/s
             regression (the fenced mode is excluded by construction —
             it exists to destroy overlap, see docs/architecture.md).
  breakdown  The paper's Figure-1 share table: the fenced
             sampling / feature / compute split of one step
             (``repro.obs.profile``) per placement scheme, all three
             schemes' spans recorded into ONE trace
             (``experiments/obs/stage_trace.json``) so
             ``python -m repro.obs.report experiments/obs/stage_trace.json``
             reproduces the table from the artifact alone.

  PYTHONPATH=src python -m benchmarks.run obs
"""
import json
import os

import jax

from benchmarks.common import dataset_columns, emit, time_driver
from repro.core.partition import build_layout, partition_graph
from repro.data.synthetic_graph import make_power_law_graph
from repro.models.gnn import GNNConfig, gnn_loss, init_gnn_params
from repro.obs import trace as obs_trace
from repro.obs.profile import profile_stages
from repro.obs.report import render_share_table, stage_shares
from repro.optim import init_opt_state
from repro.pipeline import Pipeline, PipelineSpec

SCHEMES = ("vanilla", "hybrid", "hybrid_partial(0.25)")
EXECUTOR = "vmap"
DEPTH = 1
OUT_DIR = os.path.join("experiments", "obs")
TRACE_PATH = os.path.join(OUT_DIR, "stage_trace.json")
OVERHEAD_TRACE = os.path.join(OUT_DIR, "overhead_trace.json")


def _tag(scheme: str) -> str:
    return scheme.replace("(", "").replace(")", "").replace(",", "_")


def _overhead_arm(layout, cfg, loss_fn, ds_cols, P, batch, steps):
    """steps/s with the tracer off vs on (unfenced), same driver path."""
    spec = PipelineSpec.from_scheme(
        "hybrid", num_parts=P, fanouts=cfg.fanouts, executor=EXECUTOR,
        fused_backend="reference", prefetch_depth=DEPTH)
    pipe = Pipeline.from_layout(layout, spec)
    dt = {}
    for traced in (False, True):
        if traced:
            obs_trace.start(OVERHEAD_TRACE, process_name="bench_obs")
        try:
            with pipe.train_driver(loss_fn, batch=batch,
                                   lr=6e-3) as driver:
                params = init_gnn_params(jax.random.key(0), cfg)
                opt = init_opt_state(params, kind="adamw")
                dt[traced], _ = time_driver(driver, params, opt,
                                            steps=steps, repeats=6)
        finally:
            if traced:
                obs_trace.stop()
        tag = "on" if traced else "off"
        emit(f"obs/P{P}/hybrid/trace_{tag}/steps_per_s", 1.0 / dt[traced],
             f"executor={EXECUTOR} prefetch={DEPTH} tracing={tag} "
             f"(unfenced)")
    overhead = dt[True] / dt[False] - 1.0
    emit(f"obs/P{P}/hybrid/trace_overhead", 100.0 * overhead,
         "percent steps/s cost of unfenced tracing; budget <= 2%")
    rec = {
        "workload": "obs-overhead", "scheme": "hybrid",
        "executor": EXECUTOR, "prefetch_depth": DEPTH, "workers": P,
        "batch": batch, "fenced": False,
        "steps_per_s_untraced": 1.0 / dt[False],
        "steps_per_s_traced": 1.0 / dt[True],
        "overhead_frac": overhead,
        "within_2pct_budget": bool(overhead <= 0.02),
        **ds_cols,
    }
    with open(os.path.join(OUT_DIR, "obs__overhead.json"), "w") as f:
        json.dump(rec, f, indent=1)


def run(ds, P=4, batch=128, steps=6):
    assign = partition_graph(ds.graph, P, ds.labeled_mask, seed=0)
    layout = build_layout(ds.graph, ds.features, ds.labels, assign, P)
    cfg = GNNConfig(in_dim=ds.features.shape[1], hidden_dim=32,
                    num_classes=ds.num_classes, num_layers=2,
                    fanouts=(5, 5), dropout=0.0)
    ds_cols = dataset_columns(ds)

    def loss_fn(p, mfgs, h_src, labels, valid):
        return gnn_loss(p, mfgs, h_src, labels, valid, cfg)

    os.makedirs(OUT_DIR, exist_ok=True)
    _overhead_arm(layout, cfg, loss_fn, ds_cols, P, batch, steps)

    # one trace, all schemes: the report CLI groups the fenced profile
    # spans by their "arm" tag into the Figure-1 share table
    obs_trace.start(TRACE_PATH, process_name="bench_obs")
    try:
        params = init_gnn_params(jax.random.key(0), cfg)
        for scheme in SCHEMES:
            spec = PipelineSpec.from_scheme(
                scheme, num_parts=P, fanouts=cfg.fanouts,
                executor=EXECUTOR, fused_backend="reference")
            pipe = Pipeline.from_layout(layout, spec)
            prof = profile_stages(pipe, loss_fn, params, batch=batch,
                                  arm=scheme)
            for st in ("sampling", "feature", "compute"):
                emit(f"obs/P{P}/{_tag(scheme)}/{st}_share",
                     100.0 * prof["share"][st],
                     f"fenced stage profile, step {prof['step_s']*1e3:.1f}"
                     f" ms unoverlapped")
            rec = {
                "workload": "obs-stage-breakdown", "scheme": scheme,
                "arm": scheme, "executor": EXECUTOR, "workers": P,
                "batch": batch, "steps": prof["steps"],
                "sampling_s": prof["sampling_s"],
                "feature_s": prof["feature_s"],
                "compute_s": prof["compute_s"],
                "step_s": prof["step_s"],
                "stage_breakdown": {k: round(v, 4)
                                    for k, v in prof["share"].items()},
                "trace": TRACE_PATH,
                **ds_cols,
            }
            with open(os.path.join(
                    OUT_DIR, f"obs__breakdown__{_tag(scheme)}.json"),
                    "w") as f:
                json.dump(rec, f, indent=1)
    finally:
        obs_trace.stop()
    # round-trip: the share table re-derived from the exported artifact
    with open(TRACE_PATH) as f:
        print(render_share_table(stage_shares(json.load(f))))


def main() -> None:
    # same mid-size skewed graph as the staging sweep: big enough that
    # sampling and feature stages are both visible slices of the step
    ds = make_power_law_graph(60_000, 6, num_features=32, num_classes=8,
                              seed=0)
    run(ds)


if __name__ == "__main__":
    main()
