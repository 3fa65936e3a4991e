"""Observability subsystem (``repro.obs``): tracer ring + export schema,
cross-thread stager span ordering, metrics registry semantics, the
warn-once sampler-overflow watch, driver/profiler/report integration,
rank-trace merging, and the serving loop's virtual-clock lanes.

The 2-rank *fleet* trace test (real processes exporting per-rank files
the supervisor merges) is ``multihost``-marked like the rest of the
fleet suite; ``tools/trace_smoke.py`` additionally drives the full
``train_gnn --trace`` path in CI.
"""
import json
import sys
import textwrap
import threading

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.partition import build_layout, partition_graph
from repro.data.synthetic_graph import make_power_law_graph
from repro.models.gnn import GNNConfig, gnn_loss, init_gnn_params
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.obs.metrics import MetricsRegistry
from repro.obs.profile import STAGES, profile_stages
from repro.obs.scopes import merge, op_scopes, parts
from repro.obs.report import (render_share_table, span_summary,
                              stage_shares)
from repro.obs.trace import Tracer, merge_traces, validate_trace
from repro.optim import init_opt_state
from repro.pipeline import (Pipeline, PipelineSpec, PlanSpec,
                            PrefetchSpec, SamplerSpec)

P_ = 4


@pytest.fixture(scope="module")
def world():
    ds = make_power_law_graph(1200, 6, num_features=8, num_classes=4,
                              seed=0)
    assign = partition_graph(ds.graph, P_, ds.labeled_mask, seed=0)
    layout = build_layout(ds.graph, ds.features, ds.labels, assign, P_)
    cfg = GNNConfig(in_dim=8, hidden_dim=8, num_classes=4, num_layers=2,
                    fanouts=(3, 3), dropout=0.0)
    params = init_gnn_params(jax.random.key(1), cfg)
    return ds, layout, cfg, params


def _spec(scheme="hybrid", depth=0, **prefetch_kw):
    return PipelineSpec(
        plan=PlanSpec(num_parts=P_, scheme=scheme),
        sampler=SamplerSpec(fanouts=(3, 3), backend="reference"),
        prefetch=PrefetchSpec(depth=depth, **prefetch_kw))


def _loss_fn(cfg):
    def loss_fn(p, mfgs, h_src, labels, valid):
        return gnn_loss(p, mfgs, h_src, labels, valid, cfg)
    return loss_fn


@pytest.fixture(autouse=True)
def _no_leaked_tracer():
    """Every test leaves the module-global tracer uninstalled."""
    yield
    obs_trace.stop(export=False)


# --------------------------------------------------------------------------
# tracer core
# --------------------------------------------------------------------------

def test_tracer_records_spans_with_cat_and_args():
    t = Tracer(capacity=16)
    with t.span("outer", cat="driver", step=3):
        with t.span("inner", cat="driver"):
            pass
    assert t.num_recorded == 2 and t.dropped == 0
    evs = [e for e in t.events() if e["ph"] == "X"]
    # inner closes first: ring order is completion order
    assert [e["name"] for e in evs] == ["inner", "outer"]
    outer = evs[1]
    assert outer["cat"] == "driver" and outer["args"] == {"step": 3}
    assert outer["dur"] >= evs[0]["dur"]


def test_tracer_ring_wraps_and_counts_drops(tmp_path):
    t = Tracer(capacity=4)
    for i in range(7):
        with t.span(f"s{i}"):
            pass
    assert t.num_recorded == 4 and t.dropped == 3
    names = [e["name"] for e in t.events() if e["ph"] == "X"]
    assert names == ["s3", "s4", "s5", "s6"]     # oldest dropped
    meta = [e for e in t.events() if e["name"] == "trace_ring_dropped"]
    assert meta and meta[0]["args"]["dropped"] == 3
    path = tmp_path / "wrap.json"
    n = t.export(str(path))
    assert validate_trace(str(path)) == n


def test_module_level_span_is_noop_when_off():
    assert obs_trace.active_tracer() is None
    # no tracer, no profiler session: the shared no-op, nothing built
    assert obs_trace.span("ignored", cat="driver", step=1) \
        is obs_trace._NULL_SPAN
    before = {k: list(v) for k, v in obs_trace.profiled().spans.items()}
    with obs_trace.span("ignored", cat="driver"):
        pass                                     # must not raise
    assert obs_trace.profiled().spans == before  # nothing noted
    t = obs_trace.start(None)
    with obs_trace.span("seen"):
        pass
    assert obs_trace.stop(export=False) is t
    assert t.num_recorded == 1


def test_threads_get_their_own_tracks():
    t = Tracer()
    done = threading.Event()

    def worker():
        with t.span("worker-span"):
            done.wait(1.0)

    th = threading.Thread(target=worker, name="stager-test")
    th.start()
    with t.span("main-span"):
        pass
    done.set()
    th.join()
    evs = {e["name"]: e for e in t.events() if e["ph"] == "X"}
    assert evs["worker-span"]["tid"] != evs["main-span"]["tid"]
    tnames = {e["args"]["name"] for e in t.events()
              if e["name"] == "thread_name"}
    assert "stager-test" in tnames


# --------------------------------------------------------------------------
# stager integration: worker-thread spans, in order
# --------------------------------------------------------------------------

def test_stager_thread_spans_land_in_order(world):
    from repro.pipeline.staging import SeedStager
    from repro.pipeline.prefetch import SeedStream

    ds, layout, cfg, params = world
    pipe = Pipeline.from_layout(layout, _spec(depth=1))
    tracer = obs_trace.start(None)
    stager = SeedStager(SeedStream(pipe, batch=8), depth=1, lead=2)
    try:
        for k in range(4):
            stager.get(k)
    finally:
        stager.close()
    obs_trace.stop(export=False)
    produces = [e for e in tracer.events()
                if e["ph"] == "X" and e["name"] == "stager/produce"]
    assert len(produces) >= 4
    # all on the stager thread's track, one track only
    assert len({e["tid"] for e in produces}) == 1
    main_gets = [e for e in tracer.events()
                 if e["ph"] == "X" and e["name"] == "stager/get"]
    assert main_gets and all(e["tid"] != produces[0]["tid"]
                             for e in main_gets)
    # the worker annotates its own timeline in step order
    steps = [e["args"]["step"] for e in produces]
    assert steps == sorted(steps)
    ts = [e["ts"] for e in produces]
    assert ts == sorted(ts)
    # produce spans nest the argsort + H2D children on the same track
    kids = {e["name"] for e in tracer.events()
            if e["ph"] == "X" and e.get("tid") == produces[0]["tid"]}
    assert "stager/seeds_host" in kids and "stager/h2d" in kids


# --------------------------------------------------------------------------
# metrics registry
# --------------------------------------------------------------------------

def test_registry_counter_gauge_histogram():
    reg = MetricsRegistry()
    reg.counter("bytes").add(10)
    reg.counter("bytes").add(5)
    reg.gauge("hit_rate").set(0.25)
    for v in (1.0, 2.0, 3.0, 4.0):
        reg.histogram("lat").observe(v)
    snap = reg.snapshot()
    assert snap["bytes"] == 15
    assert snap["hit_rate"] == 0.25
    assert snap["lat"]["count"] == 4 and snap["lat"]["mean"] == 2.5
    with pytest.raises(ValueError):
        reg.counter("bytes").add(-1)             # counters are monotonic
    with pytest.raises(TypeError):
        reg.gauge("bytes")                       # name/type conflict


def test_registry_delta_semantics():
    reg = MetricsRegistry()
    reg.counter("c").add(3)
    since = reg.snapshot()
    reg.counter("c").add(4)
    reg.gauge("g").set(7.0)
    d = reg.delta(since)
    assert d["c"] == 4                           # counter: difference
    assert d["g"] == 7.0                         # gauge: current value


def test_observe_step_absorbs_and_warns_once():
    reg = MetricsRegistry()
    clean = {"sampling_utilized_bytes": np.float32(100.0),
             "feature_utilized_bytes": np.float32(200.0),
             "cache_hit_rate": np.float32(0.5),
             "sampler_window_overflow": np.float32(0.0)}
    reg.observe_step(clean, step=0)
    snap = reg.snapshot()
    assert snap["feature_utilized_bytes"] == 200.0
    assert snap["steps_observed"] == 1

    bad = dict(clean, sampler_window_overflow=np.float32(9.0))
    bad["sampler_window_overflow_per_level"] = np.asarray([2.0, 7.0])
    with pytest.warns(RuntimeWarning) as rec:
        reg.observe_step(bad, step=3)
    msg = str(rec[0].message)
    assert "worst level 1" in msg and "7" in msg and "step 3" in msg
    # ...and only once per registry, however often overflow recurs
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error")
        reg.observe_step(bad, step=4)
    assert reg.snapshot()["sampler_window_overflow"] == 18.0


def test_median_wall_syncs_and_feeds_histogram():
    reg = MetricsRegistry()
    calls = []
    dt = obs_metrics.median_wall(lambda: calls.append(1), warmup=1,
                                 iters=3, histogram=reg.histogram("t"))
    assert dt >= 0 and len(calls) == 4
    assert reg.snapshot()["t"]["count"] == 3


# --------------------------------------------------------------------------
# driver + profiler + report integration
# --------------------------------------------------------------------------

@pytest.mark.parametrize("depth", [0, 1])
def test_driver_steps_are_traced(world, depth):
    ds, layout, cfg, params = world
    pipe = Pipeline.from_layout(layout, _spec(depth=depth))
    tracer = obs_trace.start(None)
    with pipe.train_driver(_loss_fn(cfg), batch=8, lr=0.01) as driver:
        opt = init_opt_state(params, kind="adamw")
        p = params
        for k in range(3):
            p, opt, loss, _ = driver.step(p, opt, k)
    obs_trace.stop(export=False)
    evs = [e for e in tracer.events() if e["ph"] == "X"]
    steps = [e for e in evs if e["name"] == "driver/step"]
    assert len(steps) == 3
    assert [e["args"]["step"] for e in steps] == [0, 1, 2]
    assert all(e["cat"] == "driver" for e in steps)
    names = {e["name"] for e in evs}
    if depth == 0:
        assert "driver/train_step" in names
    else:
        assert {"prefetch/prepare", "prefetch/consume"} <= names
    # live spans never use the report's fenced stage cats
    assert not any(e.get("cat") in STAGES for e in evs)


def _host_events(logdir):
    """``(name, start_ns, end_ns, stats)`` of every host event in the
    profile written under ``logdir``."""
    from jax.profiler import ProfileData

    path = next(logdir.rglob("*.xplane.pb"))
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
             dict(ev.stats))
            for plane in ProfileData.from_file(str(path)).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]


@pytest.mark.parametrize("ring", [False, True],
                         ids=["profiler_only", "with_tracer"])
def test_driver_spans_land_on_the_profiler_trace(world, tmp_path, ring):
    """The program's spans are profiler annotations: ``driver/seeds``
    nests in ``driver/step`` (which carries ``step=k``) on the trace's
    clock, whether or not a ring tracer is installed."""
    ds, layout, cfg, params = world
    pipe = Pipeline.from_layout(layout, _spec(depth=1))
    tracer = obs_trace.start(None) if ring else None
    record = obs_trace.profiled()
    record.clear()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with pipe.train_driver(_loss_fn(cfg), batch=8, lr=0.01) as driver:
        p, opt = params, init_opt_state(params, kind="adamw")
        p, opt, loss, _ = driver.step(p, opt, 0)       # compiles
        assert not record.spans and not record.steps
        with jax.profiler.trace(str(tmp_path), profiler_options=options):
            for k in (1, 2):
                p, opt, loss, _ = driver.step(p, opt, k)
            jax.block_until_ready(loss)
        assert not record.scopes                # published on close
    obs_trace.stop(export=False)
    events = _host_events(tmp_path)
    steps = [e for e in events if e[0] == "driver/step"]
    assert sorted(e[3]["step"] for e in steps) == [1, 2]
    assert all(e[3]["mode"] == "double_buffer" for e in steps)
    seeds = [e for e in events if e[0] == "driver/seeds"]
    assert len(seeds) == 2
    for _, s, e, _ in seeds:
        assert sum(a <= s and e <= b for _, a, b, _ in steps) == 1
    assert {"driver/runner_step", "prefetch/prepare",
            "prefetch/consume"} <= {e[0] for e in events}
    if ring:
        recorded = [e for e in tracer.events() if e.get("ph") == "X"
                    and e["name"] == "driver/step"]
        assert [e["args"]["step"] for e in recorded] == [0, 1, 2]
    # what the program noted of itself while the session collected
    assert record.spans["driver/seeds"][1] == 2
    assert record.spans["driver/step"][1] == 2
    spans_s = sum(e - s for name, s, e, _ in events
                  if name == "driver/step") * 1e-9
    assert 0 < record.spans["driver/step"][0] <= spans_s
    assert len(record.steps) == 2
    assert all(np.asarray(m["frontier_live"]).shape == (len(cfg.fanouts),)
               for m in record.steps)
    assert any("relabel" in parts(op) for op in record.scopes.values())
    record.clear()


@pytest.mark.parametrize("depth", [0, 1])
def test_op_scopes_name_the_stages_of_the_fused_step(world, depth):
    ds, _, cfg, params = world
    spec = PipelineSpec.from_scheme(
        "hybrid+fused", num_parts=1, fanouts=cfg.fanouts,
        executor="shard_map", partitioner="random", prefetch_depth=depth)
    pipe = Pipeline.build(ds.graph, ds.features, ds.labels, spec)
    with pipe.train_driver(_loss_fn(cfg), batch=8, lr=0.01) as driver:
        assert driver.op_scopes() == {}                 # nothing ran yet
        driver.step(params, init_opt_state(params, kind="adamw"))
        scopes = driver.op_scopes()
        top = driver.op_scopes(top_level=True)
    # the top-level ops (the ENTRY computation's): the relabel's while,
    # not the ops of its body
    assert top.items() <= scopes.items() and len(top) < len(scopes)
    assert any(name.startswith("while") and "relabel" in parts(op)
               for name, op in top.items())
    assert any(name.startswith("while") and "relabel" in parts(op)
               for name, op in scopes.items())
    assert any("/transpose(jvp(forward))/" in op for op in scopes.values())
    for part in ([f"level{i}" for i in range(len(cfg.fanouts))]
                 + [f"layer{i}" for i in range(cfg.num_layers)]
                 + ["fused_sample", "fetch", "optimizer"]):
        assert any(part in parts(op) for op in scopes.values()), part
    staged = [op for op in scopes.values() if parts(op)
              & {"sample", "fetch", "forward", "optimizer"}]
    assert len(staged) >= 0.9 * len(scopes)


def test_frontier_counters_match_a_numpy_count(world):
    """``frontier_live`` counts each level's distinct frontier nodes
    (seeds and their draws) over all workers; ``frontier_slots`` the
    slots relabel keeps for them."""
    from repro.core.sampler import level_salt, sample_neighbors

    ds, layout, cfg, params = world
    pipe = Pipeline.from_layout(layout, _spec())
    seeds = pipe.seeds(8, 1)
    _, _, metrics = pipe.step_fn(_loss_fn(cfg))(params, seeds,
                                                jnp.uint32(3))
    live = np.zeros(len(cfg.fanouts))
    slots = np.zeros(len(cfg.fanouts))
    for p in range(P_):
        frontier = np.asarray(seeds[p])
        for depth, fanout in enumerate(cfg.fanouts):
            samples, valid = sample_neighbors(
                layout.graph, jnp.asarray(frontier), fanout,
                level_salt(3, depth))
            nodes = np.union1d(frontier[frontier >= 0],
                               np.asarray(samples)[np.asarray(valid)])
            live[depth] += nodes.size
            slots[depth] += frontier.size * (fanout + 1)
            frontier = np.full(frontier.size * (fanout + 1), -1, np.int32)
            frontier[:nodes.size] = nodes
    np.testing.assert_array_equal(np.asarray(metrics["frontier_live"]),
                                  live)
    np.testing.assert_array_equal(np.asarray(metrics["frontier_slots"]),
                                  slots)
    assert (live < slots).all()


def test_op_scopes_parser_and_merge():
    text = textwrap.dedent("""\
        %body.7 (p.1: s32[8]) -> s32[8] {
          ROOT %gather.3 = s32[8]{0} gather(%p.1), metadata={op_name="jit(f)/sample/level2/relabel/while/body/gather"}
        }

        ENTRY %main {
          %p = s32[8]{0} parameter(0)
          %while.81 = s32[8]{0} while(%p), condition=%c, body=%b, metadata={op_name="jit(f)/sample/level2/relabel/while" source_file="x.py" source_line=3}
          ROOT %fusion.2 = f32[8]{0} fusion(%p), kind=kLoop, calls=%g, metadata={op_name="jit(f)/transpose(jvp(forward))/layer0/mul"}
        }""")
    scopes = op_scopes(text)
    assert scopes == {
        "while.81": "jit(f)/sample/level2/relabel/while",
        "fusion.2": "jit(f)/transpose(jvp(forward))/layer0/mul",
        "gather.3": "jit(f)/sample/level2/relabel/while/body/gather"}
    top = op_scopes(text, top_level=True)
    assert top == {name: scopes[name] for name in ("while.81", "fusion.2")}
    other = {"while.81": "jit(g)/optimizer/while", "add.1": "jit(g)/add"}
    merged = merge([scopes, other])
    assert merged == {"fusion.2": scopes["fusion.2"], "add.1": "jit(g)/add",
                      "gather.3": scopes["gather.3"]}


def test_profile_stages_share_and_report_round_trip(world, tmp_path):
    ds, layout, cfg, params = world
    pipe = Pipeline.from_layout(layout, _spec())
    path = tmp_path / "stages.json"
    obs_trace.start(str(path))
    prof = profile_stages(pipe, _loss_fn(cfg), params, batch=8, steps=2,
                          warmup=1, arm="hybrid")
    obs_trace.stop()
    assert set(prof["share"]) == set(STAGES)
    assert all(v > 0 for v in prof["share"].values())
    assert abs(sum(prof["share"].values()) - 1.0) < 1e-9
    assert prof["step_s"] == pytest.approx(
        prof["sampling_s"] + prof["feature_s"] + prof["compute_s"])

    validate_trace(str(path))
    with open(path) as f:
        trace = json.load(f)
    groups = stage_shares(trace)
    assert list(groups) == ["hybrid"]
    g = groups["hybrid"]
    assert g["spans"] == 2 * len(STAGES)
    for st in STAGES:
        assert g["share"][st] == pytest.approx(prof["share"][st],
                                               abs=0.25)
    table = render_share_table(groups)
    assert "| hybrid |" in table and "sampling" in table
    summary = span_summary(trace)
    assert summary["profile/sampling"]["count"] == 2


def test_profile_stages_rejects_external_row_stores(world):
    ds, layout, cfg, params = world
    spec = PipelineSpec(
        plan=PlanSpec(num_parts=P_, scheme="hybrid",
                      feature_store="staged"),
        sampler=SamplerSpec(fanouts=(3, 3), backend="reference"),
        prefetch=PrefetchSpec(depth=1))
    pipe = Pipeline.from_layout(layout, spec)
    with pytest.raises(ValueError, match="staged"):
        profile_stages(pipe, _loss_fn(cfg), params, batch=8)


def test_trainer_context_manager(world):
    from repro.train.loop import GNNTrainer
    ds, layout, cfg, params = world
    with GNNTrainer(layout, cfg, scheme="hybrid", batch_per_worker=8,
                    prefetch_depth=1) as tr:
        out = tr.run_epoch(0, steps_per_epoch=2)
        assert np.isfinite(out["loss"])


# --------------------------------------------------------------------------
# serve: virtual-clock request lanes
# --------------------------------------------------------------------------

def test_serve_emits_virtual_clock_lanes(world):
    from repro.serve import GNNServer, Predictor
    from repro.serve.server import SERVE_VPID

    ds, layout, cfg, params = world
    pipe = Pipeline.from_layout(layout, _spec())
    predictor = Predictor(pipe, params, cfg, buckets=(1, 4))
    tracer = obs_trace.start(None)
    server = GNNServer(predictor, buckets=(1, 4), max_delay=1e-3)
    arrivals = [(0.000, 3), (0.0005, 9), (0.002, 11)]
    stats = server.run(arrivals, warmup=True)
    obs_trace.stop(export=False)
    assert stats.num_requests == 3
    evs = tracer.events()
    lanes = [e for e in evs if e["ph"] == "X" and e["pid"] == SERVE_VPID]
    names = {e["name"] for e in lanes}
    assert {"serve/queue_wait", "serve/batch_delay",
            "serve/service"} <= names
    # one lane (tid) per request, in arrival order
    waits = sorted((e for e in lanes if e["name"] == "serve/queue_wait"),
                   key=lambda e: e["tid"])
    assert [e["tid"] for e in waits] == [0, 1, 2]
    assert all(e["dur"] >= 0 for e in lanes)
    # real-clock predict spans live on the real process, not the lanes
    predicts = [e for e in evs if e["ph"] == "X"
                and e["name"] == "serve/predict"]
    assert predicts and all(e["pid"] != SERVE_VPID for e in predicts)
    procs = {e["pid"]: e["args"]["name"] for e in evs
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert "virtual clock" in procs[SERVE_VPID]


# --------------------------------------------------------------------------
# merging rank traces
# --------------------------------------------------------------------------

def _rank_trace(path, pid, spans, virtual_pid=None):
    t = Tracer(pid=pid, process_name=f"worker{pid}")
    for name in spans:
        with t.span(name, cat="driver"):
            pass
    if virtual_pid is not None:
        t.name_process(virtual_pid, "lanes")
        t.event("lane", 0.0, 1e-3, tid=0, pid=virtual_pid, cat="serve")
    t.export(str(path))


def test_merge_traces_rank_as_pid(tmp_path):
    a, b = tmp_path / "a.json", tmp_path / "b.json"
    _rank_trace(a, pid=0, spans=["driver/step"], virtual_pid=100)
    _rank_trace(b, pid=0, spans=["driver/step", "driver/seeds"])
    out = tmp_path / "fleet.json"
    merged = merge_traces([str(a), str(b)], str(out))
    validate_trace(str(out))
    xs = [e for e in merged["traceEvents"] if e["ph"] == "X"]
    by_pid = {}
    for e in xs:
        by_pid.setdefault(e["pid"], []).append(e["name"])
    # rank files' primary pids remapped to 0 and 1
    assert by_pid[0] == ["driver/step"]
    assert sorted(by_pid[1][:2]) == ["driver/seeds", "driver/step"]
    # rank 0's virtual pid 100 shifted into a rank-unique range >= 2
    (vpid,) = [p for p in by_pid if p not in (0, 1)]
    assert vpid >= 2 and by_pid[vpid] == ["lane"]
    names = {e["args"]["name"] for e in merged["traceEvents"]
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert {"rank0", "rank1", "lanes"} <= names


def test_merge_traces_rejects_corrupt_rank_file(tmp_path):
    good, bad = tmp_path / "g.json", tmp_path / "b.json"
    _rank_trace(good, pid=0, spans=["driver/step"])
    bad.write_text(json.dumps({"traceEvents": [{"ph": "X"}]}))
    with pytest.raises(ValueError, match="name"):
        merge_traces([str(good), str(bad)], None)


# --------------------------------------------------------------------------
# 2-rank fleet: per-rank export + supervisor merge (multihost-marked)
# --------------------------------------------------------------------------

@pytest.mark.multihost
def test_two_rank_fleet_merged_trace(tmp_path, subproc):
    from repro.launch import multihost

    base = str(tmp_path / "fleet.json")
    script = textwrap.dedent(f"""
        import jax, jax.numpy as jnp
        from repro.launch import multihost
        from repro.obs import trace as obs_trace

        rank, num = multihost.init_from_env()
        t = obs_trace.start(multihost.rank_trace_path({base!r}, rank),
                            pid=rank, process_name=f"rank{{rank}}")
        with obs_trace.span("driver/step", cat="driver", step=0):
            out = jax.jit(lambda x: x * 2)(jnp.ones(4))
            jax.block_until_ready(out)
        obs_trace.stop()
        print("rank", rank, "done")
    """)
    multihost.launch([sys.executable, "-c", script], num_procs=2,
                     timeout=300)
    merged = multihost.merge_rank_traces(base, 2)
    validate_trace(base)
    step_pids = {e["pid"] for e in merged["traceEvents"]
                 if e["ph"] == "X" and e["name"] == "driver/step"}
    assert step_pids == {0, 1}
    names = {e["args"]["name"] for e in merged["traceEvents"]
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert {"rank0", "rank1"} <= names
