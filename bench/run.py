"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload products.train.b1000 --seed 7 \\
        --seconds 10 --trace 0

The cell, its configuration, its model, its traffic mix and its
per-layer metrics are found by name: ``BENCHMARK.json`` at the checkout's
root names the cell's configuration (``file``) and traffic mix; the
harness reads ``bench/traffic/<traffic>.json``,
``bench/limits/<workload>.json``, the model module
``bench/models/<model>.py`` that the configuration's ``model`` names, and
one reader ``bench/metrics/<metric>.py`` per per-layer metric.  Adding a
cell or a model adds files and entries; no file here changes.

A model module gives ``init_params(seed, config)`` (the float32 weights
the program and the reference both start from), ``program_loss(config)``
(the program's ``loss_fn(params, mfgs, h_src, labels, valid)``),
``reference_loss(params, h0, blocks, labels, valid, passes)`` (plain
``jax.numpy``, its matrix products through ``reference.matmul``) and
``step_flops(batch, fanouts, config)`` (forward plus backward FLOPs at
``flops.block_sizes``' nominal sizes).

``main`` first pins the host allocator's thresholds at glibc's static
defaults (``steady_allocator``).  The program runs at the matrix-product precision
its configuration states (``matmul_precision``, set with ``jax.default_matmul_precision``).

One run is one process.  Set-up (counted in ``setup_s``) loads the
cached graph (generating it on a checkout's first run), draws the
features, builds the program's ``Pipeline``, makes the weights on the
device from ``--seed`` (the model module's ``init_params``), and drives
the program's training driver through its first steps: three that the
reference checks, then the traffic's warm-up steps, so that every
program the window runs is compiled.  The
window then calls ``driver.step`` and waits for each step's loss, for
``--seconds`` seconds.  With ``--trace 1`` the window is recorded by
the JAX profiler and the per-layer metrics are read from the trace;
without it the end-to-end metrics are printed.  After the window the
peak device memory is read, the program's state is freed, and the
reference checks the three first steps (``compare.py``).

Standard output's last line is one JSON object; every other line goes to
standard error, whose last lines are the compared numbers with their
limits.  The run refuses to measure (exit 3, no result) unless JAX's
first device is a TPU and there are as many as the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import contextlib
import ctypes
import gc
import importlib.util
import json
import math
import shutil
import statistics
import sys
import tempfile
import types
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(BENCH))

import compare
import graphgen
import peaks
import reference
import trace_reduce

COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration")
CHECKED_STEPS = 3


def say(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


# ------------------------------------------------------------------ cells

def load_cell(name: str, root: Path = ROOT,
              models_dir: Path | None = None) -> types.SimpleNamespace:
    """Everything that belongs to cell ``name``, found by name under
    ``root``: its ``BENCHMARK.json`` entry, configuration, model module
    (from ``models_dir``, by default ``bench/models`` under ``root``),
    traffic mix, limits and metric definitions."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    here = root / "bench"
    config = json.loads((root / entry["file"]).read_text())
    models_dir = Path(models_dir or here / "models")
    applies = lambda m: name in m.get("workloads", [name])
    return types.SimpleNamespace(
        name=name, chips=int(cell["chips"]), config=config,
        model=load_model(models_dir, config["model"]),
        traffic=json.loads(
            (here / "traffic" / f"{cell['traffic']}.json").read_text()),
        limits=json.loads((here / "limits" / f"{name}.json").read_text()),
        end_to_end=[m for m in bench["end_to_end"] if applies(m)],
        per_layer=[m for m in bench["per_layer"] if applies(m)],
        metrics_dir=here / "metrics")


def _load(path: Path, prefix: str, name: str) -> types.ModuleType:
    spec = importlib.util.spec_from_file_location(
        prefix + name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_reader(metrics_dir: Path, name: str):
    """The ``read(ctx)`` function of ``<metrics_dir>/<name>.py``."""
    return _load(Path(metrics_dir) / f"{name}.py", "bench_metric_",
                 name).read


def load_model(models_dir: Path, name: str) -> types.ModuleType:
    """The model module ``<models_dir>/<name>.py``; a model with no
    module is an error that names the file."""
    path = Path(models_dir) / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(
            f"model {name!r} has no module: no file {path}")
    return _load(path, "bench_model_", name)


def base_salt(seed: int) -> int:
    """The seed stream's 32-bit base salt from ``--seed``: hashed, so
    neighbouring seeds share no steps."""
    x = np.array([int(seed) % 2**64], np.uint64)
    return int(graphgen.mix64(x)[0] >> np.uint64(32))


# ----------------------------------------------------------------- set-up

class CompileLog:
    """Compile (or compile-cache load) events, by phase of the run."""

    def __init__(self):
        self.phase = "setup"
        self.seconds = {"setup": 0.0, "window": 0.0, "after": 0.0}
        self.backend = {"setup": 0, "window": 0, "after": 0}

    def __call__(self, event, secs, **_):
        if event in COMPILE_EVENTS:
            self.seconds[self.phase] += secs
            if event == COMPILE_EVENTS[0]:
                self.backend[self.phase] += 1


def use_compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache at a fixed path in the
    checkout, for every program however short its compile."""
    import jax

    path = str(root / "bench" / ".cache" / "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def build(cell, *, graph_cache=graphgen.CACHE_DIR):
    """Graph, features and the program's pipeline for ``cell``.
    Returns ``(pipe, host)`` with ``host`` the reference's inputs."""
    from repro.core.graph import CSCGraph
    from repro.pipeline import Pipeline, PipelineSpec

    config, traffic = cell.config, cell.traffic
    fields = graphgen.graph_fields(config)
    t0 = time.perf_counter()
    arrays, built = graphgen.load_or_build(fields, graph_cache)
    say(f"graph: {'generated and cached' if built else 'loaded from cache'}"
        f" in {time.perf_counter() - t0:.1f} s, "
        f"{arrays['indptr'].shape[0] - 1:,} nodes, "
        f"{arrays['indices'].shape[0]:,} edges, "
        f"{int(arrays['labeled'].sum()):,} labelled")
    t0 = time.perf_counter()
    feats = graphgen.features(fields, arrays["classes"],
                              int(config["in_dim"]))
    labels = np.where(arrays["labeled"], arrays["classes"],
                      -1).astype(np.int32)
    say(f"features: {feats.shape} float32 in "
        f"{time.perf_counter() - t0:.1f} s")
    spec = PipelineSpec.from_scheme(
        config["scheme"], num_parts=int(config["num_parts"]),
        fanouts=tuple(config["fanouts"]),
        partitioner=config["partitioner"], executor=config["executor"],
        feature_store=config["feature_store"],
        prefetch_depth=int(traffic["prefetch_depth"]),
        staging=bool(traffic["staging"]))
    t0 = time.perf_counter()
    graph = CSCGraph(indptr=arrays["indptr"].astype(np.int32),
                     indices=arrays["indices"])
    pipe = Pipeline.build(graph, feats, labels, spec)
    say(f"Pipeline.build: {time.perf_counter() - t0:.1f} s")
    host = {"graph": (arrays["indptr"], arrays["indices"]),
            "feats": feats, "labels": labels}
    return pipe, host


def start_driver(pipe, cell, seed: int):
    """Weights from ``seed`` on the device, AdamW state, and the
    program's training driver over the model module's loss, as
    ``launch/train_gnn.py`` builds them."""
    from repro.optim import init_opt_state

    config = cell.config
    params = cell.model.init_params(seed, config)
    opt_state = init_opt_state(params, kind=config["optimizer"])
    driver = pipe.train_driver(
        cell.model.program_loss(config), batch=int(cell.traffic["batch"]),
        lr=float(config["lr"]),
        optimizer=config["optimizer"], grad_clip=float(config["grad_clip"]),
        base_salt=base_salt(seed), mode=cell.traffic["driver"])
    return driver, params, opt_state


def checked_steps(driver, params, opt_state):
    """The first ``CHECKED_STEPS`` steps through ``driver.step``; returns
    the state after them and what the reference is held against."""
    import jax

    prog = {"params0": jax.device_get(params), "losses": [], "overflow": []}
    for k in range(CHECKED_STEPS):
        params, opt_state, loss, metrics = driver.step(params, opt_state)
        prog["losses"].append(float(loss))
        prog["overflow"].append(float(metrics["sampler_window_overflow"]))
        if k == 0:
            prog["mu1"] = jax.device_get(opt_state.mu)
    prog["params3"] = jax.device_get(params)
    return params, opt_state, prog


def reference_of(cell, host, seed: int, **faults):
    config = cell.config
    return reference.train_steps(
        host["graph"], host["feats"], host["labels"], seed=seed,
        base_salt=base_salt(seed), batch=int(cell.traffic["batch"]),
        fanouts=tuple(config["fanouts"]), window=config["sampler_window"],
        init_params=lambda s: cell.model.init_params(s, config),
        reference_loss=cell.model.reference_loss,
        opt=optimizer_of(config), steps=CHECKED_STEPS, **faults)


def optimizer_of(config: dict) -> dict:
    return {"lr": config["lr"], "b1": config["adam_b1"],
            "b2": config["adam_b2"], "eps": config["adam_eps"],
            "weight_decay": config["weight_decay"],
            "grad_clip": config["grad_clip"]}


# ------------------------------------------------------------------ window

def window(driver, params, opt_state, seconds: float, annotate):
    """Steps until ``seconds`` have passed since the first was called,
    each waited for.  Returns the start, each step's completion time and
    each step's loss."""
    done, losses = [], []
    t0 = time.perf_counter()
    with annotate("window"):
        while True:
            with annotate("driver.step"):
                params, opt_state, loss, _ = driver.step(params, opt_state)
            with annotate("block"):
                loss.block_until_ready()
            done.append(time.perf_counter())
            losses.append(loss)
            if done[-1] - t0 >= seconds:
                break
    return t0, done, losses


def interval_p90_ms(t0: float, done: list[float]) -> float:
    """90th percentile over every step of the interval between
    consecutive step completions (the first timed from the window's
    start), in ms."""
    iv = np.diff([t0] + done) * 1e3
    if len(iv) < 2:
        return float(iv.max())
    return statistics.quantiles(iv.tolist(), n=10)[8]


def peak_bytes(devices) -> int:
    stats = [d.memory_stats() or {} for d in devices]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


# -------------------------------------------------------------------- run

def run_cell(cell, *, seed: int, seconds: float, trace: bool,
             root: Path = ROOT, t_start: float = T_START,
             require_tpu: bool = True, graph_cache=graphgen.CACHE_DIR):
    """One run of ``cell``.  Returns the result object, or None when the
    devices are not the chips the cell asks for."""
    import jax

    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu"
                        or len(devices) < cell.chips):
        say(f"bench: {cell.name} needs {cell.chips} TPU chip(s); JAX sees "
            f"{len(devices)} {devices[0].platform} device(s); no result")
        return None
    devices = devices[:cell.chips]
    say(f"bench: {cell.name}, seed {seed}, {seconds} s, trace {int(trace)}"
        f" on {len(devices)} x {devices[0].device_kind}; compile cache "
        f"{use_compile_cache(root)}")
    log = CompileLog()
    jax.monitoring.register_event_duration_secs_listener(log)
    try:
        with jax.default_matmul_precision(cell.config["matmul_precision"]):
            return _run(cell, log, devices, seed=seed, seconds=seconds,
                        trace=trace, t_start=t_start,
                        graph_cache=graph_cache)
    finally:
        jax.monitoring.unregister_event_duration_listener(log)


def _run(cell, log, devices, *, seed, seconds, trace, t_start,
         graph_cache):
    import jax

    pipe, host = build(cell, graph_cache=graph_cache)
    driver, params, opt_state = start_driver(pipe, cell, seed)
    t0 = time.perf_counter()
    params, opt_state, prog = checked_steps(driver, params, opt_state)
    for _ in range(int(cell.traffic["warmup_steps"])):
        params, opt_state, loss, _ = driver.step(params, opt_state)
        loss.block_until_ready()
    say(f"checked and warm-up steps: {time.perf_counter() - t0:.1f} s")

    tmp = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    annotate = (jax.profiler.TraceAnnotation if trace
                else lambda name: contextlib.nullcontext())
    if trace:
        # host spans come from the benchmark's TraceAnnotations; the Python
        # function tracer would add an event per call
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(tmp, profiler_options=options)
    log.phase = "window"
    w0, done, losses = window(driver, params, opt_state, seconds, annotate)
    log.phase = "after"
    if trace:
        jax.profiler.stop_trace()
    setup_s = w0 - t_start
    peak = peak_bytes(devices)
    steps, window_s = len(done), done[-1] - w0
    batch = int(cell.traffic["batch"])
    failed = int(sum(not math.isfinite(float(x)) for x in losses))
    gaps = np.diff([w0] + done) * 1e3
    say(f"window: {steps} steps in {window_s:.3f} s; set-up {setup_s:.1f} s"
        f"; peak device memory {peak / 2**30:.3f} GiB; compiles in window "
        f"{log.backend['window']}; step intervals min {gaps.min():.1f} "
        f"median {np.median(gaps):.1f} max {gaps.max():.1f} ms")

    driver.close()
    del driver, pipe, params, opt_state, losses
    gc.collect()

    t0 = time.perf_counter()
    ref = reference_of(cell, host, seed)
    b1 = float(cell.config["adam_b1"])
    correct, check = compare.judge(compare.numbers(prog, ref, b1),
                                   cell.limits)
    say(f"reference: {time.perf_counter() - t0:.1f} s; hubs met "
        f"{ref['hubs']}, counted by the program {prog['overflow']}")

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    result = {"correct": correct, "attempted": steps, "failed": failed}
    if not trace:
        values = {
            "seeds_per_s": steps * batch / window_s,
            "step_ms_p90": interval_p90_ms(w0, done),
            "peak_hbm_gib": peak / 2**30,
            "setup_s": setup_s,
        }
        metrics = {m["name"]: {"value": values[m["name"]],
                               "unit": units[m["name"]]}
                   for m in cell.end_to_end}
    else:
        path = next(Path(tmp).rglob("*.xplane.pb"))
        reduced = trace_reduce.reduce(path)
        shutil.rmtree(tmp, ignore_errors=True)
        fanouts = tuple(cell.config["fanouts"])
        ctx = types.SimpleNamespace(
            trace=reduced, steps=steps, window_s=window_s, batch=batch,
            fanouts=fanouts, config=cell.config,
            step_flops=cell.model.step_flops(batch, fanouts, cell.config),
            device_kind=devices[0].device_kind,
            chips=len(devices), compile_setup_s=log.seconds["setup"],
            window_compiles=log.backend["window"],
            peak=peaks.lookup(devices[0].device_kind))
        metrics = {}
        for m in cell.per_layer:
            value = load_reader(cell.metrics_dir, m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=reduced["busy_s"],
                      window_s=reduced["window_s"])
        result["breakdown"] = {"device_ops": trace_reduce.top_ops(reduced),
                               "idle_gaps": reduced["gaps"]}
    result["metrics"] = metrics
    result["device"] = device
    for name, c in check.items():
        say(f"check {name} {c['value']!r} limit {c['limit']!r}")
    result["check"] = check
    return result


def steady_allocator() -> bool:
    """Pin glibc malloc's mmap and trim thresholds at its static defaults
    (128 KiB), where every process starts.  Left dynamic, glibc raises
    them after a large free, so whether the host's per-step numpy
    temporaries page-fault anew would depend on what set-up happened to
    free (a run that generates the graph frees such blocks, one that loads
    it may not).  Pinned, every run pays those faults alike, as a fresh
    process does.  Returns whether the thresholds were set."""
    try:
        mallopt = ctypes.CDLL(None).mallopt
    except (OSError, AttributeError):
        return False
    m_trim_threshold, m_mmap_threshold = -1, -3
    return bool(mallopt(m_mmap_threshold, 128 << 10)
                and mallopt(m_trim_threshold, 128 << 10))


def main(argv=None) -> int:
    steady_allocator()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    result = run_cell(cell, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace))
    if result is None:
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
