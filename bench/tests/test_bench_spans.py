"""The readers of the per-stage metrics and what they read: the reduced
trace (unchanged, golden numbers on the recorded toy trace) and what the
program noted of itself while the profiler session collected
(``repro.obs.trace.profiled()``); on synthetic records, and on a toy
cell's traced window recorded on one TPU v5 lite chip with the program's
spans and its record (``data/toy_spans.*``, written by
``record_toy_spans.py``)."""
import json
import sys
import types
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import kernels
import run
import stages
import trace_reduce
from repro.obs import trace
from repro.obs.scopes import parts

DATA = BENCH / "tests" / "data"
TOY = DATA / "toy.xplane.pb"
SPANS_TRACE = DATA / "toy_spans.xplane.pb"
SPANS_RECORD = DATA / "toy_spans.record.json"
STAGES = ("sample", "fetch", "forward", "optimizer")
STAGE_READERS = {"sampling.ms_per_step": "sample",
                 "relabel.ms_per_step": "relabel",
                 "feature_fetch.ms_per_step": "fetch",
                 "model.ms_per_step": "forward"}
NEW_READERS = (list(STAGE_READERS) + ["seed_draw.ms_per_step",
                                      "frontier_live_share"])


def _read(name, ctx):
    return run.load_reader(BENCH / "metrics", name)(ctx)


def _record(monkeypatch, spans=None, steps=(), scopes=None):
    """A fresh ``profiled()`` record holding what is given."""
    rec = trace.Profiled()
    rec.spans.update(spans or {})
    for m in steps:
        rec.add_step(m)
    rec.add_scopes(scopes or {})
    monkeypatch.setattr(trace, "_PROFILED", rec)
    return rec


def test_reduce_gives_the_numbers_it_gave_before_program_spans():
    """The reduction is what it was before the program had spans and
    scopes (``data/toy.reduced.json`` was written by it then)."""
    want = json.loads((DATA / "toy.reduced.json").read_text())
    got = trace_reduce.reduce(TOY)
    assert got == want


def test_scope_parts_take_off_transform_wrappers():
    """The stage readers' scope components (``repro.obs.scopes.parts``):
    the backward pass of ``forward`` counts as ``forward``."""
    assert parts("jit(fused_raw)/transpose(jvp(forward))/layer0/dot_general"
                 ) == {"fused_raw", "forward", "layer0", "dot_general"}
    assert parts("jit(f)/sample/level2/relabel/jit(searchsorted)/vmap()/"
                 "while") == {"f", "sample", "level2", "relabel",
                              "searchsorted", "while"}
    assert parts("") == set()


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_readers_are_silent_without_their_input(name, monkeypatch):
    """A program without the record (the parent of the change that added
    it), or one that noted nothing, gives no number and no error."""
    bare = {"window_s": 1.0, "busy_s": 0.5, "idle_share": 0.5,
            "op_seconds": {"while.1": 0.1}, "gaps": []}
    contexts = (types.SimpleNamespace(trace=None, steps=3),
                types.SimpleNamespace(trace=dict(bare), steps=3))
    _record(monkeypatch)
    for ctx in contexts:
        assert _read(name, ctx) is None
    monkeypatch.delattr(trace, "profiled")
    assert stages.record() is None
    for ctx in contexts:
        assert _read(name, ctx) is None


def test_new_readers_read_their_inputs(monkeypatch):
    scopes = {"while.1": "jit(f)/sample/level0/relabel/while",
              "fused_sample.2": "jit(f)/sample/level0/jit(fused_sample)",
              "fusion.3": "jit(f)/fetch/gather",
              "fusion.4": "jit(f)/transpose(jvp(forward))/layer0/dot",
              "fusion.5": "jit(f)/jvp(forward)/layer0/dot",
              "fusion.6": "jit(f)/optimizer/mul"}
    op_seconds = {"while.1": 0.2, "fused_sample.2": 0.2, "fusion.3": 0.04,
                  "fusion.4": 0.08, "fusion.5": 0.04, "fusion.6": 0.01,
                  # a body op of the while and an op of no program
                  "fusion.7": 0.1, "copy.8": 0.3}
    steps = [{"frontier_live": np.array([10, 50, 300]),
              "frontier_slots": np.array([16, 96, 500])},
             {"frontier_live": np.array([10, 60, 200]),
              "frontier_slots": np.array([16, 96, 500])},
             {"loss": 1.0}]
    _record(monkeypatch, spans={"driver/seeds": [0.8, 4]}, steps=steps,
            scopes=scopes)
    ctx = types.SimpleNamespace(
        trace={"window_s": 2.0, "busy_s": 1.0, "idle_share": 0.5,
               "op_seconds": op_seconds, "gaps": []}, steps=4)
    assert _read("seed_draw.ms_per_step", ctx) == pytest.approx(200.0)
    assert _read("sampling.ms_per_step", ctx) == pytest.approx(100.0)
    assert _read("relabel.ms_per_step", ctx) == pytest.approx(50.0)
    assert _read("feature_fetch.ms_per_step", ctx) == pytest.approx(10.0)
    assert _read("model.ms_per_step", ctx) == pytest.approx(30.0)
    assert _read("frontier_live_share", ctx) == pytest.approx(50.0)


# ---------------------------------------------- a chip trace with spans

def _host_events(path):
    """``(name, start_ns, end_ns)`` of every host event in the trace."""
    from jax.profiler import ProfileData

    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for plane in ProfileData.from_file(str(path)).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]


@pytest.fixture(scope="module")
def chip():
    record = json.loads(SPANS_RECORD.read_text())
    spans, devices = trace_reduce.read_events(SPANS_TRACE)
    return types.SimpleNamespace(
        record=record, spans=spans, devices=devices,
        host=_host_events(SPANS_TRACE),
        reduced=trace_reduce.reduce(SPANS_TRACE))


def _chip_record(monkeypatch, chip):
    r = chip.record
    steps = [{"frontier_live": np.array(live),
              "frontier_slots": np.array(slots)}
             for live, slots in zip(r["frontier_live"], r["frontier_slots"])]
    return _record(monkeypatch, spans=r["spans"], steps=steps,
                   scopes=r["scopes"])


def test_chip_trace_holds_program_spans_nested_in_benchmark_steps(chip):
    names = {e[0] for e in chip.host}
    assert {"window", "driver.step", "block", "driver/step",
            "driver/seeds", "driver/runner_step"} <= names
    steps = [e for e in chip.host if e[0] == "driver.step"]
    seeds = [e for e in chip.host if e[0] == "driver/seeds"]
    assert len(steps) == len(seeds) == chip.record["steps"]
    for _, s, e in seeds:
        assert sum(a <= s and e <= b for _, a, b in steps) == 1


def test_chip_record_agrees_with_the_trace(chip):
    """The program's own totals of its spans are the annotations' on the
    trace, and it noted one step's counters per window step."""
    r = chip.record
    for name in ("driver/step", "driver/seeds", "driver/runner_step"):
        on_trace = [e - s for n, s, e in chip.host if n == name]
        seconds, count = r["spans"][name]
        assert count == len(on_trace)
        assert seconds == pytest.approx(sum(on_trace) * 1e-9, rel=0.05)
    assert len(r["frontier_live"]) == len(r["frontier_slots"]) == r["steps"]
    for live, slots in zip(r["frontier_live"], r["frontier_slots"]):
        assert 0 < live[-1] < slots[-1]


def test_chip_trace_stages_cover_the_busy_time(chip):
    """The top-level ops the record names run one after another on the
    device (their summed time is their union), and nearly all busy time
    falls under the four stages; relabel and the kernel lie inside
    sampling, and the levels and layers split their stages."""
    scopes = chip.record["scopes"]
    _, lo, hi = max((s for s in chip.spans if s[0] == "window"),
                    key=lambda s: s[2] - s[1])
    top = [(s, e) for n, s, e in chip.devices["/device:TPU:0"]
           if n in scopes and lo <= s < hi]
    union = trace_reduce._union(top, lo, hi)
    assert sum(e - s for s, e in top) == pytest.approx(
        sum(e - s for s, e in union), rel=1e-6)
    busy = chip.reduced["busy_s"]
    split = {}
    for name, sec in chip.reduced["op_seconds"].items():
        for part in parts(scopes.get(name, "")):
            split[part] = split.get(part, 0.0) + sec
    staged = sum(sec for name, sec in chip.reduced["op_seconds"].items()
                 if parts(scopes.get(name, "")) & set(STAGES))
    assert 0.9 * busy <= staged <= 1.02 * busy
    assert split["relabel"] <= split["sample"]
    assert 0 < kernels.seconds(chip.reduced, "fused_sample") \
        <= split["sample"]
    levels = [split[f"level{i}"] for i in range(3)]
    assert sum(levels) == pytest.approx(split["sample"], rel=1e-6)
    assert all(split[f"layer{i}"] > 0 for i in range(3))


def test_chip_trace_feeds_every_new_reader(chip, monkeypatch):
    _chip_record(monkeypatch, chip)
    ctx = types.SimpleNamespace(trace=chip.reduced,
                                steps=chip.record["steps"])
    values = {name: _read(name, ctx) for name in NEW_READERS}
    assert all(v is not None and v > 0 for v in values.values()), values
    assert values["relabel.ms_per_step"] <= values["sampling.ms_per_step"]
    stage_ms = sum(values[n] for n in STAGE_READERS
                   if n != "relabel.ms_per_step")
    assert stage_ms <= 1e3 * chip.reduced["busy_s"] / ctx.steps * 1.02
    assert values["frontier_live_share"] < 100.0
