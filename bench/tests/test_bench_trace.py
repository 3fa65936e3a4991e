"""The trace reduction on a small recorded trace: a toy cell's traced
window on one TPU v5 lite chip (``data/toy.xplane.pb``), checked against
sums worked out here from the raw events by other means."""
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import kernels
import trace_reduce

TRACE = BENCH / "tests" / "data" / "toy.xplane.pb"


@pytest.fixture(scope="module")
def events():
    return trace_reduce.read_events(TRACE)


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce(TRACE)


def _window(spans):
    return max((s for s in spans if s[0] == "window"),
               key=lambda s: s[2] - s[1])


def test_planes_and_spans(events):
    spans, devices = events
    assert list(devices) == ["/device:TPU:0"]
    names = {s[0] for s in spans}
    assert names == {"window", "driver.step", "block"}
    _, lo, hi = _window(spans)
    steps = [s for s in spans if s[0] == "driver.step"]
    assert steps and all(lo <= s[1] and s[2] <= hi for s in steps)


def test_busy_and_idle_share_by_a_time_grid(events, reduced):
    """Busy time from a 1 us occupancy grid, which knows nothing of
    interval merging, agrees with the union to the grid's resolution."""
    spans, devices = events
    _, lo, hi = _window(spans)
    ops = devices["/device:TPU:0"]
    grid = np.zeros(int((hi - lo) // 1000) + 1, bool)
    for _, s, e in ops:
        a, b = max(s, lo), min(e, hi)
        if b > a:
            grid[int((a - lo) // 1000):int(np.ceil((b - lo) / 1000))] = True
    busy_grid = grid.sum() * 1e-6
    assert reduced["window_s"] == pytest.approx((hi - lo) * 1e-9)
    assert reduced["busy_s"] == pytest.approx(busy_grid,
                                              abs=len(ops) * 2e-6)
    assert 0 < reduced["busy_s"] < reduced["window_s"]
    assert reduced["idle_share"] == pytest.approx(
        1 - reduced["busy_s"] / reduced["window_s"])


def test_longest_gap_and_its_label_by_a_time_grid(events, reduced):
    """The longest idle run of the 1 us grid is the first gap, and the
    innermost benchmark span around its middle is its label."""
    spans, devices = events
    _, lo, hi = _window(spans)
    grid = np.zeros(int((hi - lo) // 1000) + 1, bool)
    for _, s, e in devices["/device:TPU:0"]:
        a, b = max(s, lo), min(e, hi)
        if b > a:
            grid[int((a - lo) // 1000):int(np.ceil((b - lo) / 1000))] = True
    idle = np.concatenate([[0], (~grid).astype(np.int8), [0]])
    starts = np.flatnonzero(np.diff(idle) == 1)
    ends = np.flatnonzero(np.diff(idle) == -1)
    k = int(np.argmax(ends - starts))
    label, secs = reduced["gaps"][0]
    assert secs == pytest.approx((ends[k] - starts[k]) * 1e-6, abs=3e-6)
    mid = lo + (starts[k] + ends[k]) / 2 * 1000
    holding = [sp for sp in spans if sp[1] <= mid < sp[2]]
    assert label == min(holding, key=lambda sp: sp[2] - sp[1])[0]


def test_gaps_fill_the_idle_time_and_carry_span_labels(events, reduced):
    spans, _ = events
    gaps = reduced["gaps"]
    assert 0 < len(gaps) <= 10
    assert all(label in {"window", "driver.step", "block", "none"}
               for label, _ in gaps)
    secs = [s for _, s in gaps]
    assert secs == sorted(secs, reverse=True)
    # the ten longest gaps are at most the idle time, and the longest is
    # longer than any gap a spread of the idle time would leave
    idle = reduced["window_s"] - reduced["busy_s"]
    assert sum(secs) <= idle + 1e-9
    steps = sum(1 for s in spans if s[0] == "driver.step")
    assert secs[0] >= idle / (10 * steps)


def test_per_op_seconds(events, reduced):
    spans, devices = events
    _, lo, hi = _window(spans)
    want = sum((e - s) * 1e-9 for name, s, e in devices["/device:TPU:0"]
               if name.startswith("fused_sample") and lo <= s < hi)
    assert want > 0
    assert kernels.seconds(reduced, "fused_sample") == pytest.approx(want)
    top = trace_reduce.top_ops(reduced)
    assert len(top) == 10
    assert [t for _, t in top] == sorted((t for _, t in top), reverse=True)
    assert all(" = " not in name for name, _ in top)


def test_op_name_is_the_instruction_name():
    text = ('%fused_sample.5 = (s32[880640]{0}, s32[1]{0}) custom-call('
            'u32[1]{0} %bitcast.29), custom_call_target="tpu_custom_call"')
    assert trace_reduce.op_name(text) == "fused_sample.5"
    assert trace_reduce.op_name("fusion.3") == "fusion.3"
