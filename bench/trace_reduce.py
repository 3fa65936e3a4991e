"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's
device-side numbers.

The window is the benchmark's own ``window`` span, which it records with
``jax.profiler.TraceAnnotation`` on the host around the measured steps.
Within it, per device plane (``/device:TPU:<n>``):

* busy seconds: the length of the union of the intervals of the events on
  the plane's ``XLA Ops`` line, so overlapping ops count once;
* per-op seconds: the summed durations of each op's events, by its HLO
  instruction name (``fused_sample.5``, ``fusion.226``);
* idle gaps: the stretches of the window no op covers, each labelled by
  the innermost benchmark span (``driver.step``, ``block``, or the
  ``window`` itself) that holds the gap's midpoint on the host.

Device and host events share the profiler's clock.  ``busy_s`` and
``window_s`` average over the device planes found; op seconds and gaps
come from the first device.
"""
from __future__ import annotations

import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
WINDOW = "window"
SPANS = (WINDOW, "driver.step", "block")


def _union(intervals, lo, hi):
    """Merged (start, end) intervals clipped to [lo, hi], sorted."""
    out = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _label(t, spans):
    """Name of the shortest span holding time ``t``, else ``"none"``."""
    best = None
    for name, s, e in spans:
        if s <= t < e and (best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0] if best else "none"


def op_name(event_name: str) -> str:
    """An op's HLO instruction name (``fused_sample.5``) from the event
    name, which holds the whole instruction text."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def read_events(path):
    """``(host_spans, devices)`` from the trace at ``path``:
    ``host_spans`` lists ``(name, start_ns, end_ns)`` of the benchmark's
    spans; ``devices`` maps each device plane's name to its op events as
    ``(name, start_ns, end_ns)``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    spans, devices = [], {}
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in SPANS:
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
        elif DEVICE_PLANE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((op_name(ev.name), ev.start_ns,
                                ev.start_ns + ev.duration_ns)
                               for ev in line.events)
            devices[plane.name] = ops
    return spans, devices


def reduce(path, top: int = 10) -> dict:
    """The device numbers of the trace at ``path`` (see module docs).

    Returns ``{"window_s", "busy_s", "idle_share", "devices",
    "op_seconds", "gaps"}``; ``op_seconds`` maps op name to seconds and
    ``gaps`` lists the ``top`` longest idle gaps as ``[label, seconds]``.
    Raises ``ValueError`` when the trace has no window span or no device
    plane."""
    spans, devices = read_events(path)
    windows = [s for s in spans if s[0] == WINDOW]
    if not windows:
        raise ValueError(f"no {WINDOW!r} span in {path}")
    if not devices:
        raise ValueError(f"no device plane in {path}")
    _, lo, hi = max(windows, key=lambda s: s[2] - s[1])
    busy = []
    for ops in devices.values():
        merged = _union([(s, e) for _, s, e in ops], lo, hi)
        busy.append(sum(e - s for s, e in merged))
    first = devices[sorted(devices)[0]]
    op_seconds: dict[str, float] = {}
    for name, s, e in first:
        if lo <= s < hi:
            op_seconds[name] = op_seconds.get(name, 0.0) + (e - s) * 1e-9
    merged = _union([(s, e) for _, s, e in first], lo, hi)
    gaps, cursor = [], lo
    for s, e in merged + [[hi, hi]]:
        if s > cursor:
            gaps.append((_label((s + cursor) / 2, spans), (s - cursor) * 1e-9))
        cursor = max(cursor, e)
    gaps.sort(key=lambda g: -g[1])
    window_s = (hi - lo) * 1e-9
    busy_s = sum(busy) / len(busy) * 1e-9
    return {"window_s": window_s, "busy_s": busy_s,
            "idle_share": 1.0 - busy_s / window_s,
            "devices": len(devices), "op_seconds": op_seconds,
            "gaps": [[name, sec] for name, sec in gaps[:top]]}


def top_ops(reduced: dict, top: int = 10):
    """The ``top`` op names by device seconds, as ``[name, seconds]``."""
    ops = sorted(reduced["op_seconds"].items(), key=lambda kv: -kv[1])
    return [[name, sec] for name, sec in ops[:top]]
