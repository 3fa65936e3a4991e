"""``repro.obs`` — structured tracing and metrics.

FastSample's opening argument is a *measurement*: sampling overhead is a
significant share of distributed step time.  This subsystem is the
instrument that produces that breakdown for every stage of the stack:

  * ``repro.obs.trace``   — a low-overhead span tracer (monotonic-clock
    spans in a preallocated ring, thread-local span stacks so stager
    worker threads annotate their own timelines) exporting Chrome
    trace-event JSON viewable in Perfetto (https://ui.perfetto.dev);
    while a JAX profiler session collects, every span is also a
    ``TraceAnnotation`` on the device trace's clock, and what the
    program notes of itself then is kept in ``trace.profiled()``.
  * ``repro.obs.scopes``  — the named scope (``sample/level2/relabel``,
    ``forward/layer0``, ``optimizer``) of each HLO instruction of a
    compiled program, which is what a device trace's op names mean.
  * ``repro.obs.metrics`` — a counter/gauge/histogram registry with
    snapshot/delta semantics absorbing the step-metric dicts the
    pipeline emits (utilized bytes, cache hit rate, sampler window
    overflow — including the warn-once overflow watch), plus the
    median-of-repeats wall timers the benchmarks share.
  * ``repro.obs.profile`` — fenced per-stage step profiling: the
    sampling / feature-fetch / model-compute decomposition behind the
    paper's Figure-1-style table.
  * ``repro.obs.report``  — CLI rendering that table from a recorded
    trace: ``python -m repro.obs.report trace.json``.

Instrumented producers: the prefetch drivers (``repro.pipeline.
prefetch``), the staging ring (``repro.pipeline.staging``), the serving
loop (``repro.serve.server``), and the multi-host launcher
(``repro.launch.multihost`` merges per-rank traces into one fleet trace
with rank-as-pid mapping).  Everything is a no-op until a tracer is
installed (``repro.obs.trace.start``) or a profiler session collects —
the traced-off cost of an instrumentation point is one global load and
one check of the profiler's state.
"""
from repro.obs.metrics import (Counter, Gauge, Histogram,  # noqa: F401
                               MetricsRegistry, get_registry,
                               median_wall, set_registry)
from repro.obs.trace import (Tracer, active_tracer,  # noqa: F401
                             merge_traces, span, start, stop,
                             validate_trace)

__all__ = [
    "Tracer", "active_tracer", "span", "start", "stop",
    "merge_traces", "validate_trace",
    "MetricsRegistry", "Counter", "Gauge", "Histogram", "get_registry",
    "set_registry", "median_wall",
]
