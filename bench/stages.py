"""What the program noted of itself while the traced window's profiler
session collected (``repro.obs.trace.profiled()``), for the readers of
the per-stage metrics.

The record maps the op names of the program the window ran to the
program's named scopes, for the top-level ops only.  Those run one after
another (the ops of a ``while`` body inside the ``while``'s own
interval), so a stage's device time is the sum of ``op_seconds``
(``trace_reduce.reduce``) over the top-level ops under its scope.  A
program without the record (one older than it), or one that noted
nothing, gives None.
"""


def record():
    """The program's ``repro.obs.trace.profiled()``, or None where the
    program keeps no such record."""
    try:
        from repro.obs import trace
    except ImportError:
        return None
    get = getattr(trace, "profiled", None)
    return None if get is None else get()


def ms_per_step(ctx, scope: str):
    """Device time per step of the top-level ops under ``scope`` (a
    component of their scope path, a transform's wrapper taken off), in
    ms."""
    rec = record()
    if rec is None or not rec.scopes or not ctx.trace:
        return None
    from repro.obs.scopes import parts

    seconds = [sec for name, sec in ctx.trace["op_seconds"].items()
               if scope in parts(rec.scopes.get(name, ""))]
    if not seconds:
        return None
    return 1e3 * sum(seconds) / ctx.steps
