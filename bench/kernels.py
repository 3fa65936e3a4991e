"""Kernel device time from a reduced trace (``trace_reduce.reduce``)."""


def seconds(reduced, pattern: str) -> float:
    """Summed device seconds of the ops whose name contains ``pattern``;
    0.0 when the trace holds none."""
    if reduced is None:
        return 0.0
    return sum(s for name, s in reduced["op_seconds"].items()
               if pattern in name)
