"""Pallas kernels for the compute hot-spots the paper optimizes.

Kernels run compiled (Mosaic) on an accelerator and in interpret mode on
the CPU backend; ``resolve_interpret`` makes that decision when a kernel
is traced.  No environment variable overrides it: on a TPU a kernel that
fails to compile raises instead of falling back.
"""
from __future__ import annotations

import jax


def resolve_interpret(interpret=None):
    """``interpret`` if given (a bool, or ``pltpu.InterpretParams`` for the
    interpreter that models TPU DMA and semaphore semantics), else whether
    the default backend is the CPU — the only backend where a Pallas
    kernel is interpreted."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return interpret
