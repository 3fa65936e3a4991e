"""Production mesh builders.

A FUNCTION, not a module-level constant: importing this module never touches
jax device state, so tests/benches keep their single CPU device.
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    return jax.make_mesh(tuple(shape), tuple(axes),
                         (jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False, model_parallel: int = 16):
    """16x16 (256 chips) per pod; (2,16,16) across 2 pods = 512 chips.

    model_parallel reshapes the per-pod 256 chips (e.g. 8 for archs whose
    head counts don't divide 16 — a §Perf beyond-paper sharding change; the
    canonical dry-run tables use the default 16x16).
    """
    dp = 256 // model_parallel
    shape = (2, dp, model_parallel) if multi_pod else (dp, model_parallel)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1):
    """Whatever devices exist on this host (examples / subprocess tests)."""
    n = len(jax.devices())
    assert n % model_parallel == 0
    return make_mesh((n // model_parallel, model_parallel),
                     ("data", "model"))


# TPU v5e hardware constants for the roofline (per chip)
PEAK_FLOPS_BF16 = 197e12          # FLOP/s
HBM_BW = 819e9                    # B/s
ICI_BW = 50e9                     # B/s per link
