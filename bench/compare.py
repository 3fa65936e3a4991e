"""The comparison that decides ``correct`` for a training cell.

The program's first three steps, driven through the timed window's own
call in set-up, against ``reference.train_steps`` on the same seeds:

* ``loss_gap``: the largest ``|loss - ref| / |ref|`` over the steps;
* ``grad_gap``: the first step's gradient as the optimizer received it
  (read from AdamW's first moment after one step, ``mu / (1 - b1)``),
  by the worst leaf: ``| |g| - |g_ref| | / max(|g_ref|, median |g_ref|)``;
* ``change_gap``: the parameters' change over the three steps, by the
  worst leaf in the same way.  Leaves whose reference gradient is under a
  thousandth of the median leaf's move under Adam by round-off alone and
  are left out, by that rule and not by name;
* ``hub_gap``: the absolute difference between the hubs (frontier nodes
  above the sampler's neighbour window) the program counted and those the
  reference met; an exact count, limit 0.

Each number is judged against its own limit in the cell's limits file.
"""
from __future__ import annotations

import math

import numpy as np

NAMES = ("loss_gap", "grad_gap", "change_gap", "hub_gap")
QUIET = 1e-3


def _norms(tree) -> list[float]:
    import jax

    return [float(np.linalg.norm(np.asarray(x, np.float64)))
            for x in jax.tree.leaves(tree)]


def _worst(gaps) -> float:
    """The largest gap; infinite where any is NaN, which ``max`` would
    skip."""
    gaps = list(gaps)
    return math.inf if any(g != g for g in gaps) else max(gaps)


def _leaf_gap(got, want, keep=None) -> float:
    idx = [i for i in range(len(want)) if keep is None or keep[i]]
    floor = float(np.median([want[i] for i in idx]))
    return _worst(abs(got[i] - want[i]) / max(want[i], floor, 1e-30)
                  for i in idx)


def numbers(prog: dict, ref: dict, b1: float) -> dict[str, float]:
    """The compared numbers.  ``prog`` holds the program's ``losses``,
    ``mu1`` (AdamW's first moment after one step), ``params0``,
    ``params3`` and ``overflow`` (per-step hub counts); ``ref`` is
    ``reference.train_steps``'s result."""
    import jax

    g_ref = _norms(ref["grad1"])
    g_prog = [n / (1.0 - b1) for n in _norms(prog["mu1"])]
    median = float(np.median(g_ref))
    keep = [g >= QUIET * median for g in g_ref]
    diff = lambda a, b: jax.tree.map(
        lambda x, y: np.asarray(x, np.float64) - np.asarray(y, np.float64),
        a, b)
    c_ref = _norms(diff(ref["params"], ref["params0"]))
    c_prog = _norms(diff(prog["params3"], prog["params0"]))
    losses = zip(prog["losses"], ref["losses"])
    hub_gap = float(abs(sum(prog["overflow"]) - sum(ref["hubs"])))
    return {
        "loss_gap": _worst(abs(a - b) / abs(b) for a, b in losses),
        "grad_gap": _leaf_gap(g_prog, g_ref),
        "change_gap": _leaf_gap(c_prog, c_ref, keep),
        "hub_gap": hub_gap if hub_gap == hub_gap else math.inf,
    }


def judge(values: dict[str, float], limits: dict) -> tuple[bool, dict]:
    """``(correct, check)``: every number finite and within its limit;
    ``check`` maps each name to ``{"value", "limit"}``."""
    check = {k: {"value": values[k], "limit": limits[k]} for k in NAMES}
    ok = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in check.values())
    return ok, check
