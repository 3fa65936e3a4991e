"""Read the readings that the limits of ``bench/limits/<workload>.json``
are set from, on the chip, at the cells' own sizes.

    python3 bench/calibrate.py --workload products.train.b1000 \\
        --workload products.train.b250 --seeds 11 12 13 --fault-seeds 3

For each workload (all of one configuration, so one process builds the
graph and pipeline once) and each seed, the program's first three steps
go through the training driver as in a run, and ``compare.numbers`` is
read for:

* ``program``: the program against the float32 reference (the lower
  readings);
* ``control``: the reference with three-pass bfloat16 matrix products
  (``Precision.HIGH``), put in the program's place (must fail);
* ``half_batch`` and ``altered``: planted faults in the reference put in
  the program's place, on the first ``--fault-seeds`` seeds: half of each
  batch left out of the mean, and every bottom-level draw shifted to the
  next node id.

A state left unchanged reads 1 on ``change_gap`` by that number's measure
and needs no run.  One JSON line per workload and seed goes to standard
output.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys

import run


def as_program(ref: dict, b1: float) -> dict:
    """A reference result in the shape ``compare.numbers`` reads the
    program's in (AdamW's first moment after one step is
    ``(1 - b1) * grad``)."""
    import jax

    return {"losses": ref["losses"], "params0": ref["params0"],
            "params3": ref["params"], "overflow": ref["hubs"],
            "mu1": jax.tree.map(lambda g: g * (1 - b1), ref["grad1"])}


def calibrate(cells, seeds, fault_seeds, *, require_tpu=True, out=print):
    import jax

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise SystemExit("calibrate: needs a TPU")
    run.use_compile_cache(run.ROOT)
    with jax.default_matmul_precision(cells[0].config["matmul_precision"]):
        _calibrate(cells, seeds, fault_seeds, out)


def _calibrate(cells, seeds, fault_seeds, out):
    pipe, host = run.build(cells[0])
    for cell in cells:
        b1 = float(cell.config["adam_b1"])
        for i, seed in enumerate(seeds):
            driver, params, opt = run.start_driver(pipe, cell, seed)
            _, _, prog = run.checked_steps(driver, params, opt)
            driver.close()
            ref = run.reference_of(cell, host, seed)
            row = {"workload": cell.name, "seed": seed,
                   "program": run.compare.numbers(prog, ref, b1),
                   "losses": prog["losses"], "hubs": ref["hubs"]}
            plants = {"control": {"passes": 3}}
            if i < fault_seeds:
                plants.update(half_batch={"half_batch": True},
                              altered={"alter_bottom": True})
            for name, fault in plants.items():
                bad = run.reference_of(cell, host, seed, **fault)
                row[name] = run.compare.numbers(as_program(bad, b1), ref, b1)
            out(json.dumps(row))
            sys.stdout.flush()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", action="append", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--fault-seeds", type=int, default=3)
    args = ap.parse_args(argv)
    cells = [run.load_cell(w) for w in args.workload]
    if len({c.config["name"] for c in cells}) != 1:
        raise SystemExit("calibrate: workloads of one configuration only")
    calibrate(cells, args.seeds, args.fault_seeds)


if __name__ == "__main__":
    main()
