"""Record the chip data of ``test_bench_spans.py``: the toy cell's traced
window on one TPU chip, with the program's spans and stage scopes in it.

    python3 bench/tests/record_toy_spans.py OUT_DIR [--seed N]

Writes ``OUT_DIR/toy_spans.xplane.pb`` (the profiler trace of the window)
and ``OUT_DIR/toy_spans.record.json``: the window's steps, and what the
program noted of itself while the session collected
(``repro.obs.trace.profiled()``): span seconds and counts, each step's
``frontier_live``/``frontier_slots``, and the top-level op → scope map.
Prints the run's result line, with every per-layer metric of the root
``BENCHMARK.json`` read on the toy cell.
"""
import argparse
import json
import shutil
import sys
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import run
import trace_reduce

TOY = BENCH / "tests" / "data" / "toy"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("out", type=Path)
    ap.add_argument("--seed", type=int, default=2**31 + 14)
    ap.add_argument("--seconds", type=float, default=0.08)
    args = ap.parse_args(argv)
    args.out.mkdir(parents=True, exist_ok=True)
    reduce = trace_reduce.reduce

    def keep(path, *a, **k):
        shutil.copy(path, args.out / "toy_spans.xplane.pb")
        return reduce(path, *a, **k)

    trace_reduce.reduce = keep
    cell = run.load_cell("toy.train", TOY, models_dir=BENCH / "models")
    cell.per_layer = run.load_cell("products.train.b1000").per_layer
    cell.metrics_dir = BENCH / "metrics"
    result = run.run_cell(cell, seed=args.seed, seconds=args.seconds,
                          trace=True, root=TOY,
                          graph_cache=BENCH / ".cache" / "graphs")
    if result is None:
        return 3
    from repro.obs import trace

    rec = trace.profiled()
    record = {
        "steps": result["attempted"],
        "spans": rec.spans,
        "frontier_live": [np.asarray(m["frontier_live"]).tolist()
                          for m in rec.steps],
        "frontier_slots": [np.asarray(m["frontier_slots"]).tolist()
                           for m in rec.steps],
        "scopes": rec.scopes,
    }
    (args.out / "toy_spans.record.json").write_text(
        json.dumps(record, indent=0, sort_keys=True))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
