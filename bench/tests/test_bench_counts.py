"""The benchmark's yardstick arithmetic: work counts, peaks, graph
generation and its cache, and the cells and models found by name."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import flops
import graphgen
import peaks
import run

PRODUCTS = json.loads((BENCH / "configs" / "graphsage-products.json")
                      .read_text())
graphsage = run.load_model(BENCH / "models", "graphsage")


def test_block_sizes_products_b1000():
    assert flops.block_sizes(1000, (15, 10, 5)) == [1000, 16000, 176000,
                                                    1056000]


def test_sage_flops_products_b1000_by_hand():
    # bottom layer: 176,000 dst, 100 -> 256, 5 draws each
    bottom = 2 * 2 * 176_000 * 100 * 256 + 176_000 * 5 * 100
    middle = 2 * 2 * 16_000 * 256 * 256 + 16_000 * 10 * 256
    top = 2 * 2 * 1_000 * 256 * 47 + 1_000 * 15 * 256
    fwd = bottom + middle + top
    assert fwd == 22_397_632_000
    assert graphsage.forward_flops(1000, (15, 10, 5), PRODUCTS) == fwd
    assert graphsage.step_flops(1000, (15, 10, 5), PRODUCTS) \
        == 3 * fwd == 67_192_896_000


def _parent_sage_step_flops(batch, fanouts, dims):
    """The count ``mfu`` read before models were found by name."""
    sizes = flops.block_sizes(batch, fanouts)
    depth = len(fanouts)
    total = 0
    for layer in range(depth):
        d = depth - 1 - layer
        n_dst, f = sizes[d], int(fanouts[d])
        d_in, d_out = int(dims[layer]), int(dims[layer + 1])
        total += 2 * 2 * n_dst * d_in * d_out + n_dst * f * d_in
    return 3 * total


def _graphsage_cells() -> list[str]:
    """The benchmark's cells whose configuration names ``graphsage``."""
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    model = {c["name"]: json.loads((BENCH.parent / c["file"]).read_text())
             ["model"] for c in bench["configs"]}
    return [w["name"] for w in bench["workloads"]
            if model[w["config"]] == "graphsage"]


@pytest.mark.parametrize("workload", _graphsage_cells())
def test_graphsage_step_flops_is_the_parents_count(workload):
    cell = run.load_cell(workload)
    c, batch = cell.config, int(cell.traffic["batch"])
    dims = ([c["in_dim"]] + [c["hidden_dim"]] * (c["num_layers"] - 1)
            + [c["num_classes"]])
    want = _parent_sage_step_flops(batch, c["fanouts"], dims)
    assert cell.model.step_flops(batch, c["fanouts"], c) == want


def test_fused_sample_bytes_products_b1000_by_hand():
    # per seed: 2 indptr words + F ids read + F written + its count
    want = (1_000 * (8 + 8 * 15 + 4) + 16_000 * (8 + 8 * 10 + 4)
            + 176_000 * (8 + 8 * 5 + 4))
    assert want == 10_756_000
    assert flops.fused_sample_bytes(1000, (15, 10, 5)) == want


def test_peaks_v5e_and_unknown_kind_raises():
    v5e = peaks.lookup("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        peaks.lookup("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks.lookup("cpu")


TOY = {"graph_family": "powerlaw", "alpha": 2.3, "num_nodes": 5000,
       "avg_degree": 12, "num_classes": 7, "labeled_fraction": 0.1,
       "graph_seed": 3}


def test_graph_is_deterministic_csc_without_self_loops():
    a, b = graphgen.generate(TOY), graphgen.generate(TOY)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])
    indptr, indices = a["indptr"], a["indices"]
    assert indptr[0] == 0 and indptr[-1] == indices.size
    assert np.all(np.diff(indptr) >= 0)
    dst = np.repeat(np.arange(TOY["num_nodes"]), np.diff(indptr))
    assert not np.any(dst == indices)
    assert 0.9 * 5000 * 12 < indices.size <= 5000 * 12
    assert abs(a["labeled"].mean() - 0.1) < 0.02
    other = graphgen.generate(dict(TOY, graph_seed=4))
    assert not np.array_equal(other["indices"][:100], indices[:100])


def test_graph_cache_second_build_loads(tmp_path, monkeypatch):
    first, built = graphgen.load_or_build(TOY, tmp_path)
    assert built
    monkeypatch.setattr(graphgen, "generate", lambda g: pytest.fail(
        "a cached graph was generated again"))
    second, built = graphgen.load_or_build(TOY, tmp_path)
    assert not built
    for k in first:
        np.testing.assert_array_equal(first[k], second[k])
    # another graph field is another key, so another directory
    assert graphgen.cache_key(TOY) != graphgen.cache_key(
        dict(TOY, avg_degree=13))
    assert len(list(tmp_path.iterdir())) == 1


def test_configs_on_one_graph_share_its_cache(tmp_path, monkeypatch):
    """Two configurations of different names on the same graph fields
    generate the graph once, and the second loads it."""
    sage = dict(TOY, name="graphsage-toy", model="graphsage")
    other = dict(TOY, name="gcn-toy", model="gcn_toy", hidden_dim=64)
    calls = []
    generate = graphgen.generate
    monkeypatch.setattr(graphgen, "generate",
                        lambda g: calls.append(g) or generate(g))
    built = [graphgen.load_or_build(graphgen.graph_fields(c), tmp_path)[1]
             for c in (sage, other)]
    assert built == [True, False] and len(calls) == 1
    assert len(list(tmp_path.iterdir())) == 1


def test_features_deterministic_float32():
    classes = np.arange(1000) % TOY["num_classes"]
    f = graphgen.features(TOY, classes, 16)
    assert f.dtype == np.float32 and f.shape == (1000, 16)
    np.testing.assert_array_equal(f, graphgen.features(TOY, classes, 16))


def _drop_in(root: Path):
    """A new configuration, traffic mix, limits and per-layer metric,
    added as files and BENCHMARK.json entries only."""
    toy = BENCH / "tests" / "data" / "toy"
    shutil.copytree(toy, root, dirs_exist_ok=True)
    shutil.copytree(BENCH / "metrics", root / "bench" / "metrics")
    shutil.copytree(BENCH / "models", root / "bench" / "models",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "bench" / "configs" / "another.json").write_text(json.dumps(
        dict(json.loads((toy / "bench/configs/graphsage-toy.json")
                        .read_text()), name="another", hidden_dim=64)))
    (root / "bench" / "traffic" / "train.new.json").write_text(json.dumps(
        dict(json.loads((toy / "bench/traffic/train.toy.json").read_text()),
             batch=8)))
    (root / "bench" / "limits" / "another.train.new.json").write_text(
        (toy / "bench/limits/toy.train.json").read_text())
    (root / "bench" / "metrics" / "seeds_in_trace.py").write_text(
        "def read(ctx):\n    return float(ctx.steps * ctx.batch)\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "another", "source": "toy",
                             "file": "bench/configs/another.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "another.train.new",
                               "config": "another", "traffic": "train.new",
                               "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": "seeds_in_trace", "unit": "seeds", "better": "higher",
        "source": "host_clock", "layer": "driver", "moves": "seeds_per_s",
        "workloads": ["another.train.new"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


def test_new_cell_found_by_name_without_edits(tmp_path):
    before = {p: p.read_bytes() for p in BENCH.rglob("*")
              if p.is_file() and ".cache" not in p.parts
              and "__pycache__" not in p.parts}
    _drop_in(tmp_path)
    cell = run.load_cell("another.train.new", tmp_path)
    assert cell.config["hidden_dim"] == 64
    assert cell.traffic["batch"] == 8
    assert cell.limits["hub_gap"] == 0
    names = [m["name"] for m in cell.per_layer]
    assert "seeds_in_trace" in names and "mfu" in names
    read = run.load_reader(cell.metrics_dir, "seeds_in_trace")
    assert read(type("Ctx", (), {"steps": 3, "batch": 8})) == 24.0
    # the old cell does not get the new metric, which names its cells
    old = run.load_cell("toy.train", tmp_path)
    assert "seeds_in_trace" not in [m["name"] for m in old.per_layer]
    after = {p: p.read_bytes() for p in before}
    assert after == before


def test_every_benchmark_cell_has_its_files():
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = run.load_cell(w["name"])
        assert cell.traffic["batch"] > 0
        assert set(cell.limits) >= {"loss_gap", "grad_gap", "change_gap",
                                    "hub_gap"}
        assert [m["name"] for m in cell.end_to_end][-1] == "setup_s"
        for fn in ("init_params", "program_loss", "reference_loss",
                   "step_flops"):
            assert callable(getattr(cell.model, fn)), (w["name"], fn)
        for m in cell.per_layer:
            assert callable(run.load_reader(cell.metrics_dir, m["name"]))


def test_harness_refuses_cpu_backend():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "products.train.b1000", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=120)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "needs 1 TPU" in out.stderr


def test_allocator_thresholds_are_fixed():
    assert run.steady_allocator()
