"""Models found by a configuration's ``model`` key.  A second GNN added
by new files alone (the fixture ``data/gcn_toy.py``, a toy configuration
that names it, and the cell's entries) runs through the harness on the
CPU, the look for a chip skipped: correct as it is, not correct with its
reference altered.  A model with no module is refused when its cell is
loaded, with no fallback to another model."""
import json
import shutil
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import run

DATA = BENCH / "tests" / "data"
TOY = DATA / "toy"
FIXTURE = DATA / "gcn_toy.py"


def _drop_in(root: Path, model: str, module_text: str | None):
    """The toy tree under ``root`` plus a cell ``<model>.train`` whose
    configuration names ``model``, and ``module_text`` as that model's
    module (none when None)."""
    shutil.copytree(TOY, root)
    if module_text is not None:
        (root / "bench" / "models").mkdir()
        (root / "bench" / "models" / f"{model}.py").write_text(module_text)
    config = json.loads(
        (TOY / "bench" / "configs" / "graphsage-toy.json").read_text())
    (root / "bench" / "configs" / f"{model}-toy.json").write_text(
        json.dumps(dict(config, name=f"{model}-toy", model=model)))
    shutil.copy(TOY / "bench" / "limits" / "toy.train.json",
                root / "bench" / "limits" / f"{model}.train.json")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": f"{model}-toy", "source": "toy",
                             "file": f"bench/configs/{model}-toy.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": f"{model}.train",
                               "config": f"{model}-toy",
                               "traffic": "train.toy", "chips": 1,
                               "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


def _bench_files():
    return {p: p.read_bytes() for p in BENCH.rglob("*")
            if p.is_file() and ".cache" not in p.parts
            and "__pycache__" not in p.parts}


def _run_gcn(tmp_path, monkeypatch, module_text):
    monkeypatch.setattr(run, "use_compile_cache", lambda root: "off")
    root = tmp_path / "root"
    _drop_in(root, "gcn_toy", module_text)
    cell = run.load_cell("gcn_toy.train", root)
    assert cell.model.__file__ == str(root / "bench/models/gcn_toy.py")
    cell.metrics_dir = BENCH / "metrics"
    return run.run_cell(cell, seed=2**31 + 23, seconds=0.3, trace=False,
                        root=root, require_tpu=False,
                        graph_cache=tmp_path / "graphs")


def test_second_model_by_new_files_is_correct(tmp_path, monkeypatch):
    before = _bench_files()
    result = _run_gcn(tmp_path, monkeypatch, FIXTURE.read_text())
    assert result["correct"] is True, result["check"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert _bench_files() == before


def test_second_model_with_its_reference_altered_is_not_correct(
        tmp_path, monkeypatch):
    text = FIXTURE.read_text()
    assert text.count("0.5 * ") == 1
    result = _run_gcn(tmp_path, monkeypatch, text.replace("0.5 * ", ""))
    assert result["correct"] is False
    assert any(c["value"] > c["limit"] for c in result["check"].values())


def test_model_without_module_is_refused_at_load(tmp_path):
    _drop_in(tmp_path / "root", "gat", None)
    with pytest.raises(FileNotFoundError, match=r"bench/models/gat\.py"):
        run.load_cell("gat.train", tmp_path / "root")
    # the toy tree holds no model module, so not even GraphSAGE is found
    with pytest.raises(FileNotFoundError,
                       match=r"bench/models/graphsage\.py"):
        run.load_cell("toy.train", tmp_path / "root")

