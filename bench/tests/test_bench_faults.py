"""A whole run of the harness on the CPU at toy size (the look for a chip
skipped): correct on the program as it is, and not correct with each
fault a one-chip training cell can have planted underneath the timed
path.  (A cell on one chip has no exchange between chips to leave out.)"""
import sys
from pathlib import Path

import jax.numpy as jnp
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import run

TOY = BENCH / "tests" / "data" / "toy"


def _run(tmp_path, monkeypatch):
    monkeypatch.setattr(run, "use_compile_cache", lambda root: "off")
    cell = run.load_cell("toy.train", TOY, models_dir=BENCH / "models")
    cell.metrics_dir = BENCH / "metrics"
    return run.run_cell(cell, seed=2**31 + 11, seconds=0.3, trace=False,
                        root=TOY, require_tpu=False, graph_cache=tmp_path)


def test_toy_run_is_correct(tmp_path, monkeypatch):
    result = _run(tmp_path, monkeypatch)
    assert result["correct"] is True
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "check"]
    assert set(result["metrics"]) == {"seeds_per_s", "step_ms_p90",
                                      "peak_hbm_gib", "setup_s"}
    assert all(m["value"] >= 0 for m in result["metrics"].values())
    assert result["device"]["platform"] == "cpu"
    assert result["check"]["hub_gap"] == {"value": 0.0, "limit": 0}


def _unchanged_state(monkeypatch):
    from repro.pipeline import prefetch

    monkeypatch.setattr(prefetch, "make_update_fn", lambda **kw: (
        lambda params, opt_state, grads, metrics:
        (params, opt_state, metrics)))


def _half_batch(monkeypatch):
    from repro.models import gnn

    loss = gnn.gnn_loss

    def half(params, mfgs, h0, labels, valid, cfg, dropout_key=None):
        keep = jnp.arange(valid.shape[0]) < valid.shape[0] // 2
        return loss(params, mfgs, h0, labels, valid & keep, cfg,
                    dropout_key)

    monkeypatch.setattr(gnn, "gnn_loss", half)


def _answer_altered(monkeypatch):
    from repro.kernels import ops

    sample = ops.fused_sample

    def shifted(graph, seeds, fanout, salt, window=2048):
        samples, indptr, overflow = sample(graph, seeds, fanout, salt,
                                           window)
        n = graph.indptr.shape[0] - 1
        return (jnp.where(samples >= 0, (samples + 1) % n, -1), indptr,
                overflow)

    monkeypatch.setattr(ops, "fused_sample", shifted)


@pytest.mark.parametrize("plant", [_unchanged_state, _half_batch,
                                   _answer_altered],
                         ids=["state_unchanged", "half_batch",
                              "answer_altered"])
def test_planted_fault_is_not_correct(plant, tmp_path, monkeypatch):
    plant(monkeypatch)
    result = _run(tmp_path, monkeypatch)
    assert result["correct"] is False
    assert any(c["value"] > c["limit"] for c in result["check"].values())
