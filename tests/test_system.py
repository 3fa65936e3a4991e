"""End-to-end behaviour tests for the full system."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.partition import build_layout, partition_graph
from repro.data.synthetic_graph import make_power_law_graph
from repro.models.gnn import GNNConfig
from repro.train.loop import GNNTrainer


@pytest.fixture(scope="module")
def world():
    ds = make_power_law_graph(2500, 8, num_features=16, num_classes=5,
                              seed=0)
    assign = partition_graph(ds.graph, 4, ds.labeled_mask, seed=0)
    layout = build_layout(ds.graph, ds.features, ds.labels, assign, 4)
    return ds, layout


@pytest.mark.parametrize("scheme", ["vanilla", "hybrid", "hybrid+fused"])
def test_gnn_training_learns(world, scheme):
    """All three paper scenarios train and reduce the loss."""
    ds, layout = world
    cfg = GNNConfig(in_dim=16, hidden_dim=32, num_classes=5, num_layers=2,
                    fanouts=(4, 3), dropout=0.0)
    tr = GNNTrainer(layout=layout, cfg=cfg, scheme=scheme,
                    batch_per_worker=64, lr=0.01)
    m0 = tr.run_epoch(0, steps_per_epoch=4)
    m1 = tr.run_epoch(1, steps_per_epoch=4)
    assert m1["loss"] < m0["loss"]
    expected_rounds = 2 if scheme.startswith("hybrid") else 2 * cfg.num_layers
    assert tr.counter.rounds % expected_rounds == 0   # traced >= once


def test_scheme_loss_trajectories_identical(world):
    """Paper §4.2: techniques leave training mathematically unchanged —
    full trajectories, not just one step."""
    ds, layout = world
    cfg = GNNConfig(in_dim=16, hidden_dim=32, num_classes=5, num_layers=2,
                    fanouts=(4, 3), dropout=0.0)
    losses = {}
    for scheme in ("vanilla", "hybrid", "hybrid+fused"):
        tr = GNNTrainer(layout=layout, cfg=cfg, scheme=scheme,
                        batch_per_worker=32, lr=0.01)
        traj = []
        for e in range(3):
            m = tr.run_epoch(e, steps_per_epoch=2)
            traj.append(m["loss"])
        losses[scheme] = traj
    assert losses["vanilla"] == losses["hybrid"] == losses["hybrid+fused"]


def test_shard_map_multidevice_subprocess(subproc):
    """The production shard_map path on 4 placeholder devices (subprocess so
    the main process keeps its single-device view)."""
    subproc.run_module(
        "repro.launch.train_gnn", "--devices", "4", "--shard-map",
        "--scheme", "hybrid+fused", "--nodes", "1500", "--epochs", "1",
        "--steps-per-epoch", "2", "--batch", "16", expect="epoch 0")


def test_dryrun_single_combo_subprocess(subproc):
    """One real dry-run combo (512 placeholder devices) end to end."""
    subproc.run_module(
        "repro.launch.dryrun", "--arch", "mamba2-130m", "--shape",
        "decode_32k", "--mesh", "pod", "--skip-probes", "--out",
        "/tmp/test_dryrun", expect='"status": "ok"')


def test_lm_train_reduces_loss_subprocess(subproc):
    r = subproc.run_module(
        "repro.launch.train", "--arch", "stablelm-1.6b", "--reduced",
        "--steps", "30", "--batch", "16", "--seq", "64", "--lr", "5e-3")
    lines = [l for l in r.stdout.splitlines() if l.startswith("step")]
    first = float(lines[0].split("loss")[1].split()[0])
    last = float(lines[-1].split("loss")[1].split()[0])
    assert last < first - 0.5, r.stdout


def test_serve_subprocess(subproc):
    subproc.run_module(
        "repro.launch.serve_lm", "--arch", "stablelm-1.6b", "--reduced",
        "--batch", "2", "--prompt-len", "16", "--gen", "8",
        expect="decoded 8 tokens")


def test_compile_cache_location(monkeypatch):
    """The entry points' compile cache: JAX_COMPILATION_CACHE_DIR when
    set (left to JAX), else one fixed git-ignored directory inside the
    checkout."""
    import os

    import jax

    from repro.launch import cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert cache.enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = cache.enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert path == os.path.join(repo, ".jax_cache")
    with open(os.path.join(repo, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
