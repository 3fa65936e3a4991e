"""Multi-host ``"multiprocess"`` executor: cross-process bit-equivalence
plus launcher unit tests.

Two tiers live here:

* **Launcher units** (fast, no JAX import in the workers): port
  selection, rank env wiring, success capture, propagated worker
  failure, and hang detection.  These run in the default tier-1 suite.
* **The equivalence matrix** (``@pytest.mark.multihost``): a real
  2-process ``jax.distributed`` fleet replays
  {vanilla, hybrid, hybrid_partial(0.25)} x prefetch {0, 2} x
  staging {off, on} and must match the shard_map executor
  bit-for-bit — losses by exact float equality, parameters by SHA-256
  over raw bytes (multiprocess runs shard_map's traced program
  verbatim, so equality is exact).  vmap is held to exact losses and
  float-tolerance parameters: jitting the step together with the adamw
  update lets XLA fuse the vmapped program differently and reassociate
  the bias-grad sum, so vmap's bias leaves drift ~1 ulp from the
  per-shard programs (the standalone ``step_fn`` grads ARE bit-equal
  across executors — ``tests/test_data.py`` asserts that).  Select
  with ``pytest -m multihost`` (the CI ``multihost`` job); the default
  run skips it via conftest.
"""
import os
import sys
import textwrap
import time

import pytest

from repro.launch import multihost


# --------------------------------------------------------------------------
# launcher units (no fleet, or trivially-cheap non-JAX fleets)
# --------------------------------------------------------------------------

def test_pick_port_is_bindable():
    import socket
    port = multihost.pick_port()
    assert 0 < port < 65536
    with socket.socket() as s:       # free at pick time => bindable now
        s.bind(("127.0.0.1", port))


def test_rank_env_wiring():
    base = {"XLA_FLAGS": "--xla_force_host_platform_device_count=8 "
                         "--xla_dump_to=/tmp/x",
            "PATH": "/usr/bin"}
    env = multihost.rank_env(base, rank=1, num_procs=4, port=12345,
                             local_devices=2)
    assert env[multihost.ENV_RANK] == "1"
    assert env[multihost.ENV_NUM_PROCS] == "4"
    assert env[multihost.ENV_COORDINATOR] == "127.0.0.1:12345"
    assert env[multihost.ENV_LOCAL_DEVICES] == "2"
    # the launcher's device count replaces the caller's, other flags stay
    assert "--xla_force_host_platform_device_count=2" in env["XLA_FLAGS"]
    assert env["XLA_FLAGS"].count("--xla_force_host_platform_device_count") \
        == 1
    assert "--xla_dump_to=/tmp/x" in env["XLA_FLAGS"]
    assert env["PATH"] == "/usr/bin"
    # the fleet is a CPU simulation: ranks never reach for a chip
    assert env["JAX_PLATFORMS"] == "cpu"
    assert base == {"XLA_FLAGS": "--xla_force_host_platform_device_count=8 "
                                 "--xla_dump_to=/tmp/x",
                    "PATH": "/usr/bin"}     # input not mutated
    assert multihost.is_worker(env)
    assert not multihost.is_worker({"PATH": "/usr/bin"})


def test_launch_validates_num_procs():
    with pytest.raises(ValueError, match="num_procs"):
        multihost.launch([sys.executable, "-c", "pass"], num_procs=0)


def test_launch_success_captures_per_rank_logs(tmp_path):
    script = ("import os; "
              "print('rank', os.environ['REPRO_MH_RANK'], 'of', "
              "os.environ['REPRO_MH_NUM_PROCS'])")
    log_dir = multihost.launch([sys.executable, "-c", script], num_procs=2,
                               timeout=60, log_dir=str(tmp_path))
    assert log_dir == str(tmp_path)
    for r in range(2):
        out = (tmp_path / f"rank{r}.out").read_text()
        assert f"rank {r} of 2" in out


def test_worker_failure_kills_fleet_and_reports(tmp_path):
    """Rank 1 crashes; the launcher must kill the healthy rank (which
    would otherwise sleep out its barrier) and surface rank 1's stderr —
    not hang until the timeout."""
    script = textwrap.dedent("""
        import os, sys, time
        if os.environ["REPRO_MH_RANK"] == "1":
            print("boom from rank 1", file=sys.stderr)
            sys.exit(3)
        time.sleep(300)     # a healthy rank blocked on the dead one
    """)
    t0 = time.monotonic()
    with pytest.raises(multihost.WorkerFailure) as ei:
        multihost.launch([sys.executable, "-c", script], num_procs=2,
                         timeout=240, log_dir=str(tmp_path))
    assert time.monotonic() - t0 < 60      # killed, not timed out
    assert ei.value.rank == 1
    assert ei.value.returncode == 3
    assert "boom from rank 1" in ei.value.stderr_tail
    assert "boom from rank 1" in str(ei.value)


def test_hang_detection_times_out(tmp_path):
    with pytest.raises(TimeoutError, match="exceeded"):
        multihost.launch([sys.executable, "-c", "import time; "
                          "time.sleep(120)"], num_procs=2, timeout=2,
                         log_dir=str(tmp_path))


# --------------------------------------------------------------------------
# the cross-process bit-equivalence matrix (pytest -m multihost)
# --------------------------------------------------------------------------
#
# One 2-rank fleet runs every matrix cell inside a single
# jax.distributed job (one backend init, shared compile cache); rank 0
# prints a JSON record of per-cell losses + a parameter digest.  The
# parent subprocess computes the same record under vmap and shard_map
# and requires all three to agree exactly.

MATRIX_WORKER = textwrap.dedent("""
    import hashlib, json
    import numpy as np
    from repro.launch import multihost
    rank, num_procs = multihost.init_from_env()
    import jax
    from repro.core.partition import build_layout, partition_graph
    from repro.data.synthetic_graph import make_power_law_graph
    from repro.models.gnn import GNNConfig, gnn_loss, init_gnn_params
    from repro.optim import init_opt_state
    from repro.pipeline import (Pipeline, PipelineSpec, PlanSpec,
                                PrefetchSpec, SamplerSpec)

    P = 2
    ds = make_power_law_graph(600, 6, num_features=8, num_classes=4, seed=0)
    assign = partition_graph(ds.graph, P, ds.labeled_mask, seed=0)
    per = P // num_procs
    layout = build_layout(ds.graph, ds.features, ds.labels, assign, P,
                          local_parts=(rank * per, (rank + 1) * per))
    cfg = GNNConfig(in_dim=8, hidden_dim=8, num_classes=4, num_layers=2,
                    fanouts=(3, 3), dropout=0.0)
    def loss_fn(p, mfgs, h, y, v):
        return gnn_loss(p, mfgs, h, y, v, cfg)

    def digest(tree):
        h = hashlib.sha256()
        for leaf in jax.tree.leaves(tree):
            arr = (leaf.addressable_data(0)
                   if hasattr(leaf, "addressable_data") else leaf)
            h.update(np.ascontiguousarray(np.asarray(arr)).tobytes())
        return h.hexdigest()

    results = {}
    for scheme in ("vanilla", "hybrid", "hybrid_partial(0.25)"):
        for depth in (0, 2):
            for staging in (False, True):
                spec = PipelineSpec(
                    plan=PlanSpec(num_parts=P, scheme=scheme),
                    sampler=SamplerSpec(fanouts=cfg.fanouts,
                                        backend="reference"),
                    executor="multiprocess",
                    prefetch=PrefetchSpec(depth=depth, staging=staging))
                pipe = Pipeline.from_layout(layout, spec)
                driver = pipe.train_driver(loss_fn, batch=8, lr=0.01)
                params = init_gnn_params(jax.random.key(0), cfg)
                opt = init_opt_state(params, kind="adamw")
                losses = []
                for k in range(3):
                    params, opt, loss, m = driver.step(params, opt, k)
                    losses.append(float(loss))
                results["|".join([scheme, str(depth), str(int(staging))])] \\
                    = {"losses": losses, "digest": digest(params)}
    if rank == 0:
        print("MATRIX" + json.dumps(results, sort_keys=True))
""")

MATRIX_PARENT_BODY = textwrap.dedent("""
    import hashlib, json, os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import numpy as np
    import jax
    from repro.core.partition import build_layout, partition_graph
    from repro.data.synthetic_graph import make_power_law_graph
    from repro.launch import multihost
    from repro.models.gnn import GNNConfig, gnn_loss, init_gnn_params
    from repro.optim import init_opt_state
    from repro.pipeline import (Pipeline, PipelineSpec, PlanSpec,
                                PrefetchSpec, SamplerSpec)

    P = 2
    ds = make_power_law_graph(600, 6, num_features=8, num_classes=4, seed=0)
    assign = partition_graph(ds.graph, P, ds.labeled_mask, seed=0)
    layout = build_layout(ds.graph, ds.features, ds.labels, assign, P)
    cfg = GNNConfig(in_dim=8, hidden_dim=8, num_classes=4, num_layers=2,
                    fanouts=(3, 3), dropout=0.0)
    def loss_fn(p, mfgs, h, y, v):
        return gnn_loss(p, mfgs, h, y, v, cfg)

    def digest(tree):
        h = hashlib.sha256()
        for leaf in jax.tree.leaves(tree):
            arr = (leaf.addressable_data(0)
                   if hasattr(leaf, "addressable_data") else leaf)
            h.update(np.ascontiguousarray(np.asarray(arr)).tobytes())
        return h.hexdigest()

    def run_matrix(executor):
        results, leaves = {}, {}
        for scheme in ("vanilla", "hybrid", "hybrid_partial(0.25)"):
            for depth in (0, 2):
                for staging in (False, True):
                    spec = PipelineSpec(
                        plan=PlanSpec(num_parts=P, scheme=scheme),
                        sampler=SamplerSpec(fanouts=cfg.fanouts,
                                            backend="reference"),
                        executor=executor,
                        prefetch=PrefetchSpec(depth=depth, staging=staging))
                    pipe = Pipeline.from_layout(layout, spec)
                    driver = pipe.train_driver(loss_fn, batch=8, lr=0.01)
                    params = init_gnn_params(jax.random.key(0), cfg)
                    opt = init_opt_state(params, kind="adamw")
                    losses = []
                    for k in range(3):
                        params, opt, loss, m = driver.step(params, opt, k)
                        losses.append(float(loss))
                    key = "|".join([scheme, str(depth), str(int(staging))])
                    results[key] = {"losses": losses,
                                    "digest": digest(params)}
                    leaves[key] = [
                        np.asarray(l.addressable_data(0)
                                   if hasattr(l, "addressable_data") else l)
                        for l in jax.tree.leaves(params)]
        return results, leaves

    vref, vleaves = run_matrix("vmap")
    sref, sleaves = run_matrix("shard_map")
    # vmap: exact losses; params to float tolerance only — fusing the
    # step with the adamw update lets XLA reassociate the vmapped
    # program's bias-grad sum, drifting bias leaves ~1 ulp from the
    # per-shard (shard_map/multiprocess) programs.
    for key in sref:
        assert sref[key]["losses"] == vref[key]["losses"], \\
            ("vmap losses", key, vref[key], sref[key])
        for a, b in zip(vleaves[key], sleaves[key]):
            np.testing.assert_allclose(a, b, rtol=0, atol=1e-6,
                                       err_msg=str(("vmap params", key)))
    print("single-process refs agree across", len(sref), "cells",
          flush=True)
    ref = sref

    log_dir = multihost.launch([sys.executable, "-c", WORKER],
                               num_procs=2, local_devices=1, timeout=1500)
    out = open(os.path.join(log_dir, "rank0.out")).read()
    lines = [l for l in out.splitlines() if l.startswith("MATRIX")]
    assert lines, "no MATRIX record in rank0.out:\\n" + out[-2000:]
    mp = json.loads(lines[-1][len("MATRIX"):])
    assert set(mp) == set(ref)
    diffs = {k: (ref[k], mp[k]) for k in ref if mp[k] != ref[k]}
    assert not diffs, "multiprocess != shard_map: " + json.dumps(diffs)
    print("MULTIHOST_MATRIX_OK")
""")

MATRIX_PARENT = ("WORKER = " + repr(MATRIX_WORKER) + "\n"
                 + MATRIX_PARENT_BODY)


@pytest.mark.multihost
def test_multiprocess_bit_equivalence_matrix(subproc):
    """Every {scheme} x {prefetch depth} x {staging} cell yields
    bit-identical losses and parameters between shard_map and the
    2-process multiprocess executor (rank-local feature builds), and
    exact losses / float-tolerance parameters against vmap (see module
    docstring for why vmap's fused update drifts bias leaves ~1 ulp)."""
    subproc.run_code(MATRIX_PARENT, expect="MULTIHOST_MATRIX_OK",
                     timeout=1800)


TRAIN_GNN_WORKERFAIL = textwrap.dedent("""
    import os, sys
    from repro.launch import multihost
    crash = dict(os.environ)
    crash["REPRO_MH_TEST_CRASH_RANK"] = "1"
    script = (
        "import os, sys, time\\n"
        "if os.environ['REPRO_MH_RANK'] == "
        "os.environ['REPRO_MH_TEST_CRASH_RANK']:\\n"
        "    sys.stderr.write('deliberate crash before jax init\\\\n')\\n"
        "    sys.exit(7)\\n"
        "from repro.launch import multihost as mh\\n"
        "mh.init_from_env()\\n"       # healthy rank blocks on coordinator
        "import time; time.sleep(600)\\n"
    )
    try:
        multihost.launch([sys.executable, "-c", script], num_procs=2,
                         timeout=300, env=crash)
    except multihost.WorkerFailure as e:
        assert e.rank == 1 and e.returncode == 7, e
        assert "deliberate crash" in e.stderr_tail, e.stderr_tail
        print("WORKER_FAILURE_PROPAGATED_OK")
    else:
        raise SystemExit("launch() did not raise WorkerFailure")
""")


@pytest.mark.multihost
def test_worker_death_during_distributed_init(subproc):
    """A rank that dies while its peers are inside
    ``jax.distributed.initialize`` (the real-world hang: the survivor
    blocks on the coordinator barrier) is detected and reported instead
    of hanging until the fleet timeout."""
    subproc.run_code(TRAIN_GNN_WORKERFAIL,
                     expect="WORKER_FAILURE_PROPAGATED_OK", timeout=600)
