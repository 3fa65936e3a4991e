"""Local multi-process launcher for the ``"multiprocess"`` executor.

One JAX "host" per OS process: the launcher spawns ``num_procs`` worker
processes wired as ranks of a single ``jax.distributed`` job (coordinator
on a freshly-picked localhost port, rank/world-size/coordinator address
carried in ``REPRO_MH_*`` environment variables), captures each rank's
stdout/stderr to per-rank log files, and supervises the fleet:

  * a rank exiting non-zero kills the remaining ranks immediately and
    raises ``WorkerFailure`` carrying that rank's stderr tail — without
    this, the surviving ranks hang forever on the coordinator barrier
    (the failure mode ``tests/test_multihost.py`` provokes on purpose);
  * a wall-clock ``timeout`` bounds the whole run (hang detection).

Every rank runs on the CPU backend (``JAX_PLATFORMS=cpu``, set by
``rank_env``), also on a host with accelerators: a chip belongs to one
process at a time.

Workers call ``init_from_env()`` before any JAX work: it selects the
gloo CPU collectives implementation (XLA's default CPU backend cannot
run cross-process collectives) and calls ``jax.distributed.initialize``
with the env-carried coordinator/rank wiring.

The same module works for any worker entrypoint — ``train_gnn.py`` uses
it to re-exec itself (``--executor multiprocess --num-procs N``), and
tests/benchmarks pass inline ``python -c`` scripts.
"""
from __future__ import annotations

import os
import socket
import subprocess
import sys
import tempfile
import time

# env vars carrying the rank wiring from launcher to workers
ENV_COORDINATOR = "REPRO_MH_COORDINATOR"     # "host:port"
ENV_NUM_PROCS = "REPRO_MH_NUM_PROCS"
ENV_RANK = "REPRO_MH_RANK"
ENV_LOCAL_DEVICES = "REPRO_MH_LOCAL_DEVICES"

DEVICE_FLAG = "--xla_force_host_platform_device_count"


class WorkerFailure(RuntimeError):
    """A worker rank exited non-zero (or died); carries its stderr tail."""

    def __init__(self, rank: int, returncode: int, stderr_tail: str):
        self.rank = rank
        self.returncode = returncode
        self.stderr_tail = stderr_tail
        super().__init__(
            f"multihost worker rank {rank} exited with code {returncode}"
            f"; stderr tail:\n{stderr_tail}")


def pick_port() -> int:
    """A free localhost TCP port for the jax.distributed coordinator."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def rank_env(base_env: dict, *, rank: int, num_procs: int, port: int,
             local_devices: int = 1) -> dict:
    """The environment for worker ``rank``: ``REPRO_MH_*`` wiring, an
    ``XLA_FLAGS`` host-device count (replacing any pre-existing
    ``--xla_force_host_platform_device_count`` so the launcher's count
    wins) and ``JAX_PLATFORMS=cpu``.  The fleet is a CPU simulation of
    a multi-host job: on a chip host, ranks that reached for the chip
    would race each other (and the parent) for it.  The path across
    chips is the ``shard_map`` executor in one process."""
    env = dict(base_env)
    env["JAX_PLATFORMS"] = "cpu"
    env[ENV_COORDINATOR] = f"127.0.0.1:{port}"
    env[ENV_NUM_PROCS] = str(num_procs)
    env[ENV_RANK] = str(rank)
    env[ENV_LOCAL_DEVICES] = str(local_devices)
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if not f.startswith(DEVICE_FLAG)]
    flags.append(f"{DEVICE_FLAG}={local_devices}")
    env["XLA_FLAGS"] = " ".join(flags)
    return env


def is_worker(env=None) -> bool:
    """True when this process was spawned by ``launch`` (rank env set)."""
    return ENV_RANK in (os.environ if env is None else env)


def init_from_env(env=None):
    """Initialize this worker's JAX distributed runtime from the
    launcher-provided environment.  MUST run before any JAX backend use
    (device queries, array creation, tracing).

    Returns ``(rank, num_procs)``.
    """
    env = os.environ if env is None else env
    import jax

    # XLA's default CPU collectives refuse cross-process programs
    # ("Multiprocess computations aren't implemented on the CPU
    # backend"); gloo implements them.
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    rank = int(env[ENV_RANK])
    num_procs = int(env[ENV_NUM_PROCS])
    jax.distributed.initialize(coordinator_address=env[ENV_COORDINATOR],
                               num_processes=num_procs,
                               process_id=rank)
    return rank, num_procs


def rank_trace_path(base: str, rank: int) -> str:
    """Per-rank trace file for a fleet whose merged trace is ``base``.

    Workers export to ``{base}.rank{r}``; the supervisor merges the rank
    files into ``base`` with ``merge_rank_traces`` after the fleet
    exits.
    """
    return f"{base}.rank{int(rank)}"


def merge_rank_traces(base: str, num_procs: int,
                      out: str | None = None) -> dict:
    """Merge the fleet's per-rank trace files into one Perfetto-loadable
    trace with rank-as-pid mapping.

    Reads ``rank_trace_path(base, r)`` for every rank and writes the
    merged trace to ``out`` (default: ``base`` itself).  Each rank
    becomes one process track group (``pid=r``, named ``rank{r}``);
    virtual pids inside a rank (e.g. the serving loop's request lanes)
    are shifted into rank-unique ranges.  Returns the merged dict.
    """
    from repro.obs.trace import merge_traces

    paths = [rank_trace_path(base, r) for r in range(num_procs)]
    return merge_traces(paths, out if out is not None else base)


def _stderr_tail(log_dir: str, rank: int, limit: int = 4000) -> str:
    path = os.path.join(log_dir, f"rank{rank}.err")
    try:
        with open(path, "rb") as f:
            f.seek(0, os.SEEK_END)
            size = f.tell()
            f.seek(max(0, size - limit))
            return f.read().decode("utf-8", "replace")
    except OSError:
        return f"<no stderr captured at {path}>"


def _kill_all(procs, grace: float = 5.0) -> None:
    for p in procs:
        if p.poll() is None:
            p.terminate()
    deadline = time.monotonic() + grace
    for p in procs:
        if p.poll() is None:
            try:
                p.wait(timeout=max(0.0, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()


def launch(argv, *, num_procs: int, local_devices: int = 1,
           timeout: float = 600.0, log_dir: str | None = None,
           env: dict | None = None, poll_interval: float = 0.1) -> str:
    """Run ``argv`` as ``num_procs`` ranks of one jax.distributed job.

    Parameters
    ----------
    argv : list[str]
        Worker command line, e.g. ``[sys.executable, "-m",
        "repro.launch.train_gnn", ...]`` or ``[sys.executable, "-c",
        script]``.  Every rank runs the identical command; workers read
        their rank from the environment (``init_from_env``).
    num_procs : int
        World size (all ranks local to this machine).
    local_devices : int, default 1
        Host-placeholder devices per rank
        (``--xla_force_host_platform_device_count``); the global mesh
        spans ``num_procs * local_devices`` devices.
    timeout : float, default 600
        Wall-clock bound on the whole run; on expiry the fleet is killed
        and ``TimeoutError`` is raised (hang detection — a lost rank
        leaves the others blocked on collective barriers forever).
    log_dir : str, optional
        Directory for per-rank ``rank{r}.out`` / ``rank{r}.err`` capture
        (a fresh temp dir when omitted).  Returned on success.
    env : dict, optional
        Base environment (defaults to ``os.environ``).

    Raises
    ------
    WorkerFailure
        A rank exited non-zero; remaining ranks are killed first and the
        failing rank's stderr tail rides on the exception.
    TimeoutError
        The fleet outlived ``timeout``.
    """
    if num_procs < 1:
        raise ValueError(f"num_procs must be >= 1, got {num_procs}")
    port = pick_port()
    log_dir = log_dir or tempfile.mkdtemp(prefix="repro-multihost-")
    os.makedirs(log_dir, exist_ok=True)
    base = dict(os.environ if env is None else env)

    procs, files = [], []
    try:
        for r in range(num_procs):
            out = open(os.path.join(log_dir, f"rank{r}.out"), "wb")
            err = open(os.path.join(log_dir, f"rank{r}.err"), "wb")
            files += [out, err]
            procs.append(subprocess.Popen(
                argv, stdout=out, stderr=err,
                env=rank_env(base, rank=r, num_procs=num_procs,
                             port=port, local_devices=local_devices)))

        deadline = time.monotonic() + timeout
        while True:
            codes = [p.poll() for p in procs]
            failed = next((r for r, c in enumerate(codes)
                           if c not in (None, 0)), None)
            if failed is not None:
                _kill_all(procs)
                raise WorkerFailure(failed, codes[failed],
                                    _stderr_tail(log_dir, failed))
            if all(c == 0 for c in codes):
                return log_dir
            if time.monotonic() > deadline:
                _kill_all(procs)
                status = ", ".join(
                    f"rank{r}={'running' if c is None else c}"
                    for r, c in enumerate(codes))
                alive = next((r for r, c in enumerate(codes)
                              if c is None), 0)
                raise TimeoutError(
                    f"multihost run exceeded {timeout:.0f}s ({status}); "
                    f"rank {alive} stderr tail:\n"
                    f"{_stderr_tail(log_dir, alive)}")
            time.sleep(poll_interval)
    finally:
        _kill_all(procs)
        for f in files:
            f.close()
