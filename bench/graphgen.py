"""The benchmark's graphs: Chung-Lu power-law topologies with a random
labelled split, made from a configuration's own graph seed and cached in
the checkout.

The family is ``powerlaw(alpha)`` of ``repro.data``: every node gets the
weight ``w ~ Pareto(alpha) + 1``, both endpoints of each of
``num_nodes * avg_degree`` edges are drawn independently in proportion to
``w``, and self-loops are dropped.  The split is ``random(frac)``: a node
is labelled when a SplitMix64 hash of its id and the graph seed falls
under ``frac``.  This is a vectorised copy, not a call into the program:
each node's in-degree is one multinomial draw, and the sources are drawn
by inverse CDF through a guide table, in fixed chunks over threads.  The
edges so come out in CSC order (in-neighbours of node 0, then node 1, ...)
with no sort.  The draw depends on the graph seed and the chunk size
alone, never on ``--seed`` or on the number of threads.

The first run on a graph writes the arrays under
``bench/.cache/graphs/<key>/``, where the key hashes the configuration's
graph fields alone; later runs load them, whichever configuration on that
graph they run.  Features are not
cached: ``features`` draws them anew in every run, in float32.
"""
from __future__ import annotations

import hashlib
import json
import os
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

CACHE_DIR = Path(__file__).resolve().parent / ".cache" / "graphs"
# bump when the draw changes, so no cache made by an older draw is read
GENERATOR_VERSION = 1
CHUNK = 1 << 24
_ARRAYS = ("indptr", "indices", "classes", "labeled")
# the configuration keys that define the graph, and so the cache key
GRAPH_KEYS = ("graph_family", "alpha", "num_nodes", "avg_degree",
              "num_classes", "labeled_fraction", "graph_seed")


def graph_fields(config: dict) -> dict:
    """The graph-defining keys of a configuration."""
    return {k: config[k] for k in GRAPH_KEYS}


def _threads() -> int:
    return max(1, min(16, os.cpu_count() or 1))


def _chunks(total: int):
    return [(lo, min(lo + CHUNK, total)) for lo in range(0, total, CHUNK)]


def mix64(x: np.ndarray) -> np.ndarray:
    """SplitMix64 finalizer on uint64 (wraps)."""
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


def random_split(n: int, frac: float, seed: int) -> np.ndarray:
    """``random(frac)``: (n,) bool, node labelled when its hash unit is
    under ``frac``."""
    salt = np.uint64((int(seed) * 0x9E3779B97F4A7C15 + 0x5851F42D) % 2**64)
    key = mix64(np.arange(n, dtype=np.uint64) + salt)
    return key.astype(np.float64) / float(2**64) < frac


def _guide_draw(cdf, guide, rng, size):
    """``size`` ids drawn with P(i) = cdf[i] - cdf[i-1]: the guide table
    gives the first candidate of a uniform's bucket, and a short walk
    finds the first ``i`` with ``cdf[i] > u``."""
    u = rng.random(size)
    idx = guide[(u * (guide.size - 1)).astype(np.int64)]
    active = np.flatnonzero(cdf[idx] <= u)
    while active.size:
        idx[active] += 1
        active = active[cdf[idx[active]] <= u[active]]
    return idx


def generate(graph: dict) -> dict[str, np.ndarray]:
    """The topology, classes and split of ``graph`` (``graph_fields`` of
    a configuration): ``indptr`` (n+1,) int64, ``indices`` (nnz,) int32,
    ``classes`` (n,) int16 and ``labeled`` (n,) bool."""
    if graph["graph_family"] != "powerlaw":
        raise ValueError(f"unknown graph family {graph['graph_family']!r}")
    n, seed = int(graph["num_nodes"]), int(graph["graph_seed"])
    m = n * int(graph["avg_degree"])
    rng = np.random.default_rng([seed, 0])
    w = rng.pareto(float(graph["alpha"]), n) + 1.0
    p = w / w.sum()
    classes = rng.integers(0, int(graph["num_classes"]), n).astype(np.int16)
    counts = rng.multinomial(m, p)

    cdf = np.cumsum(p)
    cdf[-1] = 1.0
    guide = np.searchsorted(cdf, np.arange(n + 1) / n, side="right")
    guide = np.minimum(guide, n - 1)
    src = np.empty(m, np.int32)

    def fill(k_lo_hi):
        k, (lo, hi) = k_lo_hi
        crng = np.random.default_rng([seed, 1, k])
        src[lo:hi] = _guide_draw(cdf, guide, crng, hi - lo)

    with ThreadPoolExecutor(_threads()) as pool:
        list(pool.map(fill, enumerate(_chunks(m))))

    dst = np.repeat(np.arange(n, dtype=np.int32), counts)
    loop = src == dst
    counts -= np.bincount(dst[loop], minlength=n)
    del dst
    indices = src[~loop]
    del src, loop
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(counts, out=indptr[1:])
    labeled = random_split(n, float(graph["labeled_fraction"]), seed)
    return {"indptr": indptr, "indices": indices, "classes": classes,
            "labeled": labeled}


def cache_key(graph: dict) -> str:
    text = json.dumps({"graph": graph, "version": GENERATOR_VERSION},
                      sort_keys=True)
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def load_or_build(graph: dict, cache_dir: Path = CACHE_DIR):
    """``(arrays, built)``: the cached arrays of ``graph``, generated and
    written first when no cache with its key exists."""
    path = Path(cache_dir) / cache_key(graph)
    if path.is_dir():
        return {a: np.load(path / f"{a}.npy") for a in _ARRAYS}, False
    arrays = generate(graph)
    tmp = path.with_name(path.name + f".tmp{os.getpid()}")
    tmp.mkdir(parents=True, exist_ok=True)
    for a in _ARRAYS:
        np.save(tmp / f"{a}.npy", arrays[a])
    try:
        tmp.rename(path)
    except OSError:            # another process wrote it first
        for f in tmp.iterdir():
            f.unlink()
        tmp.rmdir()
    return arrays, True


def features(graph: dict, classes: np.ndarray, dim: int) -> np.ndarray:
    """(n, dim) float32 class-conditioned Gaussian features: a class
    centre ~ N(0, 1) plus N(0, 1.5^2) noise per entry, from the graph
    seed, filled in row chunks over threads."""
    seed = int(graph["graph_seed"])
    n = classes.shape[0]
    centres = np.random.default_rng([seed, 2]).standard_normal(
        (int(graph["num_classes"]), dim), dtype=np.float32)
    out = np.empty((n, dim), np.float32)
    rows = max(1, CHUNK // dim)

    def fill(k_lo):
        k, lo = k_lo
        hi = min(lo + rows, n)
        crng = np.random.default_rng([seed, 3, k])
        crng.standard_normal((hi - lo, dim), dtype=np.float32,
                             out=out[lo:hi])
        out[lo:hi] *= np.float32(1.5)
        out[lo:hi] += centres[classes[lo:hi]]

    with ThreadPoolExecutor(_threads()) as pool:
        list(pool.map(fill, enumerate(range(0, n, rows))))
    return out
