"""Partitioner invariants (the METIS-replacement contract) + layouts."""
import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.graph import validate_csc
from repro.core.partition import (build_hybrid, build_layout, build_vanilla,
                                  edge_cut, labeled_ids, partition_graph,
                                  seeds_per_worker, seeds_per_worker_host)
from repro.data.synthetic_graph import make_power_law_graph


@pytest.fixture(scope="module")
def ds():
    return make_power_law_graph(600, 5, num_features=10, num_classes=4,
                                labeled_fraction=0.4, seed=5)


@pytest.mark.parametrize("P", [2, 4, 8])
def test_partition_invariants(ds, P):
    assign = partition_graph(ds.graph, P, ds.labeled_mask, seed=0)
    n = ds.graph.num_nodes
    # every node assigned exactly once
    assert assign.shape == (n,)
    assert assign.min() >= 0 and assign.max() < P
    # node balance within slack
    counts = np.bincount(assign, minlength=P)
    assert counts.max() <= 1.10 * n / P + 1
    # labeled balance within slack (paper: equal seeds per machine)
    lab = np.bincount(assign[ds.labeled_mask], minlength=P)
    assert lab.max() <= 1.10 * ds.labeled_mask.sum() / P + 2
    # edge-cut beats random partitioning on a homophilous graph
    rng = np.random.default_rng(1)
    random_assign = rng.integers(0, P, n)
    assert edge_cut(ds.graph, assign) <= edge_cut(ds.graph, random_assign)


@given(st.integers(2, 6), st.integers(0, 100))
@settings(max_examples=8, deadline=None)
def test_partition_total_assignment(P, seed):
    ds = make_power_law_graph(120, 4, num_features=4, num_classes=3,
                              seed=seed % 7)
    assign = partition_graph(ds.graph, P, ds.labeled_mask, seed=seed)
    assert (assign >= 0).all()
    counts = np.bincount(assign, minlength=P)
    assert counts.sum() == ds.graph.num_nodes


def test_layout_contiguous_ownership(ds):
    P = 4
    assign = partition_graph(ds.graph, P, ds.labeled_mask, seed=0)
    layout = build_layout(ds.graph, ds.features, ds.labels, assign, P)
    validate_csc(layout.graph)
    offsets = np.asarray(layout.offsets)
    assert offsets[0] == 0 and offsets[-1] == ds.graph.num_nodes
    # relabeled features/labels match originals through the permutation
    for p in range(P):
        k = offsets[p + 1] - offsets[p]
        ids_old = layout.perm[offsets[p]:offsets[p + 1]]
        np.testing.assert_allclose(np.asarray(layout.features[p, :k]),
                                   ds.features[ids_old], rtol=1e-6)
        np.testing.assert_array_equal(np.asarray(layout.labels[p, :k]),
                                      ds.labels[ids_old])
    # owner_of agrees with the ranges
    ids = jnp.arange(ds.graph.num_nodes, dtype=jnp.int32)
    owners = np.asarray(layout.owner_of(ids))
    for p in range(P):
        assert (owners[offsets[p]:offsets[p + 1]] == p).all()


def test_vanilla_plan_edges_match_global(ds):
    """Each worker's local CSC is exactly the slice of the global CSC."""
    P = 4
    assign = partition_graph(ds.graph, P, ds.labeled_mask, seed=0)
    layout = build_layout(ds.graph, ds.features, ds.labels, assign, P)
    plan = build_vanilla(layout)
    g_indptr = np.asarray(layout.graph.indptr)
    g_indices = np.asarray(layout.graph.indices)
    offsets = np.asarray(layout.offsets)
    for p in range(P):
        lo, hi = offsets[p], offsets[p + 1]
        li = np.asarray(plan.local_indptr[p])
        lx = np.asarray(plan.local_indices[p])
        n_local = hi - lo
        expected_rows = g_indptr[lo:hi + 1] - g_indptr[lo]
        np.testing.assert_array_equal(li[:n_local + 1], expected_rows)
        nnz = expected_rows[-1]
        np.testing.assert_array_equal(lx[:nnz],
                                      g_indices[g_indptr[lo]:g_indptr[hi]])


def test_seeds_zero_labeled_partition_yields_all_minus_one(ds):
    """Regression: a partition with no labeled nodes must emit an all -1
    row — its hash ranks are all-sentinel and must never leak as seeds."""
    import dataclasses
    P = 4
    assign = partition_graph(ds.graph, P, ds.labeled_mask, seed=0)
    layout = build_layout(ds.graph, ds.features, ds.labels, assign, P)
    lab = np.asarray(layout.labels).copy()
    lab[1, :] = -1                                 # strip partition 1
    layout0 = dataclasses.replace(layout, labels=jnp.asarray(lab))
    seeds = np.asarray(seeds_per_worker(layout0, 16, epoch_salt=5))
    assert (seeds[1] == -1).all()
    # other partitions unaffected: still local, labeled, deduplicated
    offsets = np.asarray(layout.offsets)
    for p in (0, 2, 3):
        s = seeds[p][seeds[p] >= 0]
        assert s.size > 0
        assert ((s >= offsets[p]) & (s < offsets[p + 1])).all()


def test_seeds_batch_larger_than_n_max_pads(ds):
    """Regression: batch > n_max must return the full (P, batch) shape,
    -1 padded past each worker's labeled supply — never truncated."""
    P = 4
    assign = partition_graph(ds.graph, P, ds.labeled_mask, seed=0)
    layout = build_layout(ds.graph, ds.features, ds.labels, assign, P)
    labels = np.asarray(layout.labels)
    batch = layout.n_max + 13
    seeds = np.asarray(seeds_per_worker(layout, batch, epoch_salt=2))
    assert seeds.shape == (P, batch)
    offsets = np.asarray(layout.offsets)
    for p in range(P):
        row = seeds[p]
        valid = row[row >= 0]
        # every labeled node of the partition is drawn exactly once
        assert valid.size == (labels[p] >= 0).sum()
        assert len(set(valid.tolist())) == valid.size
        assert ((valid >= offsets[p]) & (valid < offsets[p + 1])).all()
        # padding is contiguous at the tail, all -1
        assert (row[valid.size:] == -1).all()


def test_seeds_drawn_from_local_labeled(ds):
    P = 4
    assign = partition_graph(ds.graph, P, ds.labeled_mask, seed=0)
    layout = build_layout(ds.graph, ds.features, ds.labels, assign, P)
    seeds = np.asarray(seeds_per_worker(layout, 20, epoch_salt=3))
    offsets = np.asarray(layout.offsets)
    labels = np.asarray(layout.labels)
    for p in range(P):
        s = seeds[p]
        s = s[s >= 0]
        assert len(set(s.tolist())) == len(s)          # no duplicates
        assert ((s >= offsets[p]) & (s < offsets[p + 1])).all()
        assert (labels[p, s - offsets[p]] >= 0).all()  # labeled only


# -- the labeled-only seed draw against the n-wide draw it replaced --------

def _n_wide_seeds(layout, batch, epoch_salt):
    """The former draw, kept as the oracle: hash every column of the
    ``(P, n_max)`` table, mask unlabeled columns to the top key, stable
    argsort all of them, keep the first ``batch``."""
    from repro.core.graph import mix64
    P = layout.num_parts
    offsets = np.asarray(layout.offsets).astype(np.int64)
    labels = np.asarray(layout.labels)
    n_max = labels.shape[1]
    gids = offsets[:-1, None] + np.arange(n_max, dtype=np.int64)[None, :]
    salt64 = np.uint64((int(epoch_salt) * 0x9E3779B97F4A7C15) % (2 ** 64))
    key = mix64(gids.astype(np.uint64) + salt64)
    key = np.where(labels >= 0, key, np.uint64(np.iinfo(np.uint64).max))
    m = min(batch, n_max)
    order = np.argsort(key, axis=1, kind="stable")[:, :m]
    picked = np.take_along_axis(gids, order, axis=1)
    take = np.minimum((labels >= 0).sum(axis=1), m)
    valid = np.arange(m)[None, :] < take[:, None]
    out = np.full((P, batch), -1, np.int32)
    out[:, :m] = np.where(valid, picked, -1)
    return out


@pytest.fixture(scope="module")
def draw_graph():
    return make_power_law_graph(2000, 4, num_features=4, num_classes=3,
                                seed=7)


_layouts = {}


def _draw_layout(graph_ds, P, frac):
    """A layout over ``graph_ds``'s graph with ``frac`` of its nodes
    labeled and a random (unbalanced) assignment to ``P`` workers."""
    if (P, frac) not in _layouts:
        n = graph_ds.graph.num_nodes
        rng = np.random.default_rng(P * 100 + int(frac * 100))
        labels = np.where(rng.random(n) < frac, graph_ds.labels,
                          -1).astype(np.int32)
        assign = rng.integers(0, P, n)
        _layouts[P, frac] = build_layout(graph_ds.graph, graph_ds.features,
                                         labels, assign, P)
    return _layouts[P, frac]


@pytest.mark.parametrize("salt", [0, 1, 2 ** 32 - 1, 2 ** 70 + 12345])
@pytest.mark.parametrize("frac", [0.0, 0.01, 0.5, 1.0])
@pytest.mark.parametrize("P", [1, 2, 4])
def test_labeled_draw_matches_n_wide_draw(draw_graph, P, frac, salt):
    """Ranking only the labeled ids gives the n-wide draw's seeds, bit
    for bit: batch below, at and above a worker's labeled count, and
    above ``n_max``; workers with no labeled node included."""
    layout = _draw_layout(draw_graph, P, frac)
    counts = [ids.size for ids in labeled_ids(layout)]
    assert counts == (np.asarray(layout.labels) >= 0).sum(axis=1).tolist()
    table = labeled_ids(layout)
    for batch in sorted({1, max(min(counts) // 2, 1), min(counts),
                         max(counts), max(counts) + 1, layout.n_max + 7}):
        want = _n_wide_seeds(layout, batch, salt)
        got = seeds_per_worker_host(layout, batch, salt, labeled=table)
        assert got.dtype == np.int32 and got.shape == (P, batch)
        np.testing.assert_array_equal(got, want)
        np.testing.assert_array_equal(
            seeds_per_worker_host(layout, batch, salt), want)
        np.testing.assert_array_equal(
            np.asarray(seeds_per_worker(layout, batch, salt)), want)


def _draw_pipeline(layout):
    from repro.pipeline import Pipeline, PipelineSpec, PlanSpec, SamplerSpec
    spec = PipelineSpec(plan=PlanSpec(num_parts=layout.num_parts,
                                      scheme="hybrid"),
                        sampler=SamplerSpec(fanouts=(3, 3),
                                            backend="reference"))
    return Pipeline.from_layout(layout, spec)


@pytest.mark.parametrize("strategy", ["counter", "fold"])
def test_seed_stream_draw_matches_n_wide_draw(draw_graph, strategy):
    from repro.pipeline import SeedStream
    layout = _draw_layout(draw_graph, 4, 0.5)
    stream = SeedStream(_draw_pipeline(layout), batch=96, strategy=strategy,
                        base_salt=2 ** 31 + 5)
    for k in (0, 1, 2, 17, 1000):
        np.testing.assert_array_equal(
            stream.seeds_host(k),
            _n_wide_seeds(layout, 96, stream.salt_int(k)))


def test_seed_draw_hashes_labeled_ids_only(draw_graph, monkeypatch):
    """One ``Pipeline.seeds_host`` call hashes exactly each worker's
    labeled ids, never all n nodes, and reads the table the pipeline
    built once rather than building one per draw."""
    import repro.core.partition as part
    layout = _draw_layout(draw_graph, 4, 0.01)
    builds = []
    real_table = part.labeled_ids
    monkeypatch.setattr(part, "labeled_ids",
                        lambda lay: builds.append(lay) or real_table(lay))
    pipe = _draw_pipeline(layout)
    assert len(builds) == 1
    hashed = []
    real_mix = part.mix64
    monkeypatch.setattr(part, "mix64",
                        lambda x: hashed.append(x.size) or real_mix(x))
    counts = (np.asarray(layout.labels) >= 0).sum(axis=1)
    pipe.seeds_host(8, epoch_salt=3)
    assert hashed == [c for c in counts.tolist() if c]
    assert sum(hashed) < layout.graph.num_nodes
    pipe.seeds_host(8, epoch_salt=4)
    assert len(builds) == 1


def _unmix64(y: int) -> int:
    """Inverse of ``mix64`` on one Python int."""
    def unshift(v, k):
        x = v
        for _ in range(64 // k + 1):
            x = v ^ (x >> k)
        return x
    mod = 2 ** 64
    y = unshift(y, 31)
    y = y * pow(0x94D049BB133111EB, -1, mod) % mod
    y = unshift(y, 27)
    y = y * pow(0xBF58476D1CE4E5B9, -1, mod) % mod
    return unshift(y, 30)


def test_labeled_node_with_top_key_is_ranked(draw_graph):
    """A labeled node whose key is ``2**64 - 1`` is drawn last among the
    labeled nodes, as any other key would be; the n-wide draw tied it
    with the unlabeled columns and let an unlabeled id through."""
    from repro.core.graph import mix64
    layout = _draw_layout(draw_graph, 1, 0.01)
    ids = labeled_ids(layout)[0]
    target = int(ids[-1])
    assert target > ids.size                  # unlabeled columns sit below it
    # the salt whose folded value sends ``target`` to the top key
    salt = ((_unmix64(2 ** 64 - 1) - target)
            * pow(0x9E3779B97F4A7C15, -1, 2 ** 64)) % 2 ** 64
    salt64 = np.uint64(salt * 0x9E3779B97F4A7C15 % 2 ** 64)
    assert mix64(np.array([target], np.uint64) + salt64)[0] == 2 ** 64 - 1
    got = seeds_per_worker_host(layout, ids.size, salt)[0]
    assert got[-1] == target
    assert sorted(got.tolist()) == ids.tolist()
    assert target not in _n_wide_seeds(layout, ids.size, salt)[0]
