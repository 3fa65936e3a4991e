"""Per-kernel validation: shape/dtype sweeps against the pure-jnp oracles
(interpret=True executes the kernel body exactly as written)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.sampler import sample_mfgs, sample_level
from repro.data.synthetic_graph import make_power_law_graph
from repro.kernels.fused_sample import fused_sample
from repro.kernels.ops import fused_sample_level
from repro.kernels.ref import (ref_fused_sample, ref_mean_aggregate,
                               ref_windowed_fused_sample)
from repro.kernels.sage_aggregate import sage_aggregate


@pytest.fixture(scope="module")
def graph():
    return make_power_law_graph(400, 5, num_features=8, num_classes=3,
                                seed=2).graph


# ---------------------------------------------------------------------------
# fused_sample
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fanout", [1, 2, 5, 16])
@pytest.mark.parametrize("n_seeds", [1, 7, 32])
def test_fused_sample_matches_oracle(graph, fanout, n_seeds):
    rng = np.random.default_rng(fanout * 100 + n_seeds)
    seeds = jnp.asarray(rng.choice(graph.num_nodes, n_seeds, replace=False)
                        .astype(np.int32))
    s_k, r_k, ovf = fused_sample(graph.indptr, graph.indices, seeds,
                                 jnp.uint32(9), fanout=fanout, window=512)
    s_r, r_r = ref_fused_sample(graph, seeds, fanout, 9)
    np.testing.assert_array_equal(np.asarray(s_k), np.asarray(s_r))
    np.testing.assert_array_equal(np.asarray(r_k), np.asarray(r_r))
    assert int(ovf) == 0          # window 512 covers every degree here


def test_fused_sample_padded_seeds(graph):
    seeds = jnp.array([5, -1, 9, -1, 0], jnp.int32)
    s_k, r_k, _ = fused_sample(graph.indptr, graph.indices, seeds,
                               jnp.uint32(3), fanout=4, window=512)
    s_r, r_r = ref_fused_sample(graph, seeds, 4, 3)
    np.testing.assert_array_equal(np.asarray(s_k), np.asarray(s_r))
    np.testing.assert_array_equal(np.asarray(r_k), np.asarray(r_r))


@given(st.integers(1, 8), st.integers(0, 2 ** 20))
@settings(max_examples=15, deadline=None)
def test_fused_sample_property(graph, fanout, salt):
    rng = np.random.default_rng(salt % 991)
    seeds = jnp.asarray(rng.choice(graph.num_nodes, 6, replace=False)
                        .astype(np.int32))
    s_k, r_k, _ = fused_sample(graph.indptr, graph.indices, seeds,
                               jnp.uint32(salt), fanout=fanout, window=512)
    s_r, r_r = ref_fused_sample(graph, seeds, fanout, salt)
    np.testing.assert_array_equal(np.asarray(s_k), np.asarray(s_r))
    np.testing.assert_array_equal(np.asarray(r_k), np.asarray(r_r))


def test_fused_sample_hub_window_overflow(graph):
    """Degrees above the window must draw uniformly from the *visible*
    neighbor set (bit-equal to a window-truncated reference) and be
    counted in overflow_count — not silently biased onto the last column
    (the old ``col = min(col, window-1)`` clamp)."""
    deg = np.asarray(graph.degrees())
    window = 8
    hubs = np.nonzero(deg > window)[0]
    assert hubs.size > 0, "fixture graph needs hubs wider than the window"
    seeds = jnp.asarray(
        np.concatenate([hubs[:8], np.nonzero(deg <= window)[0][:4]])
        .astype(np.int32))

    for fanout, salt in ((4, 7), (16, 123)):
        s_k, r_k, ovf = fused_sample(graph.indptr, graph.indices, seeds,
                                     jnp.uint32(salt), fanout=fanout,
                                     window=window)
        s_r, r_r, ovf_r = ref_windowed_fused_sample(graph, seeds, fanout,
                                                    salt, window)
        np.testing.assert_array_equal(np.asarray(s_k), np.asarray(s_r))
        np.testing.assert_array_equal(np.asarray(r_k), np.asarray(r_r))
        assert int(ovf) == ovf_r > 0


def test_fused_level_equals_reference_level(graph):
    """Kernel-backed MFG construction == two-step reference, end to end."""
    seeds = jnp.arange(10, dtype=jnp.int32) * 13
    for salt in (1, 99):
        a = sample_mfgs(graph, seeds, (4, 3), salt,
                        level_fn=fused_sample_level)
        b = sample_mfgs(graph, seeds, (4, 3), salt, level_fn=sample_level)
        for ma, mb in zip(a, b):
            for x, y in zip(ma.tree_flatten()[0], mb.tree_flatten()[0]):
                np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# sage_aggregate
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("S,F,N,D", [
    (1, 1, 1, 1), (4, 3, 10, 8), (130, 7, 300, 16), (64, 15, 64, 130),
    (128, 10, 128, 128), (37, 5, 200, 33),
])
def test_sage_aggregate_shapes(S, F, N, D):
    rng = np.random.default_rng(S + F + N + D)
    edges = rng.integers(-1, N, (S, F)).astype(np.int32)
    h = rng.normal(0, 1, (N, D)).astype(np.float32)
    out = sage_aggregate(jnp.asarray(edges), jnp.asarray(h),
                         tile_s=32, tile_n=32)
    ref = ref_mean_aggregate(jnp.asarray(edges), jnp.asarray(h))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 3e-2)])
def test_sage_aggregate_dtypes(dtype, tol):
    rng = np.random.default_rng(7)
    edges = rng.integers(-1, 50, (40, 6)).astype(np.int32)
    h = jnp.asarray(rng.normal(0, 1, (50, 24)), dtype)
    out = sage_aggregate(jnp.asarray(edges), h, tile_s=16, tile_n=16)
    ref = ref_mean_aggregate(jnp.asarray(edges), h)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("tile_s,tile_n", [(8, 8), (16, 64), (128, 128)])
def test_sage_aggregate_tilings(tile_s, tile_n):
    rng = np.random.default_rng(11)
    edges = rng.integers(-1, 90, (70, 9)).astype(np.int32)
    h = rng.normal(0, 1, (90, 40)).astype(np.float32)
    out = sage_aggregate(jnp.asarray(edges), jnp.asarray(h),
                         tile_s=tile_s, tile_n=tile_n)
    ref = ref_mean_aggregate(jnp.asarray(edges), jnp.asarray(h))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_sage_aggregate_all_invalid_rows():
    edges = jnp.full((5, 3), -1, jnp.int32)
    h = jnp.ones((10, 4), jnp.float32)
    out = sage_aggregate(edges, h, tile_s=8, tile_n=8)
    np.testing.assert_array_equal(np.asarray(out), np.zeros((5, 4)))


def test_sage_aggregate_duplicate_edges_weighting():
    """With-replacement duplicates must be weighted by multiplicity."""
    edges = jnp.array([[2, 2, 0]], jnp.int32)
    h = jnp.asarray(np.arange(12).reshape(4, 3), jnp.float32)
    out = sage_aggregate(edges, h, tile_s=8, tile_n=8)
    expected = (2 * h[2] + h[0]) / 3
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(expected))


# ---------------------------------------------------------------------------
# gather_rows (double-buffered feature row gather, repro.kernels.gather)
# ---------------------------------------------------------------------------

from repro.kernels.gather import gather_rows, gather_rows_reference


@pytest.mark.parametrize("n_ids,rows,D,block", [
    (32, 50, 8, 8), (10, 50, 8, 8),       # non-divisible N pads with -1
    (8, 1, 3, 4), (64, 200, 16, 16),
])
def test_gather_rows_matches_oracle(n_ids, rows, D, block):
    rng = np.random.default_rng(3)
    table = rng.normal(0, 1, (rows, D)).astype(np.float32)
    ids = rng.integers(0, rows, n_ids).astype(np.int32)
    got = gather_rows(jnp.asarray(table), jnp.asarray(ids), block=block)
    ref = gather_rows_reference(jnp.asarray(table), jnp.asarray(ids))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    np.testing.assert_array_equal(np.asarray(got), table[ids])


def test_gather_rows_invalid_ids_zeroed():
    """-1 padding and out-of-range ids produce zero rows, matching the
    oracle (the DMA reads a clamped row, the mask kills it)."""
    rng = np.random.default_rng(4)
    table = rng.normal(0, 1, (37, 8)).astype(np.float32)
    ids = np.array([0, -1, 36, 37, 1000, 5, -1, 2], np.int32)
    got = np.asarray(gather_rows(jnp.asarray(table), jnp.asarray(ids)))
    ref = np.asarray(gather_rows_reference(jnp.asarray(table),
                                           jnp.asarray(ids)))
    np.testing.assert_array_equal(got, ref)
    for j, g in enumerate(ids):
        if 0 <= g < 37:
            np.testing.assert_array_equal(got[j], table[g])
        else:
            np.testing.assert_array_equal(got[j], 0)


def test_gather_rows_all_invalid():
    table = jnp.ones((5, 4), jnp.float32)
    ids = jnp.full((9,), -1, jnp.int32)
    got = gather_rows(table, ids)
    np.testing.assert_array_equal(np.asarray(got), np.zeros((9, 4)))


def test_fused_level_overflow_sink():
    """Satellite: fused_sample_level reports window-truncated seeds
    through ``overflow_sink`` instead of discarding the kernel's count."""
    g = make_power_law_graph(400, 8, num_features=4, num_classes=3,
                             seed=2).graph
    deg = np.asarray(g.degrees())
    window = 4
    hubs = np.nonzero(deg > window)[0]
    assert hubs.size > 0
    seeds = jnp.asarray(hubs[:8].astype(np.int32))
    sink = []
    fused_sample_level(g, seeds, 3, jnp.uint32(1), overflow_sink=sink,
                       window=window)
    assert len(sink) == 1 and int(sink[0]) > 0
    assert fused_sample_level.supports_overflow_sink


# ---------------------------------------------------------------------------
# interpret mode follows the backend
# ---------------------------------------------------------------------------

def test_interpret_follows_backend(monkeypatch):
    """Kernels interpret exactly when the default backend is the CPU,
    decided when called (at trace time), with no environment override;
    the feature stores' "auto" policies follow the same rule."""
    from repro import kernels
    from repro.core.feature_store import PinnedHotStore, StagedStore

    monkeypatch.setenv("REPRO_KERNEL_INTERPRET", "0")   # consulted by nothing
    assert jax.default_backend() == "cpu"
    assert kernels.resolve_interpret() is True
    assert kernels.resolve_interpret(False) is False
    assert not StagedStore().hot_rows_from_cache
    table = jnp.arange(12.0).reshape(4, 3)
    ids = jnp.array([1, -1, 3], jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(PinnedHotStore()._gather_hits(table, ids)),
        np.asarray(gather_rows_reference(table, ids)))

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert kernels.resolve_interpret() is False
    assert kernels.resolve_interpret(True) is True
    assert StagedStore().hot_rows_from_cache


def test_kernels_match_oracles_under_tpu_dma_semantics():
    """The TPU interpreter runs each DMA as late as a wait allows and out
    of order: several copies signalling one semaphore only say that
    *some* of them landed.  Both kernels must still match their oracles
    (the fused sampler with window truncation and overflow)."""
    from jax.experimental.pallas import tpu as pltpu

    params = pltpu.InterpretParams(dma_execution_mode="on_wait")
    g = make_power_law_graph(400, 8, num_features=4, num_classes=3,
                             seed=2).graph
    seeds = jnp.arange(-1, 39, dtype=jnp.int32)
    s_k, r_k, ovf = fused_sample(g.indptr, g.indices, seeds, jnp.uint32(1),
                                 fanout=3, window=4, interpret=params)
    s_r, r_r, ovf_r = ref_windowed_fused_sample(g, seeds, 3, 1, 4)
    np.testing.assert_array_equal(np.asarray(s_k), np.asarray(s_r))
    np.testing.assert_array_equal(np.asarray(r_k), np.asarray(r_r))
    assert int(ovf) == ovf_r > 0

    table = jnp.asarray(np.random.default_rng(5).normal(size=(50, 8)),
                        jnp.float32)
    ids = jnp.arange(-2, 60, dtype=jnp.int32)       # -1s and out of range
    np.testing.assert_array_equal(
        np.asarray(gather_rows(table, ids, block=16, interpret=params)),
        np.asarray(gather_rows_reference(table, ids)))
