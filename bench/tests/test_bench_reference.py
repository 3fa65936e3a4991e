"""The benchmark's plain reference against the program on a toy graph on
the CPU, layer by layer: the seed draw, the fused sampler's blocks
(hubs above its neighbour window included), the feature rows fetched for
the bottom frontier, and the loss with its gradients."""
import functools
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import flops
import graphgen
import reference
import run

TOY = BENCH / "tests" / "data" / "toy"
MODELS = BENCH / "models"
BATCH = 16


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    """The toy cell's graph, features and labels, and the program's
    pipeline over them (vmap executor, so one prepared batch can be
    read back)."""
    from repro.core.graph import CSCGraph
    from repro.pipeline import Pipeline, PipelineSpec

    cell = run.load_cell("toy.train", TOY, models_dir=MODELS)
    config = cell.config
    fields = graphgen.graph_fields(config)
    arrays, _ = graphgen.load_or_build(fields,
                                       tmp_path_factory.mktemp("graphs"))
    feats = graphgen.features(fields, arrays["classes"], config["in_dim"])
    labels = np.where(arrays["labeled"], arrays["classes"],
                      -1).astype(np.int32)
    spec = PipelineSpec.from_scheme(
        config["scheme"], num_parts=1, fanouts=tuple(config["fanouts"]),
        partitioner=config["partitioner"], executor="vmap",
        feature_store=config["feature_store"], prefetch_depth=1)
    pipe = Pipeline.build(
        CSCGraph(indptr=arrays["indptr"].astype(np.int32),
                 indices=arrays["indices"]), feats, labels, spec)
    return cell, arrays, feats, labels, pipe


def _prepared(toy, salt):
    """The program's prepared batch for ``salt`` (seeds drawn by its own
    seed stream), through the executor's prepare program."""
    from repro.pipeline.executor import resolve_executor
    from repro.pipeline.prefetch import make_update_fn

    pipe = toy[-1]
    prepare, consume = pipe.make_prepare_consume(lambda *args: 0.0)
    runner = resolve_executor("vmap").bind_prefetch(
        pipe, prepare, prepare, consume, make_update_fn())
    seeds = jnp.asarray(pipe.seeds_host(BATCH, epoch_salt=salt))
    batch = runner.prepare(seeds, jnp.uint32(salt))
    return np.asarray(seeds[0]), jax.tree.map(lambda x: np.asarray(x[0]),
                                              batch)


@pytest.mark.parametrize("salt", [0, 12345, 2**32 - 3])
def test_seed_draw_matches(toy, salt):
    _, arrays, _, labels, pipe = toy
    got = pipe.seeds_host(BATCH, epoch_salt=salt)[0]
    want = reference.draw_seeds(labels >= 0, BATCH, salt)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("salt", [7, 2**31 + 5])
def test_sampled_blocks_match_with_hubs(toy, salt):
    cell, arrays, _, labels, _ = toy
    seeds, batch = _prepared(toy, salt)
    frontiers, draws, hubs = reference.sample_blocks(
        arrays["indptr"], arrays["indices"], seeds,
        cell.config["fanouts"], salt, cell.config["sampler_window"])
    deg = np.diff(arrays["indptr"])
    assert hubs > 0, "the toy graph must reach hubs above the window"
    assert hubs == int(batch.comm["sampler_window_overflow"])
    for d, mfg in enumerate(batch.mfgs):
        src = mfg.src_nodes
        np.testing.assert_array_equal(
            np.sort(src[src >= 0]), frontiers[d + 1])
        dst = mfg.dst_nodes
        live = dst >= 0
        got = np.where(mfg.edge_mask, src[np.maximum(mfg.edges, 0)], -1)
        order = np.argsort(frontiers[d])
        row = order[np.searchsorted(frontiers[d][order], dst[live])]
        np.testing.assert_array_equal(got[live], draws[d][row])
        # a hub draws only from its first `window` in-neighbours
        big = live & (deg[np.maximum(dst, 0)] > 2048)
        for v, picks in zip(dst[big], got[big]):
            first = arrays["indices"][arrays["indptr"][v]:
                                      arrays["indptr"][v] + 2048]
            assert np.isin(picks[picks >= 0], first).all()


def test_fetched_feature_rows_match(toy):
    _, _, feats, _, _ = toy
    _, batch = _prepared(toy, 99)
    src = batch.mfgs[-1].src_nodes
    want = np.where((src >= 0)[:, None], feats[np.maximum(src, 0)], 0)
    np.testing.assert_array_equal(batch.h_src, want)


def test_loss_and_grads_match(toy):
    cell, arrays, feats, labels, _ = toy
    c = cell.config
    seeds, batch = _prepared(toy, 3)
    params = cell.model.init_params(11, c)
    mfgs = jax.tree.map(jnp.asarray, list(batch.mfgs))
    got_loss, got_grads = jax.value_and_grad(cell.model.program_loss(c))(
        params, mfgs, jnp.asarray(batch.h_src),
        jnp.asarray(batch.seed_labels), jnp.asarray(batch.seed_valid))
    frontiers, draws, _ = reference.sample_blocks(
        arrays["indptr"], arrays["indices"], seeds, c["fanouts"], 3,
        c["sampler_window"])
    caps = flops.block_sizes(BATCH, c["fanouts"])
    blocks = reference.block_indices(frontiers, draws, caps)
    h0 = np.zeros((caps[-1], feats.shape[1]), np.float32)
    h0[:frontiers[-1].size] = feats[frontiers[-1]]
    want_loss, want_grads = reference._loss_and_grad(
        params, jnp.asarray(h0), blocks, jnp.asarray(labels[seeds]),
        jnp.ones(BATCH, bool), loss=cell.model.reference_loss, passes=6)
    np.testing.assert_allclose(float(got_loss), float(want_loss),
                               rtol=1e-5)
    for g, w in zip(jax.tree.leaves(got_grads),
                    jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-6)


def test_three_pass_control_fails_at_toy_size(toy):
    """The control, the reference with three-pass bfloat16 matrix products
    put in the program's place, is judged not correct against the toy
    cell's limits, which the program meets (``test_bench_faults``)."""
    import calibrate

    cell, arrays, feats, labels, _ = toy
    kw = dict(seed=5, base_salt=run.base_salt(5), batch=BATCH,
              fanouts=tuple(cell.config["fanouts"]),
              window=cell.config["sampler_window"],
              init_params=lambda s: cell.model.init_params(s, cell.config),
              reference_loss=cell.model.reference_loss,
              opt=run.optimizer_of(cell.config))
    graph = (arrays["indptr"], arrays["indices"])
    want = reference.train_steps(graph, feats, labels, **kw)
    control = reference.train_steps(graph, feats, labels, passes=3, **kw)
    b1 = cell.config["adam_b1"]
    same = run.compare.numbers(calibrate.as_program(want, b1), want, b1)
    assert all(v < 1e-6 for v in same.values())
    values = run.compare.numbers(calibrate.as_program(control, b1), want, b1)
    assert not run.compare.judge(values, cell.limits)[0]


def test_every_limit_lies_between_its_readings():
    """Each cell's limits sit above the largest reading of sound runs and
    below the smallest reading of the control or a planted fault."""
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    cells = [run.load_cell(w["name"]) for w in bench["workloads"]]
    for cell in cells + [run.load_cell("toy.train", TOY, models_dir=MODELS)]:
        for name, r in cell.limits["readings"].items():
            limit = cell.limits[name]
            if r["upper"] is None:
                assert limit == r["lower"] == 0, (cell.name, name)
            else:
                assert r["lower"] < limit < r["upper"], (cell.name, name)
                assert r["upper"] >= 3 * r["lower"], (cell.name, name)


# The parent's GraphSAGE reference, kept as the oracle of the model module:
# its weights, its loss and its training loop, as they were before the
# model was found by the configuration's ``model`` key.

@functools.partial(jax.jit, static_argnums=(1,))
def _parent_init(key, dims):
    params = []
    for i, (d_in, d_out) in enumerate(zip(dims[:-1], dims[1:])):
        k1, k2 = jax.random.split(jax.random.fold_in(key, i))
        scale = (2.0 / d_in) ** 0.5
        params.append({
            "w_self": jax.random.normal(k1, (d_in, d_out), jnp.float32)
            * scale,
            "w_neigh": jax.random.normal(k2, (d_in, d_out), jnp.float32)
            * scale,
            "b": jnp.zeros((d_out,), jnp.float32)})
    return params


def _parent_sage_loss(params, h0, blocks, labels, valid, passes):
    h = h0
    depth = len(blocks)
    for layer in range(depth):
        self_pos, nb, mask = blocks[depth - 1 - layer]
        p = params[layer]
        m = mask[..., None].astype(jnp.float32)
        agg = jnp.sum(h[nb] * m, axis=1) / jnp.maximum(jnp.sum(m, axis=1), 1)
        h = (reference.matmul(h[self_pos], p["w_self"], passes)
             + reference.matmul(agg, p["w_neigh"], passes) + p["b"])
        if layer < depth - 1:
            h = jax.nn.relu(h)
    logp = jax.nn.log_softmax(h, axis=-1)
    nll = -jnp.take_along_axis(logp, jnp.maximum(labels, 0)[:, None],
                               axis=1)[:, 0]
    ok = valid & (labels >= 0)
    return jnp.sum(jnp.where(ok, nll, 0)) / jnp.maximum(jnp.sum(ok), 1)


@functools.partial(jax.jit, static_argnames=("passes",))
def _parent_loss_and_grad(params, h0, blocks, labels, valid, passes):
    return jax.value_and_grad(_parent_sage_loss)(params, h0, blocks, labels,
                                                 valid, passes)


def _parent_train_steps(graph, feats, labels, *, seed, base_salt, batch,
                        fanouts, window, dims, opt, steps=3, passes=6):
    indptr, indices = graph
    caps = flops.block_sizes(batch, fanouts)
    params0 = jax.tree.map(np.asarray, _parent_init(
        jax.random.key(int(seed)), tuple(int(d) for d in dims)))
    params = params0
    zeros = jax.tree.map(np.zeros_like, params)
    state = (0, zeros, zeros)
    losses, hubs, grad1 = [], [], None
    for k in range(steps):
        salt = reference.step_salt(base_salt, k)
        seeds = reference.draw_seeds(labels >= 0, batch, salt)
        frontiers, draws, h = reference.sample_blocks(
            indptr, indices, seeds, fanouts, salt, window)
        hubs.append(h)
        blocks = reference.block_indices(frontiers, draws, caps)
        bottom = frontiers[-1]
        h0 = np.zeros((caps[-1], feats.shape[1]), np.float32)
        h0[:bottom.shape[0]] = feats[bottom]
        loss, grads = _parent_loss_and_grad(
            params, jnp.asarray(h0), blocks,
            jnp.asarray(labels[seeds].astype(np.int32)),
            jnp.asarray(np.ones(batch, bool)), passes=passes)
        grads = jax.tree.map(lambda g: np.asarray(g, np.float32), grads)
        grads = reference.clip_global(grads, float(opt["grad_clip"]))
        if k == 0:
            grad1 = grads
        params, state = reference.adamw(params, grads, state, opt)
        losses.append(float(loss))
    return {"losses": losses, "grad1": grad1, "params0": params0,
            "params": params, "hubs": hubs}


@pytest.mark.parametrize("passes", [6, 3], ids=["reference", "control"])
def test_module_reference_is_the_parents_bit_for_bit(toy, passes):
    """``reference.train_steps`` through the ``graphsage`` module gives the
    toy cell's losses, first gradient and parameters bit for bit as the
    parent's GraphSAGE functions did, reference and control alike."""
    cell, arrays, feats, labels, _ = toy
    c = cell.config
    graph = (arrays["indptr"], arrays["indices"])
    kw = dict(seed=2**31 + 7, base_salt=run.base_salt(2**31 + 7),
              batch=int(cell.traffic["batch"]), fanouts=tuple(c["fanouts"]),
              window=c["sampler_window"], opt=run.optimizer_of(c),
              passes=passes)
    got = run.reference_of(cell, {"graph": graph, "feats": feats,
                                  "labels": labels}, kw["seed"],
                           passes=passes)
    dims = ([c["in_dim"]] + [c["hidden_dim"]] * (c["num_layers"] - 1)
            + [c["num_classes"]])
    want = _parent_train_steps(graph, feats, labels, dims=dims, **kw)
    assert got["losses"] == want["losses"]
    assert got["hubs"] == want["hubs"]
    for key in ("params0", "grad1", "params"):
        for g, w in zip(jax.tree.leaves(got[key]),
                        jax.tree.leaves(want[key]), strict=True):
            np.testing.assert_array_equal(g, w)
