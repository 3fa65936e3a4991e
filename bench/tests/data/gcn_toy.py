"""A second model for the harness's tests, found by a configuration's
``model`` key: GCN as the program's ``conv="gcn"`` computes it, half the
sum of a node's own row and its neighbours' mean, times ``w_neigh``, plus
``b``; ReLU between layers; masked cross-entropy over the labelled seeds.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from flops import block_sizes
from reference import matmul


def _dims(config: dict) -> tuple[int, ...]:
    return ((int(config["in_dim"]),)
            + (int(config["hidden_dim"]),) * (int(config["num_layers"]) - 1)
            + (int(config["num_classes"]),))


def init_params(seed: int, config: dict) -> list[dict]:
    return _init(jax.random.key(int(seed)), _dims(config))


@functools.partial(jax.jit, static_argnums=(1,))
def _init(key, dims):
    return [{"w_neigh": jax.random.normal(jax.random.fold_in(key, i),
                                          (d_in, d_out), jnp.float32)
             * (2.0 / d_in) ** 0.5,
             "b": jnp.zeros((d_out,), jnp.float32)}
            for i, (d_in, d_out) in enumerate(zip(dims[:-1], dims[1:]))]


def program_loss(config: dict):
    from repro.models import gnn

    dims = _dims(config)
    gcfg = gnn.GNNConfig(in_dim=dims[0], hidden_dim=dims[1],
                         num_classes=dims[-1], num_layers=len(dims) - 1,
                         fanouts=tuple(config["fanouts"]),
                         dropout=float(config["dropout"]), conv="gcn")

    def loss_fn(p, mfgs, h_src, labels, valid):
        return gnn.gnn_loss(p, mfgs, h_src, labels, valid, gcfg)

    return loss_fn


def reference_loss(params, h0, blocks, labels, valid, passes):
    h = h0
    for layer, (self_pos, nb, mask) in enumerate(reversed(blocks)):
        m = mask[..., None].astype(jnp.float32)
        agg = jnp.sum(h[nb] * m, axis=1) / jnp.maximum(jnp.sum(m, axis=1), 1)
        h = matmul(0.5 * (h[self_pos] + agg), params[layer]["w_neigh"],
                   passes) + params[layer]["b"]
        h = jax.nn.relu(h) if layer < len(blocks) - 1 else h
    logp = jax.nn.log_softmax(h, axis=-1)
    nll = -jnp.take_along_axis(logp, jnp.maximum(labels, 0)[:, None],
                               axis=1)[:, 0]
    ok = valid & (labels >= 0)
    return jnp.sum(jnp.where(ok, nll, 0)) / jnp.maximum(jnp.sum(ok), 1)


def step_flops(batch: int, fanouts, config: dict) -> int:
    """Per layer, the neighbour sum, the add and halving of the self row,
    and one (N_dst x D_in) @ (D_in x D_out) product; backward twice the
    forward."""
    dims, sizes = _dims(config), block_sizes(batch, fanouts)
    depth = len(fanouts)
    fwd = 0
    for layer in range(depth):
        n_dst, f = sizes[depth - 1 - layer], int(fanouts[depth - 1 - layer])
        d_in, d_out = dims[layer], dims[layer + 1]
        fwd += 2 * n_dst * d_in * d_out + n_dst * (f + 2) * d_in
    return 3 * fwd
