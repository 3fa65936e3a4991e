"""GraphSAGE, the paper's model: 3 mean-aggregator layers,
``h W_self + mean(h_nb) W_neigh + b``, ReLU between layers, masked
cross-entropy over the labelled seeds.

The model module of configurations whose ``model`` is ``graphsage``: the
weights both sides start from, the program's loss (``repro.models.gnn``
with ``conv="sage"``), the plain reference's loss, and the FLOPs of one
training step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from flops import block_sizes
from reference import matmul


def dims_of(config: dict) -> list[int]:
    layers = int(config["num_layers"])
    return ([int(config["in_dim"])] + [int(config["hidden_dim"])]
            * (layers - 1) + [int(config["num_classes"])])


def init_params(seed: int, config: dict) -> list[dict]:
    """Weights from ``seed`` in one jitted call on the default device:
    He-normal ``w_self``/``w_neigh``, zero bias."""
    return _init(jax.random.key(int(seed)), tuple(dims_of(config)))


@functools.partial(jax.jit, static_argnums=(1,))
def _init(key, dims):
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


def program_loss(config: dict):
    """The program's loss, as ``launch/train_gnn.py`` builds it.
    ``gnn.gnn_loss`` is looked up at each call."""
    from repro.models import gnn

    dims = dims_of(config)
    gcfg = gnn.GNNConfig(in_dim=dims[0], hidden_dim=dims[1],
                         num_classes=dims[-1], num_layers=len(dims) - 1,
                         fanouts=tuple(config["fanouts"]),
                         dropout=float(config["dropout"]), conv="sage")

    def loss_fn(p, mfgs, h_src, labels, valid):
        return gnn.gnn_loss(p, mfgs, h_src, labels, valid, gcfg)

    return loss_fn


def reference_loss(params, h0, blocks, labels, valid, passes):
    """Masked mean cross-entropy of GraphSAGE over ``blocks`` (top level
    first); ``h0`` is aligned with the bottom frontier."""
    h = h0
    depth = len(blocks)
    for layer in range(depth):
        self_pos, nb, mask = blocks[depth - 1 - layer]
        p = params[layer]
        m = mask[..., None].astype(jnp.float32)
        agg = jnp.sum(h[nb] * m, axis=1) / jnp.maximum(jnp.sum(m, axis=1), 1)
        h = (matmul(h[self_pos], p["w_self"], passes)
             + matmul(agg, p["w_neigh"], passes) + p["b"])
        if layer < depth - 1:
            h = jax.nn.relu(h)
    logp = jax.nn.log_softmax(h, axis=-1)
    nll = -jnp.take_along_axis(logp, jnp.maximum(labels, 0)[:, None],
                               axis=1)[:, 0]
    ok = valid & (labels >= 0)
    return jnp.sum(jnp.where(ok, nll, 0)) / jnp.maximum(jnp.sum(ok), 1)


def forward_flops(batch: int, fanouts, config: dict) -> int:
    """Forward FLOPs: per layer, the two (N_dst x D_in) @ (D_in x D_out)
    products (self and neighbour, 2 FLOPs a multiply-add) and the
    neighbour sum (one add per drawn row entry).  Layer ``l`` (bottom
    first) consumes the MFG at depth ``L - 1 - l``."""
    dims = dims_of(config)
    sizes = block_sizes(batch, fanouts)
    depth = len(fanouts)
    total = 0
    for layer in range(depth):
        d = depth - 1 - layer
        n_dst, f = sizes[d], int(fanouts[d])
        d_in, d_out = dims[layer], dims[layer + 1]
        total += 2 * 2 * n_dst * d_in * d_out + n_dst * f * d_in
    return total


def step_flops(batch: int, fanouts, config: dict) -> int:
    """Forward plus backward (counted as twice the forward) FLOPs of one
    step; recomputation is not counted."""
    return 3 * forward_flops(batch, fanouts, config)
