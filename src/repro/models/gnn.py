"""GNN models over MFGs: GraphSAGE (paper's §4 model) and GCN.

The paper trains a 3-layer GraphSAGE, hidden 256, dropout between layers,
FP32.  Layers consume MFGs bottom-up (layer 1 eats the bottom-most MFG).
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.core.mfg import MFG, mean_aggregate


@dataclasses.dataclass(frozen=True)
class GNNConfig:
    in_dim: int
    hidden_dim: int = 256
    num_classes: int = 47
    num_layers: int = 3
    fanouts: tuple[int, ...] = (15, 10, 5)   # (N_L, ..., N_1), top first
    dropout: float = 0.5
    conv: str = "sage"                        # sage | gcn | gat | gin
    gat_heads: int = 4                        # attention heads (gat only)


def init_gnn_params(key, cfg: GNNConfig):
    dims = ([cfg.in_dim] + [cfg.hidden_dim] * (cfg.num_layers - 1)
            + [cfg.num_classes])
    params = []
    for l in range(cfg.num_layers):
        key, k1, k2, k3 = jax.random.split(key, 4)
        d_in, d_out = dims[l], dims[l + 1]
        scale = (2.0 / d_in) ** 0.5
        layer = {
            "w_self": jax.random.normal(k1, (d_in, d_out), jnp.float32) * scale,
            "w_neigh": jax.random.normal(k2, (d_in, d_out), jnp.float32) * scale,
            "b": jnp.zeros((d_out,), jnp.float32),
        }
        if cfg.conv == "gat" and d_out % cfg.gat_heads == 0:
            # final layer (d_out = num_classes) falls back to mean-agg when
            # heads don't divide — the common single-head-output compromise
            H = cfg.gat_heads
            layer["attn_src"] = (jax.random.normal(k3, (H, d_out // H),
                                                   jnp.float32) * 0.1)
            layer["attn_dst"] = (jax.random.normal(
                jax.random.fold_in(k3, 1), (H, d_out // H),
                jnp.float32) * 0.1)
        if cfg.conv == "gin":
            layer["eps"] = jnp.zeros((), jnp.float32)
            layer["w_mlp"] = (jax.random.normal(k3, (d_out, d_out),
                                                jnp.float32)
                              * (2.0 / d_out) ** 0.5)
            layer["b_mlp"] = jnp.zeros((d_out,), jnp.float32)
        params.append(layer)
    return params


def _gat_aggregate(layer, mfg: MFG, z_src: jnp.ndarray, H: int):
    """Masked GAT attention over sampled edges.

    z_src: (src_capacity, d_out) projected features; returns (num_dst, d_out).
    """
    S, F = mfg.edges.shape
    d_out = z_src.shape[1]
    dh = d_out // H
    zh = z_src.reshape(-1, H, dh)
    idx = jnp.clip(mfg.edges, 0)
    z_nb = zh[idx]                                    # (S, F, H, dh)
    z_dst = zh[:S]                                    # (S, H, dh)

    e_src = jnp.einsum("sfhd,hd->sfh", z_nb, layer["attn_src"])
    e_dst = jnp.einsum("shd,hd->sh", z_dst, layer["attn_dst"])
    e = jax.nn.leaky_relu(e_src + e_dst[:, None, :], 0.2)
    e = jnp.where(mfg.edge_mask[..., None], e, -1e30)
    a = jax.nn.softmax(e, axis=1)                     # over sampled nbrs
    a = jnp.where(mfg.edge_mask[..., None], a, 0.0)
    out = jnp.einsum("sfh,sfhd->shd", a, z_nb)
    return out.reshape(S, d_out)


def apply_layer(layer, mfg: MFG, h_src: jnp.ndarray, cfg: GNNConfig,
                *, is_last: bool, dropout_key=None) -> jnp.ndarray:
    """One SAGE/GCN layer: (src_capacity, D_in) -> (num_dst, D_out)."""
    h_dst = h_src[: mfg.num_dst]              # prefix convention
    if cfg.conv == "sage":
        agg = mean_aggregate(mfg, h_src)
        out = h_dst @ layer["w_self"] + agg @ layer["w_neigh"] + layer["b"]
    elif cfg.conv == "gcn":                    # aggregate incl. self
        agg = mean_aggregate(mfg, h_src)
        out = 0.5 * (h_dst + agg) @ layer["w_neigh"] + layer["b"]
    elif cfg.conv == "gat":
        z_src = h_src @ layer["w_neigh"]
        if "attn_src" in layer:
            out = _gat_aggregate(layer, mfg, z_src, cfg.gat_heads)
        else:                                  # head-indivisible fallback
            out = mean_aggregate(mfg, z_src)
        out = out + h_dst @ layer["w_self"] + layer["b"]
    elif cfg.conv == "gin":
        # sum aggregation: mean * count
        agg = mean_aggregate(mfg, h_src)
        count = jnp.sum(mfg.edge_mask, axis=1, keepdims=True)
        s = agg * count.astype(agg.dtype)
        pre = ((1.0 + layer["eps"]) * h_dst + s) @ layer["w_neigh"] \
            + layer["b"]
        out = jax.nn.relu(pre) @ layer["w_mlp"] + layer["b_mlp"]
    else:
        raise ValueError(cfg.conv)
    if not is_last:
        out = jax.nn.relu(out)
        if dropout_key is not None and cfg.dropout > 0:
            keep = jax.random.bernoulli(dropout_key, 1 - cfg.dropout,
                                        out.shape)
            out = out * keep / (1 - cfg.dropout)
    return out


def gnn_forward(params, mfgs: Sequence[MFG], h0: jnp.ndarray,
                cfg: GNNConfig, dropout_key=None) -> jnp.ndarray:
    """mfgs ordered top-level first (sampler order); h0 aligns with
    mfgs[-1].src_nodes.  Returns logits for the top-level seeds."""
    assert len(mfgs) == cfg.num_layers
    h = h0
    for l in range(cfg.num_layers):
        mfg = mfgs[cfg.num_layers - 1 - l]
        dk = None
        if dropout_key is not None:
            dk = jax.random.fold_in(dropout_key, l)
        with jax.named_scope(f"layer{l}"):
            h = apply_layer(params[l], mfg, h, cfg,
                            is_last=(l == cfg.num_layers - 1),
                            dropout_key=dk)
    return h


def gnn_loss(params, mfgs, h0, labels, valid, cfg: GNNConfig,
             dropout_key=None):
    """Masked cross-entropy over the labeled seeds (eq. 3)."""
    logits = gnn_forward(params, mfgs, h0, cfg, dropout_key)
    labels_ok = valid & (labels >= 0)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(
        logp, jnp.clip(labels, 0)[:, None], axis=1)[:, 0]
    nll = jnp.where(labels_ok, nll, 0.0)
    return jnp.sum(nll) / jnp.maximum(jnp.sum(labels_ok), 1)


def gnn_accuracy(params, mfgs, h0, labels, valid, cfg: GNNConfig):
    logits = gnn_forward(params, mfgs, h0, cfg)
    pred = jnp.argmax(logits, axis=-1)
    ok = valid & (labels >= 0)
    correct = jnp.where(ok, pred == labels, False)
    return jnp.sum(correct) / jnp.maximum(jnp.sum(ok), 1)
