"""The plain reference for the benchmark's training cells, in float32.

It imports nothing of the program under test.  From the same generated
graph, features and ``--seed`` it computes what a sampled GNN training
step has to give, by the semantics the configuration states:

* seeds: step ``k`` trains the ``batch`` labelled nodes with the lowest
  SplitMix64 rank of ``(node id + (base_salt + k) * golden)``;
* sampling: a node ``v`` at depth ``d`` of step ``k`` draws slot ``j`` of
  its fanout as ``hash32(v * 2654435761 + j, salt_k * 1000003 + d)`` modulo
  its visible in-degree, where ``salt_k = base_salt + k`` and the visible
  in-degree is ``min(deg, window)``: the first ``window`` in-neighbours of
  a hub are the ones it draws from.  A node whose visible degree is at
  most the fanout takes each visible neighbour once.  The hubs met in a
  step's frontiers are counted;
* model: the weights and the loss of the configuration's model module
  (``bench/models/<model>.py``: ``init_params`` and ``reference_loss``,
  which puts every matrix product through ``matmul`` below);
* update: gradients clipped to a global norm, then AdamW.

The frontier of each depth is a set of node ids (sorted here), so the
reference shares no layout, relabelling or padding with the program.
Matrix products run at ``Precision.HIGHEST``.  ``passes=3`` gives the
control: every matrix product computed as TPUs compute ``Precision.HIGH``,
from bfloat16 halves of each operand (``hi*hi + hi*lo + lo*hi``), the
nearest precision below the float32 at ``highest`` the configurations
state.  The three passes are spelled out, so the control reads the same
on any backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from flops import block_sizes
from graphgen import mix64

GOLDEN64 = 0x9E3779B97F4A7C15


def hash32(x: np.ndarray, salt: int) -> np.ndarray:
    """SplitMix32-style hash of uint32 ``x`` under ``salt`` (wraps)."""
    x = x.astype(np.uint32) + np.uint32((int(salt) * 0x9E3779B9) & 0xFFFFFFFF)
    x = (x ^ (x >> np.uint32(16))) * np.uint32(0x85EBCA6B)
    x = (x ^ (x >> np.uint32(13))) * np.uint32(0xC2B2AE35)
    return x ^ (x >> np.uint32(16))


def step_salt(base_salt: int, k: int) -> int:
    return (int(base_salt) + int(k)) % 2**32


def draw_seeds(labeled: np.ndarray, batch: int, salt: int) -> np.ndarray:
    """The ``batch`` labelled node ids of lowest hash rank under ``salt``,
    in rank order (ties by id)."""
    salt64 = np.uint64((int(salt) * GOLDEN64) % 2**64)
    ids = np.flatnonzero(labeled)
    key = mix64(ids.astype(np.uint64) + salt64)
    order = np.argsort(key, kind="stable")[:batch]
    return ids[order].astype(np.int64)


def sample_level(indptr, indices, nodes, fanout: int, salt: int,
                 window: int | None):
    """(samples (S, F) int64 global ids, -1 where no draw; hub count)."""
    start = indptr[nodes]
    deg = indptr[nodes + 1] - start
    vis = deg if window is None else np.minimum(deg, window)
    slots = np.arange(fanout, dtype=np.uint32)
    base = (nodes.astype(np.uint64) * np.uint64(2654435761)) & np.uint64(
        0xFFFFFFFF)
    bits = hash32(base.astype(np.uint32)[:, None] + slots[None, :], salt)
    col = (bits % np.maximum(vis, 1).astype(np.uint32)[:, None]).astype(
        np.int64)
    col = np.where((vis <= fanout)[:, None], slots[None, :].astype(np.int64),
                   col)
    valid = slots[None, :] < np.minimum(vis, fanout)[:, None]
    pos = np.where(valid, start[:, None] + col, 0)
    samples = np.where(valid, indices[pos], -1).astype(np.int64)
    hubs = 0 if window is None else int((deg > window).sum())
    return samples, hubs


def sample_blocks(indptr, indices, seeds, fanouts, salt: int,
                  window: int | None, alter_bottom: bool = False):
    """Frontiers and draws of one step, top level first.

    Returns ``(frontiers, draws, hubs)``: ``frontiers[0]`` is ``seeds``
    in seed order, ``frontiers[d+1]`` the sorted set of ``frontiers[d]``
    and its draws; ``draws[d]`` the (|frontiers[d]|, F_d) draws.
    ``alter_bottom`` shifts every bottom-level draw to the next node id:
    a planted fault, for reading the comparison's limits."""
    n = indptr.shape[0] - 1
    frontiers, draws, hubs = [np.asarray(seeds, np.int64)], [], 0
    for d, fanout in enumerate(fanouts):
        level = (int(salt) * 1000003 + d) % 2**32
        s, h = sample_level(indptr, indices, frontiers[-1], int(fanout),
                            level, window)
        if alter_bottom and d == len(fanouts) - 1:
            s = np.where(s >= 0, (s + 1) % n, -1)
        hubs += h
        draws.append(s)
        frontiers.append(np.union1d(frontiers[-1], s[s >= 0]))
    return frontiers, draws, hubs


def block_indices(frontiers, draws, caps):
    """Per depth, (self (cap_d,), neigh (cap_d, F), mask (cap_d, F)) as
    positions into the next frontier, padded to the nominal capacity so
    one compiled program serves every step."""
    out = []
    for d, s in enumerate(draws):
        nxt = frontiers[d + 1]
        rows = frontiers[d].shape[0]
        self_pos = np.zeros(caps[d], np.int32)
        self_pos[:rows] = np.searchsorted(nxt, frontiers[d])
        nb = np.zeros((caps[d], s.shape[1]), np.int32)
        nb[:rows] = np.where(s >= 0, np.searchsorted(nxt, np.maximum(s, 0)),
                             0)
        mask = np.zeros((caps[d], s.shape[1]), bool)
        mask[:rows] = s >= 0
        out.append((self_pos, nb, mask))
    return out


def _halves(x):
    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, (x - hi).astype(jnp.bfloat16).astype(jnp.float32)


def matmul(a, b, passes: int):
    """``a @ b`` in float32: exact products (``passes=6``, HIGHEST) or
    three bfloat16 passes (``passes=3``)."""
    hp = jax.lax.Precision.HIGHEST
    if passes == 6:
        return jnp.matmul(a, b, precision=hp)
    (ah, al), (bh, bl) = _halves(a), _halves(b)
    return (jnp.matmul(ah, bh, precision=hp) + jnp.matmul(ah, bl, precision=hp)
            + jnp.matmul(al, bh, precision=hp))


@functools.partial(jax.jit, static_argnames=("loss", "passes"))
def _loss_and_grad(params, h0, blocks, labels, valid, *, loss, passes):
    return jax.value_and_grad(loss)(params, h0, blocks, labels, valid,
                                    passes)


def clip_global(grads, max_norm: float):
    norm = np.sqrt(sum(float(np.sum(np.square(g, dtype=np.float64)))
                       for g in jax.tree.leaves(grads)))
    scale = min(1.0, max_norm / (norm + 1e-9))
    return jax.tree.map(lambda g: g * np.float32(scale), grads)


def adamw(params, grads, state, opt: dict):
    """One AdamW step in float32 on host arrays; ``state`` is
    ``(t, m, v)``."""
    t, m, v = state
    t += 1
    b1, b2 = float(opt["b1"]), float(opt["b2"])
    m = jax.tree.map(lambda m_, g: m_ * np.float32(b1)
                     + np.float32(1 - b1) * g, m, grads)
    v = jax.tree.map(lambda v_, g: v_ * np.float32(b2)
                     + np.float32(1 - b2) * g * g, v, grads)

    def upd(p, m_, v_):
        mhat = m_ / np.float32(1 - b1 ** t)
        vhat = v_ / np.float32(1 - b2 ** t)
        delta = mhat / (np.sqrt(vhat) + np.float32(opt["eps"])) \
            + np.float32(opt["weight_decay"]) * p
        return p - np.float32(opt["lr"]) * delta

    return jax.tree.map(upd, params, m, v), (t, m, v)


def train_steps(graph, feats, labels, *, seed: int, base_salt: int,
                batch: int, fanouts, window, init_params, reference_loss,
                opt: dict, steps: int = 3, passes: int = 6,
                half_batch: bool = False, alter_bottom: bool = False):
    """Run ``steps`` reference training steps from ``seed``'s weights.

    ``init_params(seed)`` gives the float32 weights and
    ``reference_loss(params, h0, blocks, labels, valid, passes)`` the
    model's loss (the configuration's model module).

    ``graph`` is ``(indptr, indices)`` on the host, ``feats`` the (n, D)
    float32 host features and ``labels`` the (n,) int32 labels, -1 where
    unlabelled.  ``passes=3`` computes the control (see ``matmul``);
    ``half_batch`` (leave the second half of each batch out
    of the mean) and ``alter_bottom`` (see ``sample_blocks``) plant the
    faults the comparison must catch.

    Returns ``{"losses", "grad1", "params0", "params", "hubs"}``:
    ``grad1`` is the first step's gradient after clipping, as the
    optimizer receives it; ``params`` the weights after ``steps`` steps.
    """
    indptr, indices = graph
    caps = block_sizes(batch, fanouts)
    params0 = jax.tree.map(np.asarray, init_params(seed))
    params = params0
    zeros = jax.tree.map(np.zeros_like, params)
    state = (0, zeros, zeros)
    losses, hubs, grad1 = [], [], None
    for k in range(steps):
        salt = step_salt(base_salt, k)
        seeds = draw_seeds(labels >= 0, batch, salt)
        frontiers, draws, h = sample_blocks(indptr, indices, seeds, fanouts,
                                            salt, window, alter_bottom)
        hubs.append(h)
        blocks = block_indices(frontiers, draws, caps)
        bottom = frontiers[-1]
        h0 = np.zeros((caps[-1], feats.shape[1]), np.float32)
        h0[:bottom.shape[0]] = feats[bottom]
        valid = np.ones(batch, bool)
        if half_batch:
            valid[batch // 2:] = False
        loss, grads = _loss_and_grad(
            params, jnp.asarray(h0), blocks,
            jnp.asarray(labels[seeds].astype(np.int32)),
            jnp.asarray(valid), loss=reference_loss, passes=passes)
        grads = jax.tree.map(lambda g: np.asarray(g, np.float32), grads)
        grads = clip_global(grads, float(opt["grad_clip"]))
        if k == 0:
            grad1 = grads
        params, state = adamw(params, grads, state, opt)
        losses.append(float(loss))
    return {"losses": losses, "grad1": grad1, "params0": params0,
            "params": params, "hubs": hubs}
