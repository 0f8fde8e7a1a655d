"""Plain reference of the pod deployment's federated step over several
pods, one per chip, with the gossip's wire replayed.

Every step, each pod runs its momentum-SGD step on its own batch, as
``refs/fedstep_ref.py`` does for one pod (v <- beta v + g, p <- p - lr v,
lr = 1 / (R (step + 1)^0.499)). Then the pods mix over the ring: pod i
takes 1/3 of its own parameters and 1/3 of what pods i + 1 and i - 1 send
(offsets 1 and n - 1 in turn), every leaf apart. Below 32 bits each sent
leaf goes over the wire as Eq. 12's b-bit stochastic rounding of
|w| / ||w|| onto the grid s = max|w| / (||w|| levels), levels = 2^(b-1) - 1,
and back: w' = sign(w) round(|w| / (||w|| s)) s ||w||, rounding up with
probability equal to the remainder. The uniforms come from the step's key
folded with the leaf's index, the offset's index and the sending pod's
index, in that order, drawn over the leaf's shape with a leading pod axis
of 1, as the program draws them inside its per-pod map. Nothing is
imported from the program.

The pods are one stacked tree with a leading pod axis, pod i's slice on
``devices[i]`` (four float32 replicas with their momentum do not fit one
chip), so each step is one program for all pods, and so is each leaf's
mixing. The half-batch fault repeats the kept rows to the batch's shape,
which leaves the mean, and the step's program, as they were.

Readings: each step's per-pod losses, the per-(pod, leaf) norms of the
first gradient and of the change after the last step.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from chipbench.refs import models

__all__ = ["ring", "qdq", "mix_leaf", "mix", "programs", "run"]


def ring(n: int) -> list:
    """(offset, weight) pairs of the ring: self, then offsets 1 and n - 1."""
    offsets = [] if n == 1 else [1] if n == 2 else [1, n - 1]
    return [(0, 1.0 / (len(offsets) + 1))] + [(o, 1.0 / (len(offsets) + 1)) for o in offsets]


def qdq(w, key, bits: int):
    """One pod's leaf sent over the b-bit wire and read back (Eq. 12)."""
    levels = (1 << (bits - 1)) - 1
    wf = w.astype(jnp.float32)
    norm = jnp.sqrt(jnp.sum(wf * wf))
    safe = jnp.where(norm > 0, norm, 1.0)
    xmax = jnp.max(jnp.abs(wf)) / safe
    s = jnp.where(xmax > 0, xmax / levels, 1.0)
    x = jnp.abs(wf) / safe
    ell = jnp.floor(x / s)
    u = jax.random.uniform(key, (1, *w.shape), jnp.float32).reshape(w.shape)
    idx = jnp.clip(ell + (u < x / s - ell).astype(jnp.float32), 0, levels)
    return (idx * jnp.sign(wf) * s * norm).astype(w.dtype)


def mix_leaf(leaf, key, li, bits: int):
    """One leaf of one gossip round over the ring; ``leaf`` is (n, ...),
    pod i's at index i, and ``li`` the leaf's index in the tree: pod i
    takes what pod (i + offset) mod n sends."""
    n = leaf.shape[0]
    pairs = ring(n)
    acc = pairs[0][1] * leaf
    for oi, (off, w) in enumerate(pairs[1:]):
        sent = leaf
        if bits < 32:
            keys = jnp.stack([jax.random.fold_in(jax.random.fold_in(
                jax.random.fold_in(key, li), oi), j) for j in range(n)])
            sent = jax.vmap(qdq, in_axes=(0, 0, None))(leaf, keys, bits)
        acc = acc + w * jnp.roll(sent, -off, axis=0)
    return acc


def mix(pods, key, bits: int, sharding=None):
    """One gossip round over the ring of a tree of (n, ...) arrays, one
    program a leaf shape; the leaves of ``pods`` are consumed."""
    one = jax.jit(mix_leaf, static_argnums=3, donate_argnums=0, out_shardings=sharding)
    leaves, treedef = jax.tree_util.tree_flatten(pods)
    out = []
    for li in range(len(leaves)):
        out.append(one(leaves[li], key, li, bits))
        leaves[li] = None
    return jax.tree_util.tree_unflatten(treedef, out)


def _leaf_norms(tree):
    """(pods, leaves) norms of a stacked tree."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)),
                                       axis=tuple(range(1, a.ndim))))
                      for a in jax.tree_util.tree_leaves(tree)], axis=1)


def programs(cfg: dict, devices: list):
    """The jitted momentum-SGD step of every pod at once, and the sharding
    of a stacked tree: pod i's slice on ``devices[i]``."""
    beta = cfg["beta"]
    lossf = models.loss(cfg)
    pods = NamedSharding(Mesh(np.array(devices), ("pod",)), PartitionSpec("pod"))

    def one(p, v, batch, lr):
        loss, g = jax.value_and_grad(lossf)(p, batch)
        v = jax.tree_util.tree_map(lambda vv, gg: beta * vv + gg, v, g)
        p = jax.tree_util.tree_map(lambda pp, vv: pp - lr * vv, p, v)
        return p, v, loss

    step = jax.jit(jax.vmap(one, in_axes=(0, 0, 0, None)), donate_argnums=(0, 1),
                   out_shardings=pods)
    return step, pods


def run(cfg: dict, traffic: dict, init_fn, batches: list, key, devices: list, *,
        dtype=jnp.float32, precision: str = "highest", batch_frac: float = 1.0) -> dict:
    """``init_fn()`` gives the pods' common initial float32 weights; ``key``
    is the root the step keys split from (``key, step_key = split(key)``
    each step); ``batches[s]`` leaves are (pods, batch, seq)."""
    n, bits, lr_r = traffic["pods"], traffic["bits"], cfg["lr_r"]
    step, pods = programs(cfg, devices[:n])
    stack = jax.jit(lambda t: jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a.astype(dtype), (n, *a.shape)), t), out_shardings=pods)
    with jax.default_matmul_precision(precision):
        p = stack(init_fn())
        v = jax.jit(lambda t: jax.tree_util.tree_map(jnp.zeros_like, t),
                    out_shardings=pods)(p)
        losses, first = [], None
        for s, batch in enumerate(batches):
            lr = jnp.asarray(1.0 / (lr_r * float(s + 1) ** 0.499), dtype)
            rows = batch["tokens"].shape[1]
            keep = max(1, int(rows * batch_frac))
            if rows % keep:
                raise ValueError(f"batch_frac {batch_frac} leaves {keep} of {rows} rows")
            # the kept rows repeated to the batch's shape: the same mean, and
            # the program the whole batch compiled
            b = jax.device_put({k: a[:, np.arange(rows) % keep] for k, a in batch.items()},
                               pods)
            p, v, loss = step(p, v, b, lr)
            losses.append([float(x) for x in np.asarray(loss)])
            if s == 0:
                first = np.asarray(jax.jit(_leaf_norms)(v))
            key, step_key = jax.random.split(key)
            if n > 1:
                p = mix(p, step_key, bits, pods)
        del v
        third = np.asarray(jax.jit(lambda p, p0: _leaf_norms(jax.tree_util.tree_map(
            lambda a, b: a.astype(jnp.float32) - b, p, p0)))(p, init_fn()))
    return {"losses": losses, "first": first, "third": third}
