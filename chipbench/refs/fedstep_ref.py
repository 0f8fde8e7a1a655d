"""Plain reference of the pod deployment's federated step, for one pod.

The pod runs a momentum-SGD step on its batch: v <- beta v + g,
p <- p - lr v with lr = 1 / (R * (step + 1)^0.499) (the paper's decreasing
schedule), g the gradient of the decoder's mean next-token cross entropy
(``refs/models.py``). With one pod the gossip is the identity; the mixing
of several pods is not replayed here.

Readings: each step's loss, the per-leaf norm of the first gradient (the
momentum after one step), and the per-leaf norm of the parameters' change
after the last step, each with a leading pod axis of 1.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.refs import models

__all__ = ["run"]


def _leaf_norms(tree) -> np.ndarray:
    return np.array([float(jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))))
                     for a in jax.tree_util.tree_leaves(tree)])


def run(cfg: dict, traffic: dict, init_fn, batches: list, *,
        dtype=jnp.float32, precision: str = "highest", batch_frac: float = 1.0) -> dict:
    """``init_fn()`` gives the initial float32 weights of the pod; it is
    called twice, so that they need not be held through the steps."""
    if traffic["pods"] != 1:
        raise NotImplementedError("the reference replays one pod, without gossip")
    beta, lr_r = cfg["beta"], cfg["lr_r"]
    lossf = models.loss(cfg)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(p, v, batch, lr):
        loss, g = jax.value_and_grad(lossf)(p, batch)
        v = jax.tree_util.tree_map(lambda vv, gg: beta * vv + gg, v, g)
        p = jax.tree_util.tree_map(lambda pp, vv: pp - lr * vv, p, v)
        return p, v, loss

    with jax.default_matmul_precision(precision):
        p = jax.jit(lambda t: jax.tree_util.tree_map(lambda a: a.astype(dtype), t))(init_fn())
        v = jax.tree_util.tree_map(jnp.zeros_like, p)
        losses, first = [], None
        for s, batch in enumerate(batches):
            lr = jnp.asarray(1.0 / (lr_r * float(s + 1) ** 0.499), dtype)
            keep = max(1, int(batch["tokens"].shape[1] * batch_frac))
            p, v, loss = step(p, v, {k: jnp.asarray(a[0, :keep]) for k, a in batch.items()}, lr)
            losses.append([float(loss)])
            if s == 0:
                first = _leaf_norms(v)[None]
        del v
        p0 = init_fn()
        third = _leaf_norms(jax.tree_util.tree_map(lambda a, b: a.astype(jnp.float32) - b, p, p0))
    return {"losses": losses, "first": first, "third": third[None]}
