"""Plain reference of DFedRW / QDFedRW rounds (arXiv:2508.21286 Alg. 1, 2).

It replays the rounds that the program ran, from the same inputs: the
walks, straggler masks, batch indices and aggregation plan the planner
drew, the round's key, and the benchmark's own initial weights. Nothing
else of the program is used. Per round:

1. Chain SGD (Eq. 10): each of the M chains starts from the model of its
   first device and takes K steps of plain SGD, with step size
   1 / (R * kbar^q), kbar counting global steps from 1; a step that the
   straggler mask drops leaves the chain as it was.
2. QDFedRW hop (Eq. 13): after each step the chains' parameter changes are
   sent as one stochastically quantized tensor per leaf, over all M chains,
   and the receiver adds the dequantized change to the model it had.
3. w^{t,last}: every device a chain visited keeps the model of the last
   visit, in step-major, then chain order.
4. Aggregation: each aggregator takes the weighted sum of the listed
   neighbours' last models (Eq. 11), or, quantized, its own round-start
   model plus the weighted sum of the neighbours' quantized changes
   against their round-start models, one tensor per (message, leaf)
   (Eq. 14).

Stochastic rounding (Eq. 12) draws its uniforms from the counter hash the
wire format specifies: a murmur3-style finaliser of the element's position
in the payload's flat layout, salted by the two words of the round key's
split. So the reference makes the same draws as the program, and differs
from it only where rounding decides a draw that lies on the edge.

Readings: each round's mean loss of the final chain models on their last
batch, and the norm of every (client, leaf) change against the initial
weights after the first and after the last round.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.refs import models

__all__ = ["run", "counter_uniforms", "qdq_leaf"]


def counter_uniforms(pos, words):
    """Uniforms in [0, 1) from uint32 positions and two uint32 seed words."""
    x = pos.astype(jnp.uint32) + words[0]
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    x = x + words[1]
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x27D4EB2F)
    x = x ^ (x >> 15)
    return (x >> 8).astype(jnp.int32).astype(jnp.float32) * jnp.float32(1.0 / (1 << 24))


def qdq_leaf(diff, pos, words, levels):
    """Eq. 12 round trip of one wire tensor: adaptive interval
    s = max|v| / (||v|| levels), unbiased stochastic rounding of |v|/||v||
    onto the s grid, dequantized. ``pos`` is each element's flat position."""
    dt = diff.dtype
    norm = jnp.sqrt(jnp.sum(diff * diff))
    safe = jnp.where(norm > 0, norm, jnp.ones((), dt))
    xmax = jnp.max(jnp.abs(diff)) / safe
    s = jnp.where(xmax > 0, xmax / levels, jnp.ones((), dt))
    x = jnp.abs(diff) / safe
    ell = jnp.floor(x / s)
    phi = x / s - ell
    u = counter_uniforms(pos, words).astype(dt)
    idx = jnp.clip(ell + (u < phi).astype(dt), 0, levels)
    return idx * jnp.sign(diff) * s * norm


def _positions(lead_rows, size, offset, d_pad, shape):
    """uint32 flat positions of a leaf's elements in a (rows, d_pad) payload."""
    rows = jnp.asarray(lead_rows, jnp.uint32).reshape((-1,) + (1,) * (len(shape) - 1))
    inner = jnp.arange(size, dtype=jnp.uint32).reshape(shape[1:]) if len(shape) > 1 \
        else jnp.zeros((), jnp.uint32)
    return rows * jnp.uint32(d_pad) + jnp.uint32(offset) + inner


def _words(key):
    return jax.random.key_data(key).reshape(-1)[:2].astype(jnp.uint32)


def run(cfg: dict, bits: int, params0, x, y, records: list, *,
        dtype=jnp.float32, precision: str = "highest", batch_frac: float = 1.0) -> dict:
    """Replay ``records`` (one dict per round: devices, mask, bidx, agg,
    key, kbar0) from ``params0``. ``dtype`` and ``precision`` set the
    arithmetic; ``batch_frac`` < 1 keeps only that share of every batch."""
    lay = models.layout(cfg)
    lossf = models.loss(cfg)
    n = cfg["n_clients"]
    levels = (1 << (bits - 1)) - 1
    quant = bits < 32
    cast = (lambda a: a.astype(dtype)) if dtype != jnp.float32 else (lambda a: a)
    with jax.default_matmul_precision(precision):
        p0 = jax.tree_util.tree_map(lambda a: jnp.asarray(a, dtype), params0)
        xs = jnp.asarray(x)
        xs = cast(xs) if jnp.issubdtype(xs.dtype, jnp.floating) else xs
        ys = jnp.asarray(y)
        grad = jax.jit(jax.vmap(jax.grad(lossf)))
        loss = jax.jit(jax.vmap(lossf))
        treedef = jax.tree_util.tree_structure(p0)
        leaves0 = jax.tree_util.tree_leaves(p0)

        @jax.jit
        def hop(old, new, key):
            words = _words(key)
            out = []
            for a, b, size, off in zip(old, new, lay["sizes"], lay["offsets"]):
                pos = _positions(np.arange(a.shape[0]), size, off, lay["d_pad"], a.shape)
                out.append(a + qdq_leaf(b - a, pos, words, levels))
            return out

        @jax.jit
        def message(diff, row, key):
            words = _words(key)
            return [qdq_leaf(dl, _positions([row], size, off, lay["d_pad"], (1,) + dl.shape)[0],
                             words, levels)
                    for dl, size, off in zip(diff, lay["sizes"], lay["offsets"])]

        change = jax.jit(lambda ls, l0: jnp.stack(
            [jnp.sqrt(jnp.sum(jnp.square((a - b).astype(jnp.float32)))) for a, b in zip(ls, l0)]))
        clients: dict = {}                    # client -> list of leaves
        get = lambda c: clients.get(int(c), leaves0)
        losses, changes = [], []
        for r, rec in enumerate(records):
            dev, mask, bidx = rec["devices"], rec["mask"], rec["bidx"]
            m, k = dev.shape
            bidx = bidx[:, :, : max(1, int(bidx.shape[2] * batch_frac))]
            chains = [jnp.stack(ls) for ls in zip(*[get(c) for c in dev[:, 0]])]
            qkey = jnp.asarray(rec["key"])
            traj = []
            for step in range(k):
                kbar = jnp.maximum(jnp.float32(rec["kbar0"] + step + 1), 1.0)
                lr = cast(1.0 / (cfg["lr_r"] * kbar ** cfg["lr_q"]))
                g = grad(jax.tree_util.tree_unflatten(treedef, chains),
                         (xs[bidx[:, step]], ys[bidx[:, step]]))
                live = jnp.asarray(mask[:, step])
                stepped = [jnp.where(live.reshape((-1,) + (1,) * (a.ndim - 1)), a - lr * ga, a)
                           for a, ga in zip(chains, jax.tree_util.tree_leaves(g))]
                if quant:
                    qkey, sub = jax.random.split(qkey)
                    stepped = hop(chains, stepped, sub)
                traj.append(stepped)
                chains = stepped
            last = jax.tree_util.tree_unflatten(treedef, chains)
            losses.append(float(jnp.mean(loss(last, (xs[bidx[:, -1]], ys[bidx[:, -1]])))))

            owner = {}                        # device -> (step, chain) of its last visit
            for step in range(k):
                for c in range(m):
                    if mask[c, step]:
                        owner[int(dev[c, step])] = (step, c)
            row = lambda s, c: [a[c] for a in traj[s]]
            start = {d: get(d) for d in owner}
            new = dict(clients)
            new.update({d: row(*sc) for d, sc in owner.items()})
            agg_dev, agg_rows, agg_w = rec["agg"]
            if quant:
                qkey, sub = jax.random.split(qkey)
                deq = {d: message([a - b for a, b in zip(row(s, c), start[d])],
                                  jnp.uint32(s * m + c), sub)
                       for d, (s, c) in owner.items()}
            updates = {}
            for a, ad in enumerate(agg_dev):
                if ad >= n:
                    continue
                if quant:
                    acc = list(get(ad))
                    for d, w in zip(agg_rows[a], agg_w[a]):
                        if int(d) in deq and w != 0:
                            acc = [u + cast(jnp.float32(w)) * v for u, v in zip(acc, deq[int(d)])]
                else:
                    acc = None
                    for d, w in zip(agg_rows[a], agg_w[a]):
                        src = new.get(int(d), leaves0)
                        term = [cast(jnp.float32(w)) * v for v in src]
                        acc = term if acc is None else [u + t for u, t in zip(acc, term)]
                updates[int(ad)] = acc
            new.update(updates)
            clients = new
            if r in (0, len(records) - 1):
                norms = np.zeros((n, len(leaves0)))
                for c, ls in clients.items():
                    norms[c] = np.asarray(change(ls, leaves0))
                changes.append(norms)
    return {"losses": losses, "first": changes[0], "third": changes[-1]}
