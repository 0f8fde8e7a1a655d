"""Gossip collectives over a device-mesh axis (pod-scale decentralized FL).

Each device along the gossip axis holds one model replica (a "pod" in the
§VI-F large-scale picture). One gossip round performs decentralized weighted
averaging (paper Eq. 11) over a virtual topology of *offsets* on the axis:
receiver i mixes shards from senders (i + o) mod n for each topology offset
o, with weights that sum to one (doubly stochastic — the global mean is
preserved, matching the MH-walk stationary distribution the paper targets).

`walk_permute_batch` is the random-walk hand-off primitive: it moves every
pod's tensors one topology hop along the axis (receiver i takes the shard of
(i - offset) mod n), i.e. the chain state w^{t,k} migrating to the next
device.

Implementation: `shard_map` + `lax.ppermute` collective permutes, one per
offset. With ``quant_bits < 32`` the transmitted payloads go through the
stochastic quantizer (paper Eq. 12) before the permute — the wire round trip
Q^-1(Q(w)) with a per-(device, offset) key — which is what QDFedRW sends on
every cross-device edge.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.quantization import QuantConfig, dequantize, quantize

__all__ = [
    "GossipConfig",
    "make_ring_weights",
    "make_expander_weights",
    "mixing_weights",
    "gossip_mix",
    "walk_permute_batch",
]


@dataclasses.dataclass(frozen=True)
class GossipConfig:
    """Gossip topology + wire format over one mesh axis.

    topology: "ring" (offsets ±1), "expander" (powers of two — a circulant
    expander with log2(n) distinct offsets), or "all" (complete graph).
    quant_bits < 32 quantizes every transmitted payload (Eq. 12/13).
    every: gossip period of the federated train step (make_fed_train_step
    mixes after every `every`-th local step).
    """

    axis: str = "pod"
    topology: str = "ring"
    quant_bits: int = 32
    every: int = 1
    seed: int = 0

    def offsets(self, n: int) -> list[int]:
        """Distinct non-zero shard offsets 0 < o < n of the virtual graph."""
        if n <= 1:
            return []
        if self.topology == "ring":
            return [1] if n == 2 else [1, n - 1]
        if self.topology == "all":
            return list(range(1, n))
        if self.topology == "expander":
            offs, o = [], 1
            while o < n:
                offs.append(o)
                o *= 2
            return offs
        raise ValueError(f"unknown gossip topology {self.topology!r}")


def mixing_weights(n: int, cfg: GossipConfig) -> list[tuple[int, float]]:
    """Uniform (offset, weight) pairs over {self} ∪ offsets; weights sum to 1.

    Uniform weights over a circulant offset neighborhood make the mixing
    matrix doubly stochastic, so the mean over the axis is preserved (the
    uniform stationary distribution the paper's MH walk targets). Ring and
    "all" offset sets are closed under negation, giving a symmetric
    (reversible) W; the powers-of-two expander set is directed — still
    doubly stochastic, not symmetric."""
    offs = cfg.offsets(n)
    w = 1.0 / (len(offs) + 1)
    return [(0, w)] + [(o, w) for o in offs]


def make_ring_weights(n: int) -> list[tuple[int, float]]:
    return mixing_weights(n, GossipConfig(topology="ring"))


def make_expander_weights(n: int, cfg: GossipConfig) -> list[tuple[int, float]]:
    return mixing_weights(n, dataclasses.replace(cfg, topology="expander"))


def _wire_round_trip(xs: jax.Array, bits: int, key: jax.Array) -> jax.Array:
    """Simulate the quantized wire: deq(Q(x)) with the Eq. 12 adaptive grid."""
    q = quantize(xs, QuantConfig(bits=bits), key)
    return dequantize(q, dtype=xs.dtype).reshape(xs.shape)


def gossip_mix(tree: Any, specs: Any, mesh, cfg: GossipConfig,
               key: jax.Array | None = None) -> Any:
    """One decentralized averaging round (Eq. 11) along ``cfg.axis``.

    ``tree`` is a pytree of arrays sharded over ``mesh`` with PartitionSpecs
    ``specs``; receiver i gets sum_{(o, w)} w * shard_{(i+o) mod n}. With
    ``cfg.quant_bits < 32`` every transmitted (non-self) payload goes through
    the stochastic quantizer, seeded per (device, offset, leaf).
    """
    n = mesh.shape[cfg.axis]
    pairs = mixing_weights(n, cfg)
    quantized = cfg.quant_bits < 32
    if quantized and key is None:
        raise ValueError("gossip_mix with quant_bits < 32 requires a PRNG key")
    if key is None:
        key = jax.random.PRNGKey(cfg.seed)  # unused on the fp32 path

    def mix(key_rep, *leaves):
        me = jax.lax.axis_index(cfg.axis)
        out = []
        for li, xs in enumerate(leaves):
            acc = pairs[0][1] * xs
            for oi, (off, w) in enumerate(pairs[1:]):
                payload = xs
                if quantized:
                    k = key_rep
                    for salt in (li, oi, me):  # collision-free per (leaf, edge, device)
                        k = jax.random.fold_in(k, salt)
                    payload = _wire_round_trip(xs, cfg.quant_bits, k)
                # receiver i takes the shard of sender (i + off) mod n.
                perm = [((i + off) % n, i) for i in range(n)]
                acc = acc + w * jax.lax.ppermute(payload, cfg.axis, perm)
            out.append(acc)
        return tuple(out)

    leaves, treedef = jax.tree_util.tree_flatten(tree)
    spec_leaves = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda s: isinstance(s, P)
    )
    mixed = jax.shard_map(
        mix,
        mesh=mesh,
        in_specs=(P(),) + tuple(spec_leaves),
        out_specs=tuple(spec_leaves),
    )(key, *leaves)
    return jax.tree_util.tree_unflatten(treedef, list(mixed))


def walk_permute_batch(tree: Any, specs: Any, mesh, axis: str,
                       offset: int = 1) -> Any:
    """Move every pod's tensors one walk hop along ``axis``: receiver i takes
    the shard of (i - offset) mod n (i.e. shard j travels to j + offset)."""
    n = mesh.shape[axis]
    perm = [(j, (j + offset) % n) for j in range(n)]

    def hop(*leaves):
        return tuple(jax.lax.ppermute(l, axis, perm) for l in leaves)

    leaves, treedef = jax.tree_util.tree_flatten(tree)
    spec_leaves = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda s: isinstance(s, P)
    )
    moved = jax.shard_map(
        hop,
        mesh=mesh,
        in_specs=tuple(spec_leaves),
        out_specs=tuple(spec_leaves),
    )(*leaves)
    return jax.tree_util.tree_unflatten(treedef, list(moved))
