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
offset. With ``quant_bits < 32`` the sender quantizes each payload (paper
Eq. 12) with a per-(leaf, offset, device) key and permutes the wire itself:
the signed indices as int8 codes and the (s, ||w||) header as one f32[2].
The receiver dequantizes (idx * s * ||w||) inside its weighted sum, so no
float payload crosses the link or is written on either side. At 32 bits the
leaf goes as it is. `wire_bytes` counts what one pod sends per round.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.quantization import (QuantConfig, Quantized, dequantize, quantize,
                                     validate_wire_bits)

__all__ = [
    "GossipConfig",
    "make_ring_weights",
    "make_expander_weights",
    "mixing_weights",
    "gossip_mix",
    "wire_bytes",
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


HEADER_BYTES = 8  # (s, ||w||) as f32[2]: the Eq. 12 side information


def wire_bytes(tree: Any, cfg: GossipConfig, n: int) -> int:
    """Bytes one pod sends per `gossip_mix` round over an ``n``-pod axis.

    ``tree`` is the stacked tree `gossip_mix` takes (arrays or
    ShapeDtypeStructs, one model per pod), so a pod holds 1/n of each leaf
    and sends it once per topology offset: as int8 codes plus the f32[2]
    header when ``quant_bits < 32``, in the leaf's dtype at 32 bits. One
    header per (leaf, offset) assumes a pod's leaf lives on one device; a
    pod split over k devices sends k headers per (leaf, offset)."""
    bits = validate_wire_bits(cfg.quant_bits)
    per_edge = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        size = math.prod(leaf.shape) // n
        per_edge += (size + HEADER_BYTES if bits < 32
                     else size * np.dtype(leaf.dtype).itemsize)
    return len(cfg.offsets(n)) * per_edge


def gossip_mix(tree: Any, specs: Any, mesh, cfg: GossipConfig,
               key: jax.Array | None = None) -> Any:
    """One decentralized averaging round (Eq. 11) along ``cfg.axis``.

    ``tree`` is a pytree of arrays sharded over ``mesh`` with PartitionSpecs
    ``specs``; receiver i gets sum_{(o, w)} w * shard_{(i+o) mod n}. With
    ``cfg.quant_bits < 32`` every transmitted (non-self) payload is
    stochastically quantized, seeded per (leaf, offset, device), and sent as
    int8 codes with its (s, ||w||) header; the receiver dequantizes it.
    """
    n = mesh.shape[cfg.axis]
    pairs = mixing_weights(n, cfg)
    bits = validate_wire_bits(cfg.quant_bits)
    quantized = bits < 32
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
                # receiver i takes the shard of sender (i + off) mod n.
                perm = [((i + off) % n, i) for i in range(n)]
                if quantized:
                    k = key_rep
                    for salt in (li, oi, me):  # collision-free per (leaf, edge, device)
                        k = jax.random.fold_in(k, salt)
                    q = quantize(xs, QuantConfig(bits=bits), k)
                    codes = jax.lax.ppermute(q.indices.astype(jnp.int8), cfg.axis, perm)
                    s, norm = jax.lax.ppermute(jnp.stack([q.s, q.norm]), cfg.axis, perm)
                    got = dequantize(Quantized(codes, s, norm, q.shape), dtype=xs.dtype)
                else:
                    got = jax.lax.ppermute(xs, cfg.axis, perm)
                acc = acc + w * got
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
