"""Stochastic quantization for communication (paper §IV-B, Eq. 12, Lemma 3).

Quantizes the *normalized* components |w_v|/||w|| onto the grid
{0, s, 2s, ..., (2^{b-1}-1) s} by unbiased stochastic rounding; one bit of b
is the sign. The wire format for a d-vector is (Lambda, s, ||w||):
b*d bits of indices+signs plus 32+32 bits of side information, i.e.
(64 + b*d) bits versus 32*d unquantized (paper's cost accounting).

QDFedRW quantizes parameter *differences* (Eq. 13/14), never raw weights,
to avoid error accumulation in non-smooth nets; callers pass diffs.

Segment wire format (flat-buffer engine)
----------------------------------------
The flat round engine (repro.core.dfedrw, engine="flat") ships a whole
payload of models as one (B, d_pad) matrix in which every model-pytree leaf
owns a 128-aligned column block (repro.core.flatten.FlatSpec). On the wire
this is a sequence of per-leaf SEGMENTS, each an independent Eq. 12 tensor
with its own (s, ||w_seg||) header:

  * hop hand-off (Eq. 13): one segment per leaf, spanning all B chain rows
    — exactly the seed semantics of quantizing the stacked (B, ...) leaf as
    one tensor. Wire cost per hand-off: sum_l (64 + b*d_l) bits.
  * aggregation (Eq. 14): one segment per (message row, leaf) — each
    neighbor's diff quantizes its leaves separately, matching the seed's
    per-row vmapped quantize. The flat engine quantizes each *sender's*
    message once and broadcasts it to every aggregator listing the sender
    (one wire message per updated device); Eq. 18 accounting still charges
    every (sender -> aggregator) edge.

  Padding lanes inside a segment hold exact zeros end to end: they quantize
  to index 0 and never contribute to norms, so d in the cost accounting is
  the TRUE parameter count (FlatSpec.d), not d_pad.

Per segment the adaptive interval is s = max_v |w_v| / (||w_seg|| * levels)
(see QuantConfig); `repro.kernels.quantize.payload_quantize_dequantize` runs
the whole payload's quantize -> dequantize round trip as one fused Pallas
kernel call with per-row (s, norm) operands.

This module is the pure-jnp reference implementation; the Pallas TPU kernel
in repro/kernels/quantize/ uses the same grid and the same rounding rule.
It draws its stochastic-rounding uniforms from an in-register counter hash
instead of the threefry stream (statistically equivalent, ~10x cheaper on
CPU), so it is validated bit for bit against its own jax.numpy oracle,
`repro.kernels.quantize.ref`, which replays that hash, and, through
`payload_quantize_dequantize`, against a per-leaf oracle of this module's
adaptive grid fed the replayed uniforms (tests/test_kernels_quantize.py).
QDFedRW trajectories of the two engines agree to quantization noise rather
than bit-for-bit; see tests/test_flat_engine.py.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

__all__ = [
    "QuantConfig",
    "Quantized",
    "SUPPORTED_WIRE_WIDTHS",
    "validate_wire_bits",
    "quantize",
    "dequantize",
    "quantize_pytree",
    "dequantize_pytree",
    "wire_bits",
    "pytree_wire_bits",
]

# Bit-widths the Eq. 12 wire format (and the fused Pallas qdq kernels, whose
# signed index must fit int8) can carry; 32 is the fp32 pass-through. The
# adaptive controller (repro.sim.adapt) and the engine's per-width program
# table validate against this set.
SUPPORTED_WIRE_WIDTHS = (2, 3, 4, 5, 6, 7, 8, 32)


def validate_wire_bits(bits: int) -> int:
    """Reject widths the wire format cannot carry (sign + index must fit the
    kernels' int8 lanes; 32 means "no quantization")."""
    if bits not in SUPPORTED_WIRE_WIDTHS:
        raise ValueError(
            f"unsupported wire bit-width {bits!r}; "
            f"supported: {SUPPORTED_WIRE_WIDTHS}")
    return int(bits)


@dataclasses.dataclass(frozen=True)
class QuantConfig:
    """b-bit stochastic quantization with interval s (Eq. 12).

    bits=32 means 'no quantization' (identity; wire cost 32d).
    s=None (default) uses an ADAPTIVE per-tensor interval
    s = max_v |w_v|/||w|| / levels, so the grid spans the payload's actual
    dynamic range instead of [0, 1] (normalized components are ~1/sqrt(d);
    a fixed unit-range grid would waste ~all of its levels). The paper's
    wire format transmits s per payload (32 bits, §IV-B), which is exactly
    what makes the per-tensor choice free.
    """

    bits: int = 8
    s: float | None = None

    @property
    def levels(self) -> int:
        return (1 << (self.bits - 1)) - 1  # sign bit reserved

    @property
    def interval(self) -> float:
        """Static fallback interval (used when s is fixed)."""
        return self.s if self.s is not None else 1.0 / max(self.levels, 1)

    @property
    def enabled(self) -> bool:
        return self.bits < 32


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Quantized:
    """Wire representation of one quantized tensor: (Lambda, s, ||w||)."""

    indices: jax.Array  # int32 signed index: sgn(w_v) * ell'
    s: jax.Array        # scalar quantization interval (f32)
    norm: jax.Array     # scalar ||w|| (f32)
    shape: tuple = dataclasses.field(default=())

    def tree_flatten(self):
        return (self.indices, self.s, self.norm), self.shape

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, shape=aux)


def quantize(w: jax.Array, cfg: QuantConfig, key: jax.Array) -> Quantized:
    """Eq. 12: unbiased stochastic rounding of |w_v|/||w|| onto the s-grid."""
    wf = w.astype(jnp.float32)
    norm = jnp.linalg.norm(wf.reshape(-1))
    safe_norm = jnp.where(norm > 0, norm, 1.0)
    if cfg.s is None:
        # Adaptive per-tensor grid: cover [0, max|w_v|/||w||] exactly.
        xmax = jnp.max(jnp.abs(wf)) / safe_norm
        s = jnp.where(xmax > 0, xmax / max(cfg.levels, 1), 1.0).astype(jnp.float32)
    else:
        s = jnp.float32(cfg.s)
    x = jnp.abs(wf) / safe_norm          # in [0, 1]
    ell = jnp.floor(x / s)               # lower grid index
    phi = x / s - ell                    # relative position in the interval
    u = jax.random.uniform(key, wf.shape, dtype=jnp.float32)
    up = (u < phi).astype(jnp.float32)   # round up w.p. phi  (unbiased)
    idx = jnp.clip(ell + up, 0, cfg.levels).astype(jnp.int32)
    signed = idx * jnp.sign(wf).astype(jnp.int32)
    return Quantized(indices=signed, s=s, norm=norm, shape=tuple(w.shape))


def dequantize(q: Quantized, dtype: Any = jnp.float32) -> jax.Array:
    w = q.indices.astype(jnp.float32) * q.s * q.norm
    return w.astype(dtype).reshape(q.shape)


def quantize_pytree(tree, cfg: QuantConfig, key: jax.Array):
    """Quantize every leaf with an independent fold_in'd key."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    keys = jax.random.split(key, len(leaves))
    qleaves = [quantize(leaf, cfg, k) for leaf, k in zip(leaves, keys)]
    return jax.tree_util.tree_unflatten(treedef, qleaves)


def dequantize_pytree(qtree, dtype: Any = jnp.float32):
    return jax.tree_util.tree_map(
        lambda q: dequantize(q, dtype),
        qtree,
        is_leaf=lambda x: isinstance(x, Quantized),
    )


def wire_bits(d: int, bits: int) -> int:
    """Paper §IV-B: quantized vector costs 64 + b*d bits; fp32 costs 32*d."""
    if bits >= 32:
        return 32 * d
    return 64 + bits * d


def pytree_wire_bits(tree, bits: int) -> int:
    sizes = [int(x.size) for x in jax.tree_util.tree_leaves(tree)]
    return sum(wire_bits(d, bits) for d in sizes)
