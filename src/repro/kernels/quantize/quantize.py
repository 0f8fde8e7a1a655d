"""Pallas TPU kernel: fused stochastic quantize-dequantize (paper Eq. 12).

The wire-format hot loop of QDFedRW: every hop hand-off (Eq. 13) and every
aggregation message (Eq. 14) sends Q(w) of a parameter diff, and the
receiver dequantizes it at once. Unfused, XLA materializes the
normalize -> grid-index -> stochastic-round -> sign -> rescale chain as
HBM-round-trip intermediates; this kernel runs the whole round trip
Q^-1(Q(w)) in ONE pass in VMEM, so the int8 indices never leave the
registers, and optionally adds the receiver's base in the same pass.

Layout: the payload is (rows, 128) lanes, and every row belongs to one wire
tensor (repro.core.flatten pads leaves to 128-element boundaries), so the
per-tensor adaptive grid rides along as per-row scale and norm operands,
(tile, 1) VMEM blocks. Row tiles of 512 (512*128*4B = 256 KiB/operand,
comfortably inside the ~16 MiB VMEM); in interpret mode (CPU) the whole
payload is one block. Stochastic-rounding uniforms come from a counter hash
in registers, salted by two seed words in SMEM; ref.py replays it in plain
jax.numpy and is the kernel's bit-exact oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["qdq_rows_kernel_call", "ROW_TILE", "LANES"]

ROW_TILE = 512
LANES = 128

_SMEM_WHOLE = pl.BlockSpec(memory_space=pltpu.SMEM)


def _counter_uniforms(tile: int, pid, seed_ref):
    """In-kernel stochastic-rounding uniforms: a murmur3-style finalizer over
    the element counter, salted by two 32-bit seed words. ~8 vectorizable
    integer ops per element and NO uniforms operand — on CPU,
    threefry/philox streams cost more than the entire rest of the
    quantization path, and on TPU this saves an HBM read of the whole
    payload. Counter-based (stateless), so the kernel stays deterministic
    given (seed, element position) and identical across grid layouts."""
    r = jax.lax.broadcasted_iota(jnp.uint32, (tile, LANES), 0)
    c = jax.lax.broadcasted_iota(jnp.uint32, (tile, LANES), 1)
    x = (r + jnp.uint32(pid * tile)) * jnp.uint32(LANES) + c
    x = x + seed_ref[0, 0]
    x = x ^ (x >> jnp.uint32(16))
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> jnp.uint32(13))
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> jnp.uint32(16))
    x = x + seed_ref[0, 1]
    x = x ^ (x >> jnp.uint32(16))
    x = x * jnp.uint32(0x27D4EB2F)
    x = x ^ (x >> jnp.uint32(15))
    # 24-bit mantissa resolution, matching jax.random.uniform's float32.
    # The value is below 2^24, so the cast through int32 (Mosaic has no
    # uint32 -> float32 conversion) is exact.
    return ((x >> jnp.uint32(8)).astype(jnp.int32).astype(jnp.float32)
            * jnp.float32(1.0 / (1 << 24)))


def _qdq_rows_kernel(w_ref, *refs, levels: int, tile: int):
    """out = [base +] Q^-1(Q(w)) for one row tile; ``refs`` is
    ``([base_ref,] seed_ref, s_ref, norm_ref, out_ref)``."""
    *base_ref, seed_ref, s_ref, norm_ref, out_ref = refs
    w = w_ref[...].astype(jnp.float32)
    u = _counter_uniforms(tile, pl.program_id(0), seed_ref)
    s = s_ref[...]                   # (tile, 1) per-row grid interval
    norm = norm_ref[...]             # (tile, 1) per-row segment norm
    safe = jnp.where(norm > 0.0, norm, 1.0)
    x = jnp.abs(w) / safe
    ell = jnp.floor(x / s)
    phi = x / s - ell
    idx = jnp.clip(ell + (u < phi).astype(jnp.float32), 0.0, float(levels))
    base = [ref[...] for ref in base_ref]
    deq = idx * jnp.sign(w) * s * norm
    out_ref[...] = base[0] + deq if base else deq


def qdq_rows_kernel_call(w2d: jax.Array, s_rows: jax.Array, norm_rows: jax.Array,
                         *, bits: int, seed: jax.Array,
                         base2d: jax.Array | None = None,
                         interpret: bool = False) -> jax.Array:
    """Fused quantize+dequantize of a (R, 128) payload with per-row segment
    scales — the QDFedRW hot-path form (Eq. 13/14 transmit Q(diff); the
    receiver immediately dequantizes, so the simulation never needs the
    indices in memory). Row r uses grid interval ``s_rows[r]`` and norm
    ``norm_rows[r]``; ``seed`` is two uint32 words of the counter RNG. With
    ``base2d`` the kernel also applies the received update in the same
    pass: out = base + Q^-1(Q(w)). R must be a multiple of ROW_TILE unless
    ``interpret`` (one whole-payload block).

    The diff payload is a temporary, so its buffer is aliased with the
    output (`input_output_aliases`): the interpret-mode pass stays in-place
    instead of round-tripping a fresh buffer per call."""
    rows = w2d.shape[0]
    tile = rows if interpret else ROW_TILE
    assert w2d.shape[1] == LANES and rows % tile == 0, w2d.shape
    row_block = pl.BlockSpec((tile, LANES), lambda i: (i, 0))
    scalar_block = pl.BlockSpec((tile, 1), lambda i: (i, 0))
    bases = () if base2d is None else (base2d,)
    return pl.pallas_call(
        functools.partial(_qdq_rows_kernel, levels=(1 << (bits - 1)) - 1, tile=tile),
        grid=(rows // tile,),
        in_specs=[row_block] * (1 + len(bases)) + [_SMEM_WHOLE, scalar_block, scalar_block],
        out_specs=row_block,
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
        input_output_aliases={0: 0},
        interpret=interpret,
    )(w2d, *bases, seed.reshape(1, 2).astype(jnp.uint32),
      s_rows.reshape(rows, 1), norm_rows.reshape(rows, 1))
