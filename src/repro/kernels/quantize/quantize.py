"""Pallas TPU kernel: fused stochastic quantization (paper Eq. 12).

The wire-format hot loop of QDFedRW: for every parameter-diff payload each
gossip/walk hop quantizes d elements. Unfused, XLA materializes four
HBM-round-trip intermediates (|w|/||w||, floor, phi, compare); this kernel
fuses normalize -> grid-index -> stochastic-round -> sign into ONE pass in
VMEM, reading w (+ pre-generated uniforms) once and writing int8 once.

TPU adaptation (DESIGN.md §2): the GPU reference implementations do
per-thread RNG; on TPU we keep the kernel deterministic and feed uniforms
as an operand (generated by XLA's threefry, which fuses well), which also
makes the kernel bit-exact against the pure-jnp oracle in ref.py. Block
layout: input reshaped to (rows, 128) lanes, row-tiles of 512 in VMEM
(512*128*4B = 256 KiB/operand -- comfortably inside the ~16 MiB VMEM).

The scalar ||w|| is computed by XLA (a single fused reduction) and passed
via SMEM; quantization grid s and bit-width are compile-time constants.
The segment-wise kernels take their per-row scale and norm as (tile, 1)
VMEM blocks, and the counter-RNG seed words as a whole-array SMEM operand.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "quantize_kernel_call",
    "dequantize_kernel_call",
    "quantize_rows_kernel_call",
    "dequantize_rows_kernel_call",
    "qdq_rows_kernel_call",
    "ROW_TILE",
    "LANES",
]

ROW_TILE = 512
LANES = 128

_SMEM_WHOLE = pl.BlockSpec(memory_space=pltpu.SMEM)


def _quantize_kernel(w_ref, u_ref, norm_ref, out_ref, *, s: float, levels: int):
    w = w_ref[...].astype(jnp.float32)
    u = u_ref[...]
    norm = norm_ref[0, 0]
    safe = jnp.where(norm > 0.0, norm, 1.0)
    x = jnp.abs(w) / safe
    ell = jnp.floor(x / s)
    phi = x / s - ell
    idx = jnp.clip(ell + (u < phi).astype(jnp.float32), 0.0, float(levels))
    out_ref[...] = (idx * jnp.sign(w)).astype(jnp.int8)


def _dequantize_kernel(q_ref, norm_ref, out_ref, *, s: float):
    q = q_ref[...].astype(jnp.float32)
    norm = norm_ref[0, 0]
    out_ref[...] = (q * s * norm).astype(out_ref.dtype)


def quantize_kernel_call(w2d: jax.Array, u2d: jax.Array, norm: jax.Array,
                         *, s: float, bits: int, interpret: bool = False) -> jax.Array:
    """w2d (R, 128) f32/bf16, u2d (R, 128) f32 uniforms, norm scalar f32.
    R must be a multiple of ROW_TILE (ops.py pads)."""
    rows = w2d.shape[0]
    assert w2d.shape[1] == LANES and rows % ROW_TILE == 0, w2d.shape
    levels = (1 << (bits - 1)) - 1
    grid = (rows // ROW_TILE,)
    return pl.pallas_call(
        functools.partial(_quantize_kernel, s=s, levels=levels),
        grid=grid,
        in_specs=[
            pl.BlockSpec((ROW_TILE, LANES), lambda i: (i, 0)),
            pl.BlockSpec((ROW_TILE, LANES), lambda i: (i, 0)),
            _SMEM_WHOLE,
        ],
        out_specs=pl.BlockSpec((ROW_TILE, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.int8),
        interpret=interpret,
    )(w2d, u2d, norm.reshape(1, 1))


def _quantize_rows_kernel(w_ref, u_ref, s_ref, norm_ref, out_ref, *, levels: int):
    w = w_ref[...].astype(jnp.float32)
    u = u_ref[...]
    s = s_ref[...]                   # (ROW_TILE, 1) per-row grid interval
    norm = norm_ref[...]             # (ROW_TILE, 1) per-row segment norm
    safe = jnp.where(norm > 0.0, norm, 1.0)
    x = jnp.abs(w) / safe
    ell = jnp.floor(x / s)
    phi = x / s - ell
    idx = jnp.clip(ell + (u < phi).astype(jnp.float32), 0.0, float(levels))
    out_ref[...] = (idx * jnp.sign(w)).astype(jnp.int8)


def _dequantize_rows_kernel(q_ref, s_ref, norm_ref, out_ref):
    q = q_ref[...].astype(jnp.float32)
    out_ref[...] = (q * s_ref[...] * norm_ref[...]).astype(out_ref.dtype)


def _qdq_rows_kernel(w_ref, u_ref, s_ref, norm_ref, out_ref, *, levels: int):
    """Quantize -> dequantize in registers: the wire round trip Q^-1(Q(w))
    the protocol simulation needs, in ONE memory pass (the int8 indices never
    leave the kernel)."""
    w = w_ref[...].astype(jnp.float32)
    u = u_ref[...]
    s = s_ref[...]
    norm = norm_ref[...]
    safe = jnp.where(norm > 0.0, norm, 1.0)
    x = jnp.abs(w) / safe
    ell = jnp.floor(x / s)
    phi = x / s - ell
    idx = jnp.clip(ell + (u < phi).astype(jnp.float32), 0.0, float(levels))
    out_ref[...] = (idx * jnp.sign(w) * s * norm).astype(out_ref.dtype)


def _qdq_delta_rows_kernel(w_ref, base_ref, u_ref, s_ref, norm_ref, out_ref,
                           *, levels: int):
    """QDFedRW hop hand-off in one pass: out = base + Q^-1(Q(w)) — the
    receiver reconstructs w^k + deq(Q(diff)) without a separate add pass."""
    w = w_ref[...].astype(jnp.float32)
    u = u_ref[...]
    s = s_ref[...]
    norm = norm_ref[...]
    safe = jnp.where(norm > 0.0, norm, 1.0)
    x = jnp.abs(w) / safe
    ell = jnp.floor(x / s)
    phi = x / s - ell
    idx = jnp.clip(ell + (u < phi).astype(jnp.float32), 0.0, float(levels))
    out_ref[...] = (base_ref[...] + idx * jnp.sign(w) * s * norm).astype(out_ref.dtype)


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


def _qdq_delta_rows_rng_kernel(w_ref, base_ref, seed_ref, s_ref, norm_ref,
                               out_ref, *, levels: int, tile: int):
    """`_qdq_delta_rows_kernel` with in-kernel counter RNG."""
    w = w_ref[...].astype(jnp.float32)
    u = _counter_uniforms(tile, pl.program_id(0), seed_ref)
    s = s_ref[...]
    norm = norm_ref[...]
    safe = jnp.where(norm > 0.0, norm, 1.0)
    x = jnp.abs(w) / safe
    ell = jnp.floor(x / s)
    phi = x / s - ell
    idx = jnp.clip(ell + (u < phi).astype(jnp.float32), 0.0, float(levels))
    out_ref[...] = (base_ref[...] + idx * jnp.sign(w) * s * norm).astype(out_ref.dtype)


def _qdq_rows_rng_kernel(w_ref, seed_ref, s_ref, norm_ref, out_ref,
                         *, levels: int, tile: int):
    """`_qdq_rows_kernel` with in-kernel counter RNG."""
    w = w_ref[...].astype(jnp.float32)
    u = _counter_uniforms(tile, pl.program_id(0), seed_ref)
    s = s_ref[...]
    norm = norm_ref[...]
    safe = jnp.where(norm > 0.0, norm, 1.0)
    x = jnp.abs(w) / safe
    ell = jnp.floor(x / s)
    phi = x / s - ell
    idx = jnp.clip(ell + (u < phi).astype(jnp.float32), 0.0, float(levels))
    out_ref[...] = (idx * jnp.sign(w) * s * norm).astype(out_ref.dtype)


def quantize_rows_kernel_call(w2d: jax.Array, u2d: jax.Array, s_rows: jax.Array,
                              norm_rows: jax.Array, *, bits: int,
                              interpret: bool = False) -> jax.Array:
    """Segment-wise variant of the fused quantizer: row r of the (R, 128)
    payload uses grid interval ``s_rows[r]`` and norm ``norm_rows[r]``.

    Callers lay the payload out so every row belongs to exactly one tensor
    segment (repro.core.flatten pads leaves to 128-element boundaries), which
    preserves the paper's per-tensor adaptive grid while keeping ONE kernel
    launch per payload. The per-row scalars ride along as (R, 1) operands
    (dynamic, data-dependent — unlike the compile-time `s` of
    quantize_kernel_call).

    In interpret mode (CPU) the whole payload is one grid step: the
    interpreter's grid loop carries full-buffer copies per step, and the
    one-block variant lets XLA fuse the body like plain vector ops. On TPU
    the VMEM row tile applies."""
    rows = w2d.shape[0]
    tile = rows if interpret else ROW_TILE
    assert w2d.shape[1] == LANES and rows % tile == 0, w2d.shape
    levels = (1 << (bits - 1)) - 1
    grid = (rows // tile,)
    return pl.pallas_call(
        functools.partial(_quantize_rows_kernel, levels=levels),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile, LANES), lambda i: (i, 0)),
            pl.BlockSpec((tile, LANES), lambda i: (i, 0)),
            pl.BlockSpec((tile, 1), lambda i: (i, 0)),
            pl.BlockSpec((tile, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((tile, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.int8),
        interpret=interpret,
    )(w2d, u2d, s_rows.reshape(rows, 1), norm_rows.reshape(rows, 1))


def qdq_rows_kernel_call(w2d: jax.Array, u2d: jax.Array | None,
                         s_rows: jax.Array, norm_rows: jax.Array, *, bits: int,
                         base2d: jax.Array | None = None,
                         seed: jax.Array | None = None,
                         out_dtype=jnp.float32, interpret: bool = False) -> jax.Array:
    """Fused quantize+dequantize with per-row segment scales — the QDFedRW
    hot-path form (Eq. 13/14 transmit Q(diff); the receiver immediately
    dequantizes, so the simulation never needs the indices in memory). With
    ``base2d`` the kernel also applies the received update in the same pass:
    out = base + Q^-1(Q(w)).

    Uniforms: pass ``u2d`` to feed pre-drawn uniforms (bit-exact against the
    oracle given the same draws), or pass ``u2d=None`` with a ``seed`` of two
    uint32 words to let the kernel generate counter-hash uniforms in
    registers (`_counter_uniforms`) — the fast path for protocol simulation.

    The diff payload is a temporary, so its buffer is aliased with the
    output (`input_output_aliases`): the interpret-mode pass stays in-place
    instead of round-tripping a fresh buffer per call."""
    rows = w2d.shape[0]
    tile = rows if interpret else ROW_TILE
    assert w2d.shape[1] == LANES and rows % tile == 0, w2d.shape
    assert (u2d is None) != (seed is None), "pass exactly one of u2d / seed"
    levels = (1 << (bits - 1)) - 1
    grid = (rows // tile,)
    row_block = pl.BlockSpec((tile, LANES), lambda i: (i, 0))
    scalar_block = pl.BlockSpec((tile, 1), lambda i: (i, 0))
    if u2d is not None:
        rng_operand = u2d
        rng_spec = row_block
        kernels = (_qdq_rows_kernel, _qdq_delta_rows_kernel)
        extra = {}
    else:
        rng_operand = seed.reshape(1, 2).astype(jnp.uint32)
        rng_spec = _SMEM_WHOLE
        kernels = (_qdq_rows_rng_kernel, _qdq_delta_rows_rng_kernel)
        extra = {"tile": tile}
    if base2d is None:
        kernel = functools.partial(kernels[0], levels=levels, **extra)
        operands = (w2d, rng_operand)
        in_specs = [row_block, rng_spec, scalar_block, scalar_block]
    else:
        kernel = functools.partial(kernels[1], levels=levels, **extra)
        operands = (w2d, base2d, rng_operand)
        in_specs = [row_block, row_block, rng_spec, scalar_block, scalar_block]
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=row_block,
        out_shape=jax.ShapeDtypeStruct((rows, LANES), out_dtype),
        input_output_aliases={0: 0},
        interpret=interpret,
    )(*operands, s_rows.reshape(rows, 1), norm_rows.reshape(rows, 1))


def dequantize_rows_kernel_call(q2d: jax.Array, s_rows: jax.Array,
                                norm_rows: jax.Array, *, out_dtype=jnp.float32,
                                interpret: bool = False) -> jax.Array:
    rows = q2d.shape[0]
    tile = rows if interpret else ROW_TILE
    assert q2d.shape[1] == LANES and rows % tile == 0, q2d.shape
    grid = (rows // tile,)
    return pl.pallas_call(
        functools.partial(_dequantize_rows_kernel),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile, LANES), lambda i: (i, 0)),
            pl.BlockSpec((tile, 1), lambda i: (i, 0)),
            pl.BlockSpec((tile, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((tile, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANES), out_dtype),
        interpret=interpret,
    )(q2d, s_rows.reshape(rows, 1), norm_rows.reshape(rows, 1))


def dequantize_kernel_call(q2d: jax.Array, norm: jax.Array, *, s: float,
                           out_dtype=jnp.float32, interpret: bool = False) -> jax.Array:
    rows = q2d.shape[0]
    assert q2d.shape[1] == LANES and rows % ROW_TILE == 0, q2d.shape
    grid = (rows // ROW_TILE,)
    return pl.pallas_call(
        functools.partial(_dequantize_kernel, s=s),
        grid=grid,
        in_specs=[
            pl.BlockSpec((ROW_TILE, LANES), lambda i: (i, 0)),
            _SMEM_WHOLE,
        ],
        out_specs=pl.BlockSpec((ROW_TILE, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANES), out_dtype),
        interpret=interpret,
    )(q2d, norm.reshape(1, 1))
