"""Plain jax.numpy oracle of the fused qdq kernel (quantize.py).

It replays the kernel's counter-hash uniforms from each element's position
in the (rows, 128) payload and the two seed words, then applies the same
Eq. 12 round trip, so the kernel must equal it bit for bit at any row count
and grid layout. It shares no code with the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["counter_uniforms", "qdq_rows_ref"]

LANES = 128


def counter_uniforms(rows: int, seed: jax.Array) -> jax.Array:
    """(rows, 128) float32 uniforms in [0, 1): the murmur3-style finalizer
    over the element counter r * 128 + c, salted by ``seed``'s two uint32
    words, keeping the top 24 bits."""
    seed = seed.reshape(2).astype(jnp.uint32)
    x = jnp.arange(rows * LANES, dtype=jnp.uint32).reshape(rows, LANES)
    x = x + seed[0]
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    x = x + seed[1]
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x27D4EB2F)
    x = x ^ (x >> 15)
    return (x >> 8).astype(jnp.float32) * jnp.float32(1.0 / (1 << 24))


@functools.partial(jax.jit, static_argnames=("bits",))
def qdq_rows_ref(w2d: jax.Array, s_rows: jax.Array, norm_rows: jax.Array, *,
                 bits: int, seed: jax.Array,
                 base2d: jax.Array | None = None) -> jax.Array:
    """[base +] Q^-1(Q(w)) of a (rows, 128) payload, row r on grid interval
    ``s_rows[r]`` and norm ``norm_rows[r]``. Jitted: XLA then compiles
    ``base + deq`` as it compiles the interpreted kernel's body (it may fuse
    the multiply and the add), so the two agree bit for bit on the CPU."""
    levels = (1 << (bits - 1)) - 1
    w = w2d.astype(jnp.float32)
    s = s_rows.reshape(-1, 1).astype(jnp.float32)
    norm = norm_rows.reshape(-1, 1).astype(jnp.float32)
    u = counter_uniforms(w.shape[0], seed)
    x = jnp.abs(w) / jnp.where(norm > 0.0, norm, 1.0)
    ell = jnp.floor(x / s)
    idx = jnp.clip(ell + (u < x / s - ell), 0, levels)
    deq = idx * jnp.sign(w) * s * norm
    return deq if base2d is None else base2d + deq
