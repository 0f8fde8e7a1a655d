"""Public wrapper of the fused qdq kernel: one flat-buffer payload's
Eq. 12/13/14 wire round trip in one kernel call.

`payload_quantize_dequantize` computes each wire tensor's (s, norm) header
from plain sliced reductions over the 128-aligned leaf column ranges, lays
the payload out as (rows, 128) lanes and calls `qdq_rows_kernel_call`:
interpreted on CPU, compiled to one fused VMEM pass on TPU.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.quantize.quantize import LANES, ROW_TILE, qdq_rows_kernel_call

__all__ = ["payload_quantize_dequantize"]


def payload_quantize_dequantize(payload: jax.Array, layout, *, per_message: bool,
                                bits: int, key: jax.Array,
                                s: float | None = None,
                                base: jax.Array | None = None,
                                interpret: bool | None = None) -> jax.Array:
    """Eq. 12/13/14 wire round trip for a whole (B, d_pad) flat-buffer
    payload in ONE fused Pallas kernel call.

    ``layout`` is the `repro.core.flatten.FlatSpec` describing the 128-
    aligned leaf column ranges. Per wire tensor the paper's adaptive grid is
    used (norm = ||w_seg||, s = max|w_v| / (||w_seg|| levels)); wire tensors
    are the per-leaf column blocks, either per message row
    (``per_message=True``, Eq. 14 aggregation: one tensor per (message,
    leaf)) or spanning all B rows (Eq. 13 hop hand-off: one tensor per
    leaf). Because every leaf is a contiguous, statically known column
    range, the side information comes from plain sliced reductions — no
    scatter-based segment ops on the hot path. ``s`` fixes the grid
    interval (QuantConfig.s) instead of the per-tensor adaptive choice.
    ``base`` fuses the receiver's base + deq into the kernel pass.
    Stochastic-rounding uniforms come from the kernel's in-register counter
    RNG seeded by ``key``. ``interpret`` defaults by backend (interpreter on
    CPU, compiled kernel otherwise).
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    b, d_pad = payload.shape
    assert d_pad == layout.d_pad, (d_pad, layout.d_pad)
    levels = max((1 << (bits - 1)) - 1, 1)
    wf = payload.astype(jnp.float32)
    s_parts, n_parts = [], []
    for off, psize in zip(layout.offsets, layout.padded_sizes):
        blk = jax.lax.slice_in_dim(wf, off, off + psize, axis=1)
        rows_l = psize // LANES
        if per_message:
            norm = jnp.sqrt(jnp.sum(blk * blk, axis=1))        # (B,)
            amax = jnp.max(jnp.abs(blk), axis=1)
        else:
            norm = jnp.broadcast_to(jnp.sqrt(jnp.sum(blk * blk)), (b,))
            amax = jnp.broadcast_to(jnp.max(jnp.abs(blk)), (b,))
        safe = jnp.where(norm > 0, norm, 1.0)
        if s is None:
            xmax = amax / safe
            s_leaf = jnp.where(xmax > 0, xmax / levels, 1.0).astype(jnp.float32)
        else:
            s_leaf = jnp.full((b,), s, dtype=jnp.float32)
        s_parts.append(jnp.broadcast_to(s_leaf[:, None], (b, rows_l)))
        n_parts.append(jnp.broadcast_to(norm[:, None].astype(jnp.float32),
                                        (b, rows_l)))
    rows = b * layout.rows
    s_rows = jnp.concatenate(s_parts, axis=1).reshape(rows)
    norm_rows = jnp.concatenate(n_parts, axis=1).reshape(rows)
    seed = jax.random.key_data(key).reshape(-1)[:2]
    w2d = wf.reshape(rows, LANES)
    base2d = None if base is None else base.reshape(rows, LANES)
    if not interpret:
        # pad rows quantize to 0 (w=0, s=1, norm=0 -> safe norm 1) and are
        # sliced off after
        pad = (-rows) % ROW_TILE
        if pad:
            w2d = jnp.pad(w2d, ((0, pad), (0, 0)))
            s_rows = jnp.pad(s_rows, (0, pad), constant_values=1.0)
            norm_rows = jnp.pad(norm_rows, (0, pad))
            if base2d is not None:
                base2d = jnp.pad(base2d, ((0, pad), (0, 0)))
    deq = qdq_rows_kernel_call(w2d, s_rows, norm_rows, bits=bits, seed=seed,
                               base2d=base2d, interpret=interpret)
    return deq[: rows].reshape(b, d_pad)
