"""Public wrapper of the row-merge kernel: one round's row writes into the
(n, d) client matrix, in place.

``merge_rows`` builds the kernel's per-row source table on the device from
the writers' target rows, picks the column block from a VMEM budget, and
runs the kernel: compiled on TPU, interpreted on CPU. The kernel holds every
set row and add row of a column block in VMEM at once; where even a
128-lane column block of them passes 96 MiB (about 98,000 rows of the two
together), the rows are written by XLA's row scatter instead
(``ref.merge_rows_ref``), which gives the same result.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.rowmerge.ref import merge_rows_ref
from repro.kernels.rowmerge.rowmerge import GROUP, LANES, merge_rows_kernel_call

__all__ = ["merge_rows"]

# double-buffered blocks of the matrix (in and out), the set rows and the
# add rows take at most this much VMEM; the column block shrinks to fit,
# down to 128 lanes, which may take up to _VMEM_MAX (v5e: 128 MiB of VMEM)
_VMEM_BLOCKS = 24 << 20
_VMEM_MAX = 96 << 20


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def _padded(rows: int) -> int:
    return _cdiv(rows, GROUP) * GROUP


def _sources(rows: int, n: int, set_ids: jax.Array, add_ids: jax.Array | None):
    """(rows,) int32: -1 keep, w take set row w, W + a add row a."""
    src = jnp.full((rows,), -1, jnp.int32)
    for ids, first in ((set_ids.reshape(-1), 0), (add_ids, set_ids.size)):
        if ids is None:
            continue
        ids = ids.astype(jnp.int32)
        ids = jnp.where((ids >= 0) & (ids < n), ids, rows)      # dropped
        src = src.at[ids].set(first + jnp.arange(ids.size, dtype=jnp.int32), mode="drop")
    return src


def merge_rows(matrix: jax.Array, set_ids: jax.Array, set_rows: jax.Array,
               add_ids: jax.Array | None = None,
               add_rows: jax.Array | None = None) -> jax.Array:
    """``matrix`` with row ``set_ids[w]`` replaced by ``set_rows[w]`` and
    row ``add_ids[a]`` replaced by ``matrix[add_ids[a]] + add_rows[a]``.

    ``set_rows`` is (W, d) or (K, M, d), with ``set_ids`` of its leading
    shape; ``add_rows`` is (A, d). An id outside [0, n) writes nothing; the
    ids in range are distinct within each of the two lists. A row named by
    both lists takes the add, from the old row. The matrix is updated in
    place: donate it, and read nothing of it after this call.
    """
    interpret = jax.default_backend() == "cpu"
    n, d = matrix.shape
    assert d % LANES == 0, d
    assert set_ids.shape == set_rows.shape[:-1], (set_ids.shape, set_rows.shape)
    set3 = set_rows if set_rows.ndim == 3 else set_rows[None]
    lead, minor = set3.shape[:2]
    if interpret:
        # one block: the interpreter copies whole buffers at every grid step
        return merge_rows_kernel_call(
            matrix, _sources(n, n, set_ids, add_ids), set3, add_rows,
            rows=n, cols=d, interpret=True)
    # f32 words a column of the double-buffered blocks holds (sublanes pad to 8)
    words = 2 * (2 * GROUP + lead * _padded(minor)
                 + (0 if add_rows is None else _padded(add_rows.shape[0])))
    if 4 * words * LANES > _VMEM_MAX:
        return merge_rows_ref(matrix, set_ids, set_rows, add_ids, add_rows)
    tiles = d // LANES
    blocks = _cdiv(tiles, max(1, _VMEM_BLOCKS // (4 * words * LANES)))
    cols = _cdiv(tiles, blocks) * LANES       # equal blocks, each within budget
    return merge_rows_kernel_call(
        matrix, _sources(_padded(n), n, set_ids, add_ids), set3, add_rows,
        rows=GROUP, cols=cols, vmem_limit_bytes=4 * words * cols + (8 << 20))
