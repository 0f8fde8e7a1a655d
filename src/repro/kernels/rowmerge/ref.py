"""Pure-jnp oracle of the row merge: the two row scatters it replaces."""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["merge_rows_ref"]


def merge_rows_ref(matrix: jax.Array, set_ids: jax.Array, set_rows: jax.Array,
                   add_ids: jax.Array | None = None,
                   add_rows: jax.Array | None = None) -> jax.Array:
    n, d = matrix.shape
    ids = set_ids.reshape(-1)
    out = matrix.at[jnp.where(ids < 0, n, ids)].set(set_rows.reshape(-1, d), mode="drop")
    if add_ids is None:
        return out
    ids = jnp.where(add_ids < 0, n, add_ids)
    return out.at[ids].set(matrix[jnp.minimum(ids, n - 1)] + add_rows, mode="drop")
