"""Flat parameter-buffer codec for the vectorized DFedRW round engine.

The protocol engine keeps all n device models as ONE `(n, d_pad)` float32
matrix instead of a stacked pytree, so every protocol operation — chain
gathers, straggler masking, `w^{t,last}` scatters, Eq. 11/14 aggregation and
Eq. 12 quantization — is a single 2-D array op.

Layout: leaves are concatenated in pytree order along the last axis, each
leaf padded up to a multiple of ``LANES`` (= 128, the TPU lane width) so

  * every leaf occupies a whole number of 128-element rows, which lets the
    fused Pallas quantization kernel apply per-leaf (segment-wise) adaptive
    grids via per-row scale operands (see repro.kernels.quantize), and
  * a payload of B models reshapes to ``(B * rows_per_model, 128)`` with each
    row belonging to exactly one (model, leaf) segment.

Padding entries start at zero and stay exactly zero through the whole
protocol: gradients w.r.t. them vanish (``unflatten`` never reads them),
quantized diffs at zero are zero, and aggregation is linear.

`elect_writers` is the vectorized replacement for the seed engine's
per-chain ``lax.fori_loop``/``lax.cond`` scatter: it reproduces the
sequential tie-breaking semantics (the highest-index *active* chain visiting
a device in a step owns its `w^{t,last}` slot) with one scatter-max over
chain priorities; the winners' rows are then written in one pass
(`repro.kernels.rowmerge.merge_rows`).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "LANES",
    "FlatSpec",
    "make_flat_spec",
    "flatten_tree",
    "unflatten_tree",
    "elect_writers",
]

LANES = 128


@dataclasses.dataclass(frozen=True)
class FlatSpec:
    """Static codec between a model pytree and its padded flat layout.

    shapes/sizes describe the *single-model* leaves (no batch axes);
    ``offsets[l] : offsets[l] + sizes[l]`` is leaf l's live slice of the flat
    vector, inside its 128-aligned segment of ``padded_sizes[l]`` elements.
    """

    treedef: Any
    shapes: tuple
    sizes: tuple            # true element counts per leaf
    padded_sizes: tuple     # aligned up to a multiple of LANES
    offsets: tuple          # start of each leaf segment in the flat vector
    d: int                  # true total parameter count (wire accounting)
    d_pad: int              # flat vector length (multiple of LANES)

    @property
    def n_leaves(self) -> int:
        return len(self.shapes)

    @property
    def rows(self) -> int:
        """128-lane rows per flattened model."""
        return self.d_pad // LANES

    def row_leaf_ids(self) -> np.ndarray:
        """(rows,) int32: which leaf each 128-lane row belongs to."""
        ids = np.zeros(self.rows, dtype=np.int32)
        for l, (off, psize) in enumerate(zip(self.offsets, self.padded_sizes)):
            ids[off // LANES : (off + psize) // LANES] = l
        return ids


def make_flat_spec(template: Any) -> FlatSpec:
    """Build the codec from a single-model pytree (arrays or ShapeDtypeStructs)."""
    leaves, treedef = jax.tree_util.tree_flatten(template)
    shapes = tuple(tuple(l.shape) for l in leaves)
    sizes = tuple(int(np.prod(s)) if s else 1 for s in shapes)
    padded = tuple(-(-sz // LANES) * LANES for sz in sizes)
    offsets = tuple(int(o) for o in np.concatenate([[0], np.cumsum(padded)[:-1]]))
    return FlatSpec(
        treedef=treedef,
        shapes=shapes,
        sizes=sizes,
        padded_sizes=padded,
        offsets=offsets,
        d=int(sum(sizes)),
        d_pad=int(sum(padded)),
    )


def flatten_tree(tree: Any, spec: FlatSpec) -> jax.Array:
    """Pack a pytree with leaves of shape ``batch_shape + spec.shapes[l]``
    into a ``batch_shape + (d_pad,)`` matrix (zero padding between leaves)."""
    leaves = jax.tree_util.tree_leaves(tree)
    bshape = leaves[0].shape[: leaves[0].ndim - len(spec.shapes[0])]
    segs = []
    for leaf, size, psize in zip(leaves, spec.sizes, spec.padded_sizes):
        flat = jnp.reshape(leaf, bshape + (size,))
        pad = [(0, 0)] * len(bshape) + [(0, psize - size)]
        segs.append(jnp.pad(flat, pad))
    return jnp.concatenate(segs, axis=-1)


def unflatten_tree(flat: jax.Array, spec: FlatSpec) -> Any:
    """Inverse of :func:`flatten_tree`; drops the padding entries."""
    bshape = flat.shape[:-1]
    leaves = []
    for shape, size, off in zip(spec.shapes, spec.sizes, spec.offsets):
        seg = jax.lax.slice_in_dim(flat, off, off + size, axis=flat.ndim - 1)
        leaves.append(jnp.reshape(seg, bshape + shape))
    return jax.tree_util.tree_unflatten(spec.treedef, leaves)


def elect_writers(
    idx: jax.Array, mask: jax.Array, n: int
) -> tuple[jax.Array, jax.Array]:
    """Elect, per target row, the LAST active writer in sequence order.

    Returns ``(winner, wins)``: ``winner[j]`` is the index of the writer that
    owns row j (-1 if untouched) and ``wins[c]`` marks writers that own their
    row. One scatter-max over writer priorities (inactive writers carry
    priority -1 and can never win); winners are unique per row by
    construction.
    """
    m = idx.shape[0]
    prio = jnp.where(mask, jnp.arange(m, dtype=jnp.int32), -1)
    winner = (
        jnp.full((n,), -1, dtype=jnp.int32)
        .at[idx]
        .max(prio, mode="drop")
    )
    wins = (winner[idx] == jnp.arange(m, dtype=jnp.int32)) & mask
    return winner, wins
