"""Pallas TPU kernel: write a round's changed rows of the (n, d) client
matrix in one pass of whole (8, 128) tiles.

In the TPU's (8, 128) tiling of an (n, d) float32 matrix, row i is one
sublane of every tile of its 8-row group, so writing one row by itself reads
and rewrites all of its group's tiles. XLA's row scatter does exactly that,
once per update row. This kernel passes over the matrix once per column
block instead: it copies each row block, replaces or adds to its changed
rows from the set-row and add-row blocks (their whole row extent by C,
fetched once per column block), and writes the block back in place (the
matrix is aliased from input to output).

Grid: (column blocks, row blocks), every row block of the matrix: 8 rows on
TPU, the whole matrix in one block when interpreted. A scalar-prefetched
per-row source table steers each row: -1 keep; w < W take set row w;
W + a add row a to the old row. Rows past n in a partial last block keep
their -1 and are not written back.
"""
from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["GROUP", "LANES", "merge_rows_kernel_call"]

GROUP = 8            # f32 sublanes: the rows that share one (8, 128) tile
LANES = 128


def _merge_kernel(src_ref, mat_ref, set_ref, *rest, rows: int, n_set: int, set_minor: int):
    add_ref, out_ref = rest if len(rest) == 2 else (None, rest[0])
    out_ref[...] = mat_ref[...]
    base = pl.program_id(1) * rows

    def row(r, carry):
        s = src_ref[base + r]

        @pl.when((s >= 0) & (s < n_set))
        def _():
            out_ref[pl.ds(r, 1), :] = set_ref[s // set_minor, pl.ds(s % set_minor, 1), :]

        if add_ref is not None:
            @pl.when(s >= n_set)
            def _():
                out_ref[pl.ds(r, 1), :] = (mat_ref[pl.ds(r, 1), :]
                                           + add_ref[pl.ds(s - n_set, 1), :])
        return carry

    jax.lax.fori_loop(0, rows, row, 0)


def merge_rows_kernel_call(matrix: jax.Array, src: jax.Array, set_rows: jax.Array,
                           add_rows: jax.Array | None, *, rows: int, cols: int,
                           vmem_limit_bytes: int | None = None,
                           interpret: bool = False) -> jax.Array:
    """``matrix`` (n, d) f32, aliased to the output; ``src`` (ceil(n/rows)*rows,)
    int32 source table; ``set_rows`` (K, M, d); ``add_rows`` (A, d) or None;
    ``rows`` the row block (8, or n for one block); ``cols`` the column
    block, a multiple of 128 or d."""
    n, d = matrix.shape
    lead, minor = set_rows.shape[:2]
    in_specs = [pl.BlockSpec((rows, cols), lambda c, g, s: (g, c)),
                pl.BlockSpec((lead, minor, cols), lambda c, g, s: (0, 0, c))]
    operands = [matrix, set_rows]
    if add_rows is not None:
        in_specs.append(pl.BlockSpec((add_rows.shape[0], cols), lambda c, g, s: (0, c)))
        operands.append(add_rows)
    kernel = functools.partial(_merge_kernel, rows=rows, n_set=lead * minor,
                               set_minor=minor)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(pl.cdiv(d, cols), pl.cdiv(n, rows)),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((rows, cols), lambda c, g, s: (g, c)),
        ),
        out_shape=jax.ShapeDtypeStruct((n, d), matrix.dtype),
        # operand 1 is the matrix (after the prefetched source table)
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
        name="rowmerge",
    )(src, *operands)
