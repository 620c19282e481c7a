"""Block-sparse × dense GEMM (paper §III-C) and grouped matmul, TPU-native.

The paper stores A in BCSC and iterates block rows with CPU-core work queues.
On TPU the grid must be shape-static and output-stationary, so we adapt
(DESIGN.md §2):

  * BCSR storage flattened to a **work list** — one grid step per nonzero
    block, sorted row-major: ``blocks (nnzb, bm, bk)``, ``row_id``/``col_id``
    (nnzb,).
  * ``row_id``/``col_id`` are **scalar-prefetched** (SMEM) and drive the
    BlockSpec index maps — the TPU-idiomatic replacement for pointer chasing:
    the B tile is gathered by ``col_id[t]``, the C tile revisited while
    ``row_id`` stays constant and flushed exactly when it changes.
  * the fp32 VMEM accumulator is zeroed on the first work item of each row and
    written out on the last (the same first/last-visit pattern as BRGEMM's
    K loop).

Every block row must have ≥1 work item (the ops wrapper pads empty rows with
an all-zero dummy block) so that every C tile gets written.

``grouped_matmul`` reuses the identical scalar-prefetch machinery for MoE
expert computation (one expert id per row tile of the token matrix) — the
megablox pattern.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["block_spmm_pallas", "grouped_matmul_pallas", "densify_to_bcsr"]


def densify_to_bcsr(a_dense, bm: int, bk: int, *, pad_empty_rows: bool = True):
    """Convert a dense matrix to BCSR work-list storage (test/bench helper).

    Returns (blocks (nnzb, bm, bk), row_id, col_id) sorted row-major, with an
    all-zero dummy block appended for every empty block row when requested.
    """
    a = np.asarray(a_dense)
    m, k = a.shape
    assert m % bm == 0 and k % bk == 0
    nr, nc = m // bm, k // bk
    tiles = a.reshape(nr, bm, nc, bk).transpose(0, 2, 1, 3)
    nz = np.abs(tiles).sum(axis=(2, 3)) != 0
    blocks, rows, cols = [], [], []
    for r in range(nr):
        any_in_row = False
        for c in range(nc):
            if nz[r, c]:
                blocks.append(tiles[r, c])
                rows.append(r)
                cols.append(c)
                any_in_row = True
        if pad_empty_rows and not any_in_row:
            blocks.append(np.zeros((bm, bk), a.dtype))
            rows.append(r)
            cols.append(0)
    return (
        jnp.asarray(np.stack(blocks)),
        jnp.asarray(np.array(rows, np.int32)),
        jnp.asarray(np.array(cols, np.int32)),
    )


def block_spmm_pallas(
    blocks,
    row_id,
    col_id,
    b,
    *,
    nrows_b: int,
    bn: int = 128,
    out_dtype=None,
    interpret: bool = False,
):
    """C = A_sparse @ B.  ``blocks`` (nnzb,bm,bk) BCSR work list (row-major
    sorted, every row represented); ``b`` (K, N) dense."""
    nnzb, bm, bk = blocks.shape
    k, n = b.shape
    assert n % bn == 0
    out_dtype = out_dtype or b.dtype
    nb_n = n // bn

    def kernel(row_ref, col_ref, blocks_ref, b_ref, o_ref, acc_ref):
        t = pl.program_id(1)
        row = row_ref[t]
        prev_row = row_ref[jnp.maximum(t - 1, 0)]
        next_row = row_ref[jnp.minimum(t + 1, nnzb - 1)]
        first = jnp.logical_or(t == 0, row != prev_row)
        last = jnp.logical_or(t == nnzb - 1, row != next_row)

        @pl.when(first)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += jax.lax.dot_general(
            blocks_ref[0], b_ref[...],
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

        @pl.when(last)
        def _():
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nb_n, nnzb),
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda j, t, row_ref, col_ref: (t, 0, 0)),
            pl.BlockSpec((bk, bn), lambda j, t, row_ref, col_ref: (col_ref[t], j)),
        ],
        out_specs=pl.BlockSpec(
            (bm, bn), lambda j, t, row_ref, col_ref: (row_ref[t], j)
        ),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    fn = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nrows_b * bm, n), out_dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
    )
    return fn(row_id, col_id, blocks, b)


def grouped_matmul_pallas(
    x,
    group_id,
    w,
    *,
    bf: int = 128,
    out_dtype=None,
    interpret: bool = False,
):
    """Per-tile expert matmul (MoE): x (T, d) in bm-row tiles, ``group_id``
    (T//bm,) expert of each tile, w (E, d, f) → out (T, f).

    The whole ``d`` dim is kept in one VMEM block (document: d·bf·dtype must
    fit the VMEM budget — true for all assigned configs)."""
    t_rows, d = x.shape
    n_tiles = group_id.shape[0]
    bm = t_rows // n_tiles
    e, d2, f = w.shape
    assert d2 == d and f % bf == 0
    out_dtype = out_dtype or x.dtype

    def kernel(gid_ref, x_ref, w_ref, o_ref):
        o_ref[...] = jax.lax.dot_general(
            x_ref[...], w_ref[0],
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(o_ref.dtype)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_tiles, f // bf),
        in_specs=[
            pl.BlockSpec((bm, d), lambda t, j, gid_ref: (t, 0)),
            pl.BlockSpec((1, d, bf), lambda t, j, gid_ref: (gid_ref[t], 0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bf), lambda t, j, gid_ref: (t, j)),
    )
    fn = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t_rows, f), out_dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
    )
    return fn(group_id, x, w)
