"""Block-CSC sparse matmul Pallas kernel — the Sparse PE (paper §IV) on TPU.

The paper's PE walks CSC-compressed weights (address/count/data vectors) and
*skips the cycles* of zero entries. A systolic MXU cannot skip per-scalar
cycles, so the TPU-native "skip" is structural (DESIGN.md §2): weights are
tiled into MXU-aligned (bk × bn) blocks, all-zero blocks are never fetched nor
multiplied.

Mechanism = the paper's address vector, verbatim: the grid has one step per
*non-zero* block (nnzb, not nbk·nbn); two scalar-prefetched vectors —
``row_ids`` (which K-block each payload block came from) and ``col_ids``
(which N-block it belongs to, the expanded CSC col_ptr) — drive the BlockSpec
index maps, exactly like the PE's addr SPad drives its weight SPad reads.
Runtime is proportional to nnzb: a 90%-block-sparse layer takes ~10% of the
dense grid steps. Weight sparsity is compile-time-known (paper Table III), so
the vectors are built on host at encode time.

Revisit contract: BCSC stores blocks column-major, so all payload blocks of one
output column are consecutive grid steps — output-tile revisits are contiguous
and the fp32 accumulate-in-place pattern is safe.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.sparsity import BCSCMatrix
from repro.kernels.epilogue import fused_epilogue


def _bcsc_kernel(row_ids_ref, col_ids_ref, x_ref, blk_ref, o_ref):
    """Grid (m_tiles, nnzb). One step = one non-zero weight block."""
    j = pl.program_id(1)
    col = col_ids_ref[j]
    prev = col_ids_ref[jnp.maximum(j - 1, 0)]
    first = jnp.logical_or(j == 0, col != prev)   # new output column segment

    partial = jnp.dot(x_ref[...], blk_ref[0],
                      preferred_element_type=jnp.float32)

    @pl.when(first)
    def _init():
        o_ref[...] = partial

    @pl.when(jnp.logical_not(first))
    def _accum():
        o_ref[...] += partial


def expand_col_ptr(col_ptr: np.ndarray) -> np.ndarray:
    """CSC address vector -> per-block column ids (host-side, compile time)."""
    cp = np.asarray(col_ptr)
    return np.repeat(np.arange(cp.size - 1, dtype=np.int32), np.diff(cp))


def ensure_nonempty_cols(m: BCSCMatrix) -> BCSCMatrix:
    """Insert one explicit zero block into every empty block-column.

    Mirrors the paper's repeated-address convention for all-zero columns
    (Fig. 16): every output tile must be visited at least once so the kernel
    initializes it. Host-side; weight sparsity is static.
    """
    cp = np.asarray(m.col_ptr)
    counts = np.diff(cp)
    if (counts > 0).all():
        return m
    blocks = np.asarray(m.blocks)
    row_ids = np.asarray(m.row_ids)
    bk, bn = m.block
    new_blocks, new_rows, new_cp = [], [], [0]
    zero = np.zeros((bk, bn), blocks.dtype)
    for c in range(counts.size):
        lo, hi = cp[c], cp[c + 1]
        if hi > lo:
            new_blocks.append(blocks[lo:hi])
            new_rows.append(row_ids[lo:hi])
        else:
            new_blocks.append(zero[None])
            new_rows.append(np.zeros((1,), np.int32))
        new_cp.append(new_cp[-1] + max(hi - lo, 1))
    return BCSCMatrix(jnp.asarray(np.concatenate(new_blocks)),
                      jnp.asarray(np.concatenate(new_rows).astype(np.int32)),
                      jnp.asarray(np.asarray(new_cp, np.int32)),
                      m.shape, m.block)


def bcsc_matmul_raw(x, blocks, row_ids, col_ids, *, n_out: int, bm: int,
                    out_dtype=jnp.float32, interpret: bool = False):
    """x (M,K) · BCSC(K,N) -> (M,N).

    blocks (nnzb,bk,bn); row_ids/col_ids (nnzb,) int32 with col_ids
    non-decreasing and covering every block-column at least once
    (ensure_nonempty_cols). M % bm == 0; K % bk == 0; n_out % bn == 0.
    """
    M, K = x.shape
    nnzb, bk, bn = blocks.shape
    assert M % bm == 0 and K % bk == 0 and n_out % bn == 0, (M, K, n_out)
    nm = M // bm

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nm, nnzb),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, rows, cols: (i, rows[j])),
            pl.BlockSpec((1, bk, bn), lambda i, j, rows, cols: (j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, rows, cols: (i, cols[j])),
    )
    return pl.pallas_call(
        _bcsc_kernel,
        grid_spec=grid_spec,
        name="bcsc_matmul",
        out_shape=jax.ShapeDtypeStruct((M, n_out), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(row_ids, col_ids, x, blocks)


# ------------------------------------------------------------ GEMV fast path
def _bcsc_gemv_kernel(row_ids_ref, col_ids_ref, x_ref, blk_ref, *rest,
                      nnzb: int, activation, has_bias: bool):
    """Grid (nnzb,): one step per non-zero block, single m-tile (M ≤ bm).

    Decode-shaped variant (DESIGN.md §2): instead of revisit-accumulating
    through ``o_ref`` the column partials build up in a fp32 VMEM scratch tile
    (the psum-SPad analogue), and the fused bias+activation epilogue fires on
    the last block of each output-column segment as the tile drains to HBM.
    """
    if has_bias:
        bias_ref, o_ref, acc_ref = rest
    else:
        o_ref, acc_ref = rest
        bias_ref = None
    j = pl.program_id(0)
    col = col_ids_ref[j]
    first = jnp.logical_or(j == 0, col != col_ids_ref[jnp.maximum(j - 1, 0)])
    last = jnp.logical_or(j == nnzb - 1,
                          col != col_ids_ref[jnp.minimum(j + 1, nnzb - 1)])

    @pl.when(first)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], blk_ref[0],
                            preferred_element_type=jnp.float32)

    @pl.when(last)
    def _flush():
        b = bias_ref[0] if has_bias else None
        o_ref[...] = fused_epilogue(acc_ref[...], b,
                                    activation).astype(o_ref.dtype)


def bcsc_gemv_raw(x, blocks, row_ids, col_ids, *, n_out: int, bm: int,
                  bias=None, activation=None, out_dtype=jnp.float32,
                  interpret: bool = False):
    """Skinny x (M,K) · BCSC(K,N) -> (M,N), M ≤ bm (padded by ops.py).

    Same index-vector contract as bcsc_matmul_raw (col_ids non-decreasing,
    every block-column covered). bias, if given, is (1, n_out). Runtime is one
    grid step per non-zero block — the batch-1 regime where weight-block
    skipping is the whole win (paper Table VI).
    """
    M, K = x.shape
    nnzb, bk, bn = blocks.shape
    assert M == bm and K % bk == 0 and n_out % bn == 0, (M, K, n_out, bm)
    has_bias = bias is not None

    in_specs = [
        pl.BlockSpec((bm, bk), lambda j, rows, cols: (0, rows[j])),
        pl.BlockSpec((1, bk, bn), lambda j, rows, cols: (j, 0, 0)),
    ]
    args = [row_ids, col_ids, x, blocks]
    if has_bias:
        in_specs.append(
            pl.BlockSpec((1, bn), lambda j, rows, cols: (0, cols[j])))
        args.append(bias)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nnzb,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda j, rows, cols: (0, cols[j])),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_bcsc_gemv_kernel, nnzb=nnzb,
                          activation=activation, has_bias=has_bias),
        grid_spec=grid_spec,
        name="bcsc_gemv",
        out_shape=jax.ShapeDtypeStruct((bm, n_out), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*args)
