"""Row-stationary dense matmul Pallas kernel (paper §II RS dataflow → TPU).

Hardware adaptation (DESIGN.md §2): the paper's PE keeps a small weight matrix
stationary in its SPad and streams iact windows past it, accumulating into a
psum SPad. On TPU the MXU has no per-scalar SPad; the stationarity that matters
is the *psum tile* — we hold a (bm × bn) fp32 accumulator in VMEM (the psum-SPad
analogue) across the whole K reduction while (bm × bk) activation tiles and
(bk × bn) weight tiles stream HBM→VMEM. Tile shapes come from
core.dataflow.rs_matmul_tiling, which enforces the VMEM-fit constraint
(the paper's Table-III SPad-fit check) and MXU alignment (multiples of 128).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.epilogue import fused_epilogue


def _rs_matmul_kernel(x_ref, w_ref, *rest, nk: int, activation, has_bias: bool):
    """Grid (m, n, k), k innermost: accumulate into the stationary psum tile.

    The fused bias+activation epilogue (kernels/epilogue.py) runs as the psum
    tile drains at k == nk-1 — shared with the bcsc_gemv decode kernel.
    """
    if has_bias:
        bias_ref, o_ref, acc_ref = rest
    else:
        o_ref, acc_ref = rest
        bias_ref = None
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _done():
        b = bias_ref[0] if has_bias else None
        o_ref[...] = fused_epilogue(acc_ref[...], b,
                                    activation).astype(o_ref.dtype)


def rs_matmul_raw(x, w, *, bm: int, bk: int, bn: int, bias=None,
                  activation=None, out_dtype=jnp.float32,
                  interpret: bool = False):
    """(M,K)·(K,N) -> (M,N). M % bm == K % bk == N % bn == 0 (pad in ops.py).

    bias, if given, is (1, N) and is added — with ``activation`` applied —
    inside the kernel's final k-step (no second pass over the output).
    """
    M, K = x.shape
    K2, N = w.shape
    assert K == K2, (x.shape, w.shape)
    assert M % bm == 0 and K % bk == 0 and N % bn == 0, (M, K, N, bm, bk, bn)
    nm, nn, nk = M // bm, N // bn, K // bk
    has_bias = bias is not None

    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
        pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
    ]
    args = [x, w]
    if has_bias:
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, k: (0, j)))
        args.append(bias)

    return pl.pallas_call(
        functools.partial(_rs_matmul_kernel, nk=nk, activation=activation,
                          has_bias=has_bias),
        grid=(nm, nn, nk),
        name="rs_matmul",
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*args)
