"""Public jit'd wrappers around the Pallas kernels.

Each wrapper: picks tile shapes (core.dataflow — the SPad/VMEM-fit constraint),
pads inputs to tile multiples, dispatches the kernel, slices the result. On a
TPU backend the kernels compile to Mosaic; on the CPU backend (the tests) they
run with interpret=True, the Python interpreter of the kernel body. Any other
backend is refused: :func:`interpret_mode` is the one place that decides.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dataflow, plan as _plan
from repro.core.sparsity import BCSCMatrix
from repro.kernels import bcsc_matmul as _bcsc
from repro.kernels import bcsc_mlp as _bmlp
from repro.kernels import epilogue as _epi
from repro.kernels import local_attention as _swa
from repro.kernels import paged_attention as _paged
from repro.kernels import rs_matmul as _rs


def interpret_mode() -> bool:
    """False on a TPU backend (Mosaic), True on the CPU backend (Pallas
    interpreter); any other backend raises rather than fall back."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels compile for TPU or run interpreted on CPU; the "
        f"{backend!r} backend has neither path")


def _pad_to(x, m: int, axis: int):
    pad = (-x.shape[axis]) % m
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


# ------------------------------------------------------------------ rs_matmul
def rs_matmul(x, w, *, bias=None, activation: Optional[str] = None,
              out_dtype=jnp.float32, tiling=None,
              interpret: Optional[bool] = None):
    """Dense (M,K)·(K,N) via the row-stationary kernel. Any M,K,N (padded).

    bias (N,) and ``activation`` fuse into the kernel's accumulator-flush
    epilogue (kernels/epilogue.py) — no second pass over the output.
    """
    interpret = interpret_mode() if interpret is None else interpret
    M, K = x.shape
    _, N = w.shape
    t = tiling or dataflow.rs_matmul_tiling(M, K, N, x.dtype.itemsize)
    assert t.fits(), t                       # the Table-III SPad-fit gate
    xp = _pad_to(_pad_to(x, t.bm, 0), t.bk, 1)
    wp = _pad_to(_pad_to(w, t.bk, 0), t.bn, 1)
    bp = None if bias is None else _pad_to(bias.reshape(1, N), t.bn, 1)
    out = _rs.rs_matmul_raw(xp, wp, bm=t.bm, bk=t.bk, bn=t.bn, bias=bp,
                            activation=activation, out_dtype=out_dtype,
                            interpret=interpret)
    return out[:M, :N]


# ---------------------------------------------------------------- bcsc_matmul
def prepare_bcsc(m: BCSCMatrix):
    """Host-side (compile-time) index-vector prep: non-empty columns + col ids.

    Returns (blocks, row_ids, col_ids, n_out) ready for bcsc_matmul.
    """
    m = _bcsc.ensure_nonempty_cols(m)
    col_ids = _bcsc.expand_col_ptr(np.asarray(m.col_ptr))
    return (m.blocks, m.row_ids, jnp.asarray(col_ids), m.shape[1])


def _bcsc_apply(x, blocks, row_ids, col_ids, *, n_out: int, bm: int,
                bias, activation, out_dtype, interpret):
    """Shared GEMV/GEMM dispatch over prepared BCSC vectors.

    The route/tile come from the active ServePlan when a serving engine has
    one activated (core.plan.route_matmul/tile_m), else from the
    core.dataflow rule — the same resolved crossover either way."""
    M = x.shape[0]
    if bm <= 0:
        bm = _plan.tile_m(M)
    xp = _pad_to(x, bm, 0)
    bp = None if bias is None else _pad_to(bias.reshape(1, n_out),
                                           blocks.shape[2], 1)
    if _plan.route_matmul(M) == "gemv" and bm == _plan.gemv_bm():
        out = _bcsc.bcsc_gemv_raw(xp, blocks.astype(x.dtype), row_ids,
                                  col_ids, n_out=n_out, bm=bm, bias=bp,
                                  activation=activation, out_dtype=out_dtype,
                                  interpret=interpret)
        return out[:M]
    out = _bcsc.bcsc_matmul_raw(xp, blocks.astype(x.dtype), row_ids, col_ids,
                                n_out=n_out, bm=bm, out_dtype=jnp.float32,
                                interpret=interpret)
    if bias is not None or activation not in (None, "none"):
        # GEMM path keeps the revisit-accumulate kernel; epilogue applies as a
        # jnp post-op through the same shared definition (numerics identical).
        out = _epi.fused_epilogue(out, bp, activation)
    return out[:M].astype(out_dtype)


def bcsc_matmul(x, m: BCSCMatrix, *, bm: int = 0, bias=None,
                activation: Optional[str] = None, out_dtype=jnp.float32,
                interpret: Optional[bool] = None):
    """Sparse (M,K)·BCSC(K,N) -> (M,N); skips zero weight blocks entirely.

    Dispatches automatically on M (core.dataflow.matmul_path): decode-shaped
    M ≤ GEMV_M_MAX takes the scratch-accumulator GEMV kernel, larger M the
    revisit-accumulate GEMM kernel. Pass ``bm`` to force a GEMM tile.
    """
    interpret = interpret_mode() if interpret is None else interpret
    blocks, row_ids, col_ids, n_out = prepare_bcsc(m)
    assert x.shape[1] == m.shape[0], (x.shape, m.shape)
    return _bcsc_apply(x, blocks, row_ids, col_ids, n_out=n_out, bm=bm,
                       bias=bias, activation=activation, out_dtype=out_dtype,
                       interpret=interpret)


def bcsc_gemv(x, m: BCSCMatrix, *, bias=None,
              activation: Optional[str] = None, out_dtype=jnp.float32,
              interpret: Optional[bool] = None):
    """Decode fast path: skinny (M≤8,K)·BCSC(K,N) -> (M,N) via the GEMV kernel."""
    M = x.shape[0]
    assert M <= dataflow.GEMV_M_MAX, \
        f"bcsc_gemv is the M<={dataflow.GEMV_M_MAX} decode path, got M={M}"
    return bcsc_matmul(x, m, bias=bias, activation=activation,
                       out_dtype=out_dtype, interpret=interpret)


def is_packed(w) -> bool:
    """True if a params leaf-group is a BCSC-packed weight dict — the
    {blocks, row_ids, col_ids} contract consumed by bcsc_apply_packed
    (produced by serve.sparse.pack_weight)."""
    return isinstance(w, dict) and "blocks" in w and "col_ids" in w


def bcsc_apply_packed(x, packed, *, n_out: int, bias=None,
                      activation: Optional[str] = None,
                      out_dtype=jnp.float32,
                      interpret: Optional[bool] = None):
    """Jit-friendly entry: (M,K) · packed BCSC dict -> (M,N).

    ``packed`` is serve.sparse.pack_weight's dict of plain arrays
    {blocks (nnzb,bk,bn), row_ids (nnzb,), col_ids (nnzb,)} — traversable as a
    params pytree leaf group (stacks under lax.scan, no host-side prep at
    trace time). n_out must be static (callers derive it from the config).
    """
    interpret = interpret_mode() if interpret is None else interpret
    return _bcsc_apply(x, packed["blocks"], packed["row_ids"],
                       packed["col_ids"], n_out=n_out, bm=0, bias=bias,
                       activation=activation, out_dtype=out_dtype,
                       interpret=interpret)


def packed_nnzb(packed) -> jnp.ndarray:
    """Actual (un-padded) block count of a packed weight, int32 scalar.

    Ragged-aware packs (serve.sparse ≥ PR 2) carry ``nnzb``; legacy packs
    fall back to the padded payload length (every block treated as real).
    """
    n = packed.get("nnzb")
    if n is None:
        return jnp.int32(packed["blocks"].shape[0])
    return n.astype(jnp.int32).reshape(())


def bcsc_mlp_packed(x, gate_packed, up_packed, down_packed, *, d_ff: int,
                    n_out: int, activation: Optional[str] = None,
                    counts=None, out_dtype=jnp.float32,
                    interpret: Optional[bool] = None):
    """Fused sparse MLP megakernel over packed BCSC dicts (one pallas_call).

    ``gate_packed``/``down_packed`` are serve.sparse packed dicts for the
    gate/up-projection and down-projection; ``up_packed`` is the second
    (linear) up-projection for gated MLPs, or None. The hidden activation
    stays in VMEM scratch; per-layer actual nnzb rides the prefetched
    ``counts`` vector so padded stack blocks are skipped (no DMA, no MACs).
    ``counts`` is the pack-time-prepared (3,) int32 [n_g, n_u, n_d]
    (serve.sparse stores it as ``_bcsc_counts``); assembled here when absent.
    Callers should gate on ``core.dataflow.mlp_path(...) == 'fused'``.
    """
    interpret = interpret_mode() if interpret is None else interpret
    M = x.shape[0]
    bm = _plan.tile_m(M)
    xp = _pad_to(x, bm, 0)
    gated = up_packed is not None
    if counts is None:
        counts = jnp.stack([
            packed_nnzb(gate_packed),
            packed_nnzb(up_packed) if gated else jnp.int32(0),
            packed_nnzb(down_packed),
        ])
    kw = {}
    if gated:
        kw = dict(u_blocks=up_packed["blocks"].astype(x.dtype),
                  u_rows=up_packed["row_ids"], u_cols=up_packed["col_ids"])
    out = _bmlp.bcsc_mlp_raw(
        xp, gate_packed["blocks"].astype(x.dtype), gate_packed["row_ids"],
        gate_packed["col_ids"], down_packed["blocks"].astype(x.dtype),
        down_packed["row_ids"], down_packed["col_ids"], counts,
        d_ff=d_ff, n_out=n_out, bm=bm, activation=activation,
        out_dtype=out_dtype, interpret=interpret, **kw)
    return out[:M]


# ------------------------------------------------------- paged attention
def paged_attention(q, k_pool, v_pool, block_table, lengths, *,
                    k_scale=None, v_scale=None, softcap: float = 0.0,
                    interpret: Optional[bool] = None):
    """Decode attention against a paged KV pool through a block table.

    q (B,1,H,D) — the decode-step query layout of layers.decode_attention;
    k_pool/v_pool (P, page_size, KV, D); block_table (B, max_pages) int32
    (-1 = unallocated); lengths (B,) int32 valid tokens per row. Returns
    (B,1,H,D) fp32. Dispatch between this and the contiguous-ring path is
    core.dataflow.attn_path's call (occupancy rule).

    int8 pools (core.dataflow.kv_quant_path) pass their per-(page, kv-head)
    amax scales as ``k_scale``/``v_scale`` (P, KV) fp32; the kernel
    dequantizes each page inside its online-softmax loop.
    """
    interpret = interpret_mode() if interpret is None else interpret
    B, _, H, D = q.shape
    KV = k_pool.shape[2]
    R = H // KV
    out = _paged.paged_attention_raw(
        q.reshape(B, KV, R, D), k_pool, v_pool, block_table, lengths,
        k_scale=k_scale, v_scale=v_scale, softcap=softcap,
        interpret=interpret)
    return out.reshape(B, 1, H, D)


# -------------------------------------------------- sliding-window attention
def sliding_window_attention(q, k, v, *, window: int, softcap: float = 0.0,
                             bq: int = 128, bkv: int = 128,
                             interpret: Optional[bool] = None):
    """q (B,S,H,D); k,v (B,S,KV,D) -> (B,S,H,D) fp32. Any S (padded)."""
    interpret = interpret_mode() if interpret is None else interpret
    B, S, H, D = q.shape
    bq = min(bq, max(8, S))
    bkv = min(bkv, max(8, S))
    qt = _pad_to(jnp.moveaxis(q, 2, 1), bq, 2)       # (B,H,Sp,D)
    kt = _pad_to(jnp.moveaxis(k, 2, 1), bkv, 2)      # (B,KV,Sp,D)
    vt = _pad_to(jnp.moveaxis(v, 2, 1), bkv, 2)
    Sp = max(qt.shape[2], kt.shape[2])
    qt = _pad_to(qt, Sp, 2)
    kt = _pad_to(kt, Sp, 2)
    vt = _pad_to(vt, Sp, 2)
    out = _swa.sliding_window_attention_raw(
        qt, kt, vt, window=window, bq=bq, bkv=bkv, softcap=softcap,
        interpret=interpret)
    return jnp.moveaxis(out[:, :, :S], 1, 2)         # (B,S,H,D)


def flash_attention(q, k, v, *, softcap: float = 0.0, bq: int = 128,
                    bkv: int = 128, interpret: Optional[bool] = None):
    """Full causal attention = sliding window with window = S."""
    return sliding_window_attention(q, k, v, window=q.shape[1],
                                    softcap=softcap, bq=bq, bkv=bkv,
                                    interpret=interpret)
