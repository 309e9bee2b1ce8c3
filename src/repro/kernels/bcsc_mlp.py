"""Fused BCSC MLP megakernel — the whole sparse MLP in one ``pallas_call``.

Why one kernel (paper §III/§IV, FlexNN 2403.09026, S2TA 2107.07983): compressed
-domain wins evaporate if the operator chain round-trips intermediates through
the memory hierarchy. The two-call path (PR 1) runs up-projection and
down-projection as separate GEMV kernels with the (bm × d_ff) hidden activation
materialized in HBM between them — at decode shapes that round-trip plus the
extra kernel dispatches cost more than the zero-block skipping saves
(DESIGN.md §9). This kernel is the hierarchical-mesh answer: the hidden
activation lives in a VMEM scratch accumulator (the PE-cluster SPad analogue)
from the first up-projection MAC to the last down-projection drain and is
never written to HBM.

Layout: one sequential grid walks the concatenated BCSC payloads of all
projections — ``[wg | (wu) | wd]`` — in **chunks of C contiguous payload
blocks** per grid step. A chunk is processed as three small batched
contractions instead of C scalar-indexed block ops:

  row1h (C, nK)   one-hot of the chunk's block-row ids   ⎫ the paper's addr-
  col1h (C, nF)   one-hot of the chunk's block-col ids   ⎭ vector decode
  xg    = row1h · x-blocks          gather the C activation slices
  part  = xg ⊗ payload              C block MACs as ONE batched matmul
  dst  += col1h · part              scatter-add into the hidden scratch

This keeps the MXU fed with one (C·bk × bn)-scale contraction per step (the
one-hot decode costs C·nK MACs ≪ the C·bk·bn block MACs) and — on the CPU
interpret backend — collapses ~4·C per-block XLA ops into ~7 per chunk, which
is what lets the fused path beat the dense einsum chain at decode shapes.

Ragged skip: segment capacities PG/PU/PD are static (the padded stack shape)
but *occupancy* is dynamic — the actual per-layer block counts arrive as a
scalar-prefetched ``counts`` vector, so under ``lax.scan`` over stacked layers
each layer executes only its own non-zero chunks. A chunk wholly past its
segment's count is skipped with ``pl.when`` and its block-stream index map
clamps to the last real chunk (no new DMA, no MACs); pad blocks *inside* a
partial chunk are masked out of ``row1h`` (and carry zero payload anyway —
serve.sparse.pad_packed), so the skip granularity is one chunk.

Phase walk (col-major BCSC ⇒ each up block finishes one bn-slice of hidden):

  j ∈ [0, NG)        h_g += scatter(x · wg-chunk)
  j ∈ [NG, NG+NU)    h_u += scatter(x · wu-chunk)              (gated only)
  j == NG+NU         h_g = act(h_g) [* h_u]           — fused activation/gate
  j ∈ [NG+NU, +ND)   o_acc += scatter(h_g · wd-chunk)
  j == last          o_ref = o_acc                     — single drain to HBM

The activation row x rides along fully VMEM-resident (decode-shaped bm × K is
KBs), so chunks with mixed block-rows need no per-block x DMA. Empty block-
columns need no explicit zero blocks here (scratch is zero-initialized), but
the packed format keeps ``ensure_nonempty_cols`` coverage so the same arrays
still feed the two-call kernels for shapes where the fused scratch would not
fit VMEM (core.dataflow.mlp_path decides).

TPU caveats (interpret=True on this container): the id vectors are read from
the scalar-prefetch (SMEM) refs with a dynamic slice — on real TPU they could
ride a VMEM stream blocked like the payload instead; and bn=16 sub-lane
one-hot scatters want lane-width alignment for peak Mosaic lowering. The
VMEM-fit gate in core.dataflow keeps the bm·d_ff scratch within budget.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import dataflow
from repro.kernels.epilogue import fused_epilogue


# Total chunk count at/below which the single-grid-step (fully unrolled)
# variant is used: the whole payload rides VMEM-resident and the phase walk
# compiles to one straight-line dependency chain (no sequential grid).
UNROLL_CHUNKS_MAX = 8


def _pick_chunk(P: int) -> int:
    """Largest supported chunk dividing the padded capacity P (static).

    Packs are padded to multiples of dataflow.BCSC_CHUNK (8); the stream
    chunk doubles that when it divides, trading skip granularity for fewer
    grid steps (one chunk = one DMA + one batched contraction).
    """
    for c in (2 * dataflow.BCSC_CHUNK, dataflow.BCSC_CHUNK):
        if P % c == 0:
            return c
    return 1


def _chunk_accum(rows_ref, cols_ref, blk_ref, src, dst_ref, base, count,
                 C: int, bk: int, bn: int, n_src: int, n_dst: int):
    """One chunk of C payload blocks: gather → batched MAC → scatter-add."""
    dst_ref[...] += _chunk_part(rows_ref, cols_ref, blk_ref[...], src, base,
                                count, C, bk, bn, n_src, n_dst)


def _chunk_part(rows_ref, cols_ref, blk, src, base, count,
                C: int, bk: int, bn: int, n_src: int, n_dst: int):
    """One chunk's contribution as a (bm, n_dst·bn) value (pure).

    ``blk`` is the chunk's (C, bk, bn) payload value; ids are read from the
    scalar-prefetch refs at ``base``. Pad blocks (≥ count) are masked out of
    the row one-hot, so their contribution is exactly zero.
    """
    rows = rows_ref[pl.ds(base, C)]
    cols = cols_ref[pl.ds(base, C)]
    valid = (base + jnp.arange(C, dtype=jnp.int32)) < count
    row1h = jnp.where(valid[:, None],
                      rows[:, None] == jnp.arange(n_src)[None, :],
                      False).astype(src.dtype)                    # (C, nK)
    bm = src.shape[0]
    xg = jnp.einsum("cs,msb->cmb", row1h,
                    src.reshape(bm, n_src, bk))                   # gather
    part = jnp.einsum("cmb,cbn->cmn", xg, blk.astype(src.dtype),
                      preferred_element_type=jnp.float32)         # C MACs
    col1h = (cols[:, None] == jnp.arange(n_dst)[None, :]).astype(jnp.float32)
    return jnp.einsum("cd,cmn->mdn", col1h, part,
                      preferred_element_type=jnp.float32
                      ).reshape(bm, n_dst * bn)                   # scatter


def _mlp_kernel_unrolled(counts_ref, g_rows_ref, g_cols_ref, u_rows_ref,
                         u_cols_ref, d_rows_ref, d_cols_ref, x_ref, g_blk_ref,
                         u_blk_ref, d_blk_ref, o_ref, *, NG: int, NU: int,
                         ND: int, CG: int, CU: int, CD: int, bk: int, bn: int,
                         d_ff: int, n_out: int, activation, gated: bool,
                         hidden_dtype):
    """Single-grid-step variant for decode-scale payloads (few chunks total).

    The whole phase walk is straight-line code — no sequential grid, no
    scratch refs, the hidden lives in registers/VREGs — so the interpret
    backend (and XLA generally) fuses it into one dependency chain instead of
    a while loop. Ragged skip degrades gracefully: pad blocks are masked out
    of the one-hots (zero contribution); at these payload sizes the stream
    waste is < one chunk per segment. Large payloads take _mlp_kernel, where
    whole chunks are skipped with no DMA at all.
    """
    x = x_ref[...]
    K = x.shape[1]
    n_g, n_u, n_d = counts_ref[0], counts_ref[1], counts_ref[2]

    def phase(rows_ref, cols_ref, blk_ref, src, count, N, C, n_src, n_dst):
        acc = jnp.zeros((src.shape[0], n_dst * bn), jnp.float32)
        for c in range(N):
            acc += _chunk_part(rows_ref, cols_ref,
                               blk_ref[pl.ds(c * C, C)], src, c * C, count,
                               C, bk, bn, n_src, n_dst)
        return acc

    h = phase(g_rows_ref, g_cols_ref, g_blk_ref, x, n_g, NG, CG,
              K // bk, d_ff // bn)
    h = fused_epilogue(h, None, activation)
    if gated:
        h = h * phase(u_rows_ref, u_cols_ref, u_blk_ref, x, n_u, NU, CU,
                      K // bk, d_ff // bn)
    h = h.astype(hidden_dtype).astype(jnp.float32)   # match two-call rounding
    out = phase(d_rows_ref, d_cols_ref, d_blk_ref, h, n_d, ND, CD,
                d_ff // bk, n_out // bn)
    o_ref[...] = out.astype(o_ref.dtype)


def _mlp_kernel(counts_ref, g_rows_ref, g_cols_ref, u_rows_ref, u_cols_ref,
                d_rows_ref, d_cols_ref, x_ref, g_blk_ref, u_blk_ref, d_blk_ref,
                o_ref, h_ref, u_hid_ref, o_acc_ref, *, NG: int, NU: int,
                ND: int, CG: int, CU: int, CD: int, bk: int, bn: int,
                d_ff: int, n_out: int, activation, gated: bool, hidden_dtype):
    """Grid (m_tiles, NG+NU+ND) chunk steps. ``u_*`` refs None when ungated."""
    j = pl.program_id(1)
    n_g = counts_ref[0]
    n_u = counts_ref[1]
    n_d = counts_ref[2]
    K = x_ref.shape[1]

    @pl.when(j == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)
        o_acc_ref[...] = jnp.zeros_like(o_acc_ref)
        if gated:
            u_hid_ref[...] = jnp.zeros_like(u_hid_ref)

    @pl.when(jnp.logical_and(j < NG, j * CG < n_g))
    def _up_gate():
        _chunk_accum(g_rows_ref, g_cols_ref, g_blk_ref, x_ref[...], h_ref,
                     jnp.minimum(j, NG - 1) * CG, n_g, CG, bk, bn,
                     K // bk, d_ff // bn)

    if gated:
        @pl.when(jnp.logical_and(jnp.logical_and(j >= NG, j < NG + NU),
                                 (j - NG) * CU < n_u))
        def _up_lin():
            _chunk_accum(u_rows_ref, u_cols_ref, u_blk_ref, x_ref[...],
                         u_hid_ref, jnp.clip(j - NG, 0, NU - 1) * CU, n_u,
                         CU, bk, bn, K // bk, d_ff // bn)

    @pl.when(j == NG + NU)
    def _activate():
        h = fused_epilogue(h_ref[...], None, activation)
        if gated:
            h = h * u_hid_ref[...]
        # round to the streaming compute dtype (bf16 in serving) so the fused
        # hidden matches the dense/two-call paths bit-for-bit at the rounding
        # step; scratch storage stays fp32 (the psum SPad precision)
        h_ref[...] = h.astype(hidden_dtype).astype(jnp.float32)

    @pl.when(jnp.logical_and(j >= NG + NU, (j - (NG + NU)) * CD < n_d))
    def _down():
        _chunk_accum(d_rows_ref, d_cols_ref, d_blk_ref, h_ref[...], o_acc_ref,
                     jnp.clip(j - (NG + NU), 0, ND - 1) * CD, n_d,
                     CD, bk, bn, d_ff // bk, n_out // bn)

    @pl.when(j == NG + NU + ND - 1)
    def _drain():
        o_ref[...] = o_acc_ref[...].astype(o_ref.dtype)


def bcsc_mlp_raw(x, g_blocks, g_rows, g_cols, d_blocks, d_rows, d_cols,
                 counts, *, u_blocks=None, u_rows=None, u_cols=None,
                 d_ff: int, n_out: int, bm: int, activation=None,
                 out_dtype=jnp.float32, interpret: bool = False):
    """Fused sparse MLP: ``act(x·Wg) [* (x·Wu)] · Wd`` in one kernel.

    x (M,K) with M % bm == 0; *_blocks (P?,bk,bn) BCSC payloads (padded
    capacity P?, actual occupancy ``counts`` = int32 (3,) [n_g, n_u, n_d]);
    *_rows/*_cols (P?,) int32 with pad entries repeating the last real entry
    (serve.sparse.pad_packed) so pad blocks are numeric no-ops and clamped
    index maps stay DMA-idempotent. d_ff % bn == 0 (hidden width),
    n_out % bn == 0. Returns (M, n_out).

    The hidden activation exists only as VMEM scratch — the out_shape is the
    (M, n_out) result alone, which tests assert (no HBM aliasing).
    """
    M, K = x.shape
    PG, bk, bn = g_blocks.shape
    PD = d_blocks.shape[0]
    gated = u_blocks is not None
    PU = u_blocks.shape[0] if gated else 0
    assert M % bm == 0 and K % bk == 0, (M, K, bm, bk)
    assert d_ff % bn == 0 and d_ff % bk == 0 and n_out % bn == 0, (
        d_ff, n_out, bk, bn)
    nm = M // bm
    CG, CU, CD = _pick_chunk(PG), _pick_chunk(max(PU, 1)), _pick_chunk(PD)
    NG, NU, ND = PG // CG, (PU // CU if gated else 0), PD // CD
    # decode-scale payloads (few chunks) take the straight-line single-step
    # variant: whole payloads VMEM-resident, no sequential grid
    unrolled = (NG + NU + ND) <= UNROLL_CHUNKS_MAX

    def _blk_map(offset, N, C, count_idx):
        """Chunk index map: clamp to the segment's last *real* chunk so steps
        past the occupancy re-point at resident data (no DMA)."""
        def index_map(i, j, cnt, *scalars):
            last = jnp.maximum((cnt[count_idx] - 1) // C, 0)
            return (jnp.clip(j - offset, 0, jnp.minimum(last, N - 1)), 0, 0)
        return index_map

    in_specs = [
        # activation row: fully VMEM-resident per m-tile (decode bm·K is KBs)
        pl.BlockSpec((bm, K), lambda i, *s: (i, 0)),
        pl.BlockSpec((PG, bk, bn) if unrolled else (CG, bk, bn),
                     (lambda i, *s: (0, 0, 0)) if unrolled
                     else _blk_map(0, NG, CG, 0)),
    ]
    args = [g_rows, g_cols]
    tensor_args = [x, g_blocks]
    if gated:
        in_specs.append(
            pl.BlockSpec((PU, bk, bn) if unrolled else (CU, bk, bn),
                         (lambda i, *s: (0, 0, 0)) if unrolled
                         else _blk_map(NG, NU, CU, 1)))
        args += [u_rows, u_cols]
        tensor_args.append(u_blocks)
    else:
        # dummy u operands keep the kernel arity static; pinned to block 0,
        # never read (scalar (1,) vectors, one zero payload block)
        in_specs.append(pl.BlockSpec((1, bk, bn), lambda i, *s: (0, 0, 0)))
        args += [jnp.zeros((1,), jnp.int32), jnp.zeros((1,), jnp.int32)]
        tensor_args.append(jnp.zeros((1, bk, bn), x.dtype))
    in_specs.append(
        pl.BlockSpec((PD, bk, bn) if unrolled else (CD, bk, bn),
                     (lambda i, *s: (0, 0, 0)) if unrolled
                     else _blk_map(NG + NU, ND, CD, 2)))
    args += [d_rows, d_cols]
    tensor_args.append(d_blocks)

    common = dict(NG=NG, NU=NU, ND=ND, CG=CG, CU=CU, CD=CD, bk=bk, bn=bn,
                  d_ff=d_ff, n_out=n_out, activation=activation, gated=gated,
                  hidden_dtype=x.dtype)
    if unrolled:
        grid = (nm,)
        semantics = ("parallel",)
        scratch = []
        kernel = functools.partial(_mlp_kernel_unrolled, **common)
    else:
        grid = (nm, NG + NU + ND)
        semantics = ("parallel", "arbitrary")
        scratch = [pltpu.VMEM((bm, d_ff), jnp.float32)]
        if gated:
            scratch.append(pltpu.VMEM((bm, d_ff), jnp.float32))
        scratch.append(pltpu.VMEM((bm, n_out), jnp.float32))
        if gated:
            kernel = functools.partial(_mlp_kernel, **common)
        else:
            def kernel(counts_ref, gr, gc, ur, uc, dr, dc, x_ref, g_blk,
                       u_blk, d_blk, o_ref, h_ref, o_acc_ref):
                return _mlp_kernel(counts_ref, gr, gc, ur, uc, dr, dc, x_ref,
                                   g_blk, u_blk, d_blk, o_ref, h_ref, None,
                                   o_acc_ref, **common)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, n_out), lambda i, *s: (i, 0)),
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        name="bcsc_mlp",
        out_shape=jax.ShapeDtypeStruct((M, n_out), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics),
        interpret=interpret,
    )(counts, *args, *tensor_args)
