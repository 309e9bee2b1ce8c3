"""Paged decode-attention Pallas kernel — K/V read through a block table.

The paged KV cache (serve/paging.py) is the paper's compressed-domain idea
applied to activations-over-time: instead of a dense ``(rows, cache_len, ...)``
slot sized for the worst case, each sequence owns ``ceil(len / page_size)``
fixed-size pages, and a per-row **block table** maps logical page j to a
physical page id — the CSC address-vector indirection of §IV, with pages in
the role of non-zero blocks. This kernel is the decode-attention consumer of
that layout: one query token per row attends to its whole history without the
history ever being gathered into a contiguous buffer.

Structure (same scalar-prefetch trick as the nnzb walk in bcsc_mlp.py):

* grid ``(B, max_pages)`` — rows parallel, pages sequential per row;
* the flattened block table and per-row lengths ride the scalar prefetch, so
  the K/V index maps pick the *physical* page ``bt[b, j]`` for logical page j
  (clamped into range — unallocated entries are skipped, no new DMA);
* online-softmax running ``(m, l, acc)`` state lives in fp32 VMEM scratch
  (the psum-SPad analogue, identical to local_attention.py) and merges page
  partials in any physical order;
* pages past a row's occupancy ``ceil(len/ps)`` are skipped with ``pl.when``
  — per row the kernel does real work on exactly ``pages_for(len)`` grid
  steps, the proxy scripts/perf_guard.py gates.

GQA is native: q carries (KV, R, D) per row, K/V pages carry (ps, KV, D);
scores reduce per kv-head. ``core.dataflow.attn_path`` decides when decode
dispatches here vs. the contiguous-ring path (models/decoding._attn_decode).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import dataflow

NEG_INF = -2.0e38


def row_work_steps(length, page_size: int):
    """The kernel's skip bound for one row: pages with real work (DMA+MACs).

    This is the SAME expression the kernel body evaluates for its
    ``pl.when(j < n_pages)`` guard (int or traced scalar) — the single
    source of truth, so a kernel-side change to the skip logic moves the
    cost proxy with it.
    """
    return (length + page_size - 1) // page_size


def work_steps(lengths, page_size: int) -> int:
    """Grid steps doing real work over a batch: Σ row_work_steps over rows.

    The wall-clock-free cost proxy benchmarks/sparse_decode.py records and
    scripts/perf_guard.py gates against the *independently* computed
    ``dataflow.pages_for`` bound (work ≤ ceil(len/ps) per row) and the
    padded (rows × max_pages) grid (strictly fewer steps on ragged rows).
    """
    return sum(int(row_work_steps(int(n), page_size)) for n in lengths)


def _paged_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, *rest,
                  page_size: int, max_pages: int, softcap: float,
                  quantized: bool):
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[b]
    n_pages = row_work_steps(length, page_size)

    @pl.when(j < n_pages)
    def _page():
        q = q_ref[0].astype(jnp.float32)                 # (KV, R, D)
        k = k_ref[0].astype(jnp.float32)                 # (ps, KV, D)
        v = v_ref[0].astype(jnp.float32)
        if quantized:
            # per-page dequant inside the online-softmax loop: the page's
            # (KV,) amax scales ride the same block-table index map as the
            # payload, so int8 pages never round-trip through a dense fp
            # buffer — the compressed-domain contract of the BCSC kernels
            # applied to KV-over-time
            k = k * (ks_ref[...] * (1.0 / 127.0))        # (1, KV, 1)
            v = v * (vs_ref[...] * (1.0 / 127.0))
        s = jnp.einsum("grd,tgd->grt", q, k,
                       preferred_element_type=jnp.float32)
        s = s * (1.0 / math.sqrt(q.shape[-1]))
        if softcap > 0.0:
            s = jnp.tanh(s / softcap) * softcap
        # logical token positions of this page; the tail page masks past len
        tpos = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, page_size), 2)
        s = jnp.where(tpos < length, s, NEG_INF)

        m_prev = m_ref[...]                              # (KV, R)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.where(s > NEG_INF / 2, jnp.exp(s - m_new[..., None]), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * corr[..., None] + jnp.einsum(
            "grt,tgd->grd", p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == max_pages - 1)
    def _done():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)[..., None]
                    ).astype(o_ref.dtype)


def paged_attention_raw(q, k_pool, v_pool, block_table, lengths, *,
                        k_scale=None, v_scale=None, softcap: float = 0.0,
                        out_dtype=jnp.float32, interpret: bool = False):
    """q (B,KV,R,D); k_pool/v_pool (P,ps,KV,D); block_table (B,MP) int32
    (physical page id, or -1 for unallocated); lengths (B,) int32 ≥ 1.

    Returns (B,KV,R,D) ``out_dtype``. Tokens of row b live at pool position
    (block_table[b, t // ps], t % ps) for t < lengths[b]; the kernel never
    reads past a row's occupancy, so unallocated table entries only need to
    be out of the ``pages_for(length)`` prefix.

    ``k_scale``/``v_scale`` (P, KV) fp32 switch on the int8 page format:
    pools hold symmetric int8 payloads and each page is dequantized by its
    own per-kv-head amax scale inside the page loop (scales are fetched
    through the same block-table index map as the payload).
    """
    B, KV, R, D = q.shape
    P, ps, KVp, Dp = k_pool.shape
    MP = block_table.shape[1]
    assert (KV, D) == (KVp, Dp), (q.shape, k_pool.shape)
    assert block_table.shape == (B, MP) and lengths.shape == (B,)
    quantized = k_scale is not None
    assert quantized == (v_scale is not None), "need both or neither scale"
    if quantized:
        assert k_scale.shape == (P, KV) and v_scale.shape == (P, KV), \
            (k_scale.shape, v_scale.shape, (P, KV))

    def kv_map(b, j, bt, lens):
        # physical page through the prefetched block table; clamp keeps the
        # DMA in range on skipped (unallocated / past-occupancy) steps
        return (jnp.clip(bt[b * MP + j], 0, P - 1), 0, 0, 0)

    def scale_map(b, j, bt, lens):
        return (jnp.clip(bt[b * MP + j], 0, P - 1), 0, 0)

    kernel = functools.partial(_paged_kernel, page_size=ps, max_pages=MP,
                               softcap=softcap, quantized=quantized)
    in_specs = [
        pl.BlockSpec((1, KV, R, D), lambda b, j, *s: (b, 0, 0, 0)),
        pl.BlockSpec((1, ps, KV, D), kv_map),
        pl.BlockSpec((1, ps, KV, D), kv_map),
    ]
    operands = [q, k_pool, v_pool]
    if quantized:
        # scales ride as (P, KV, 1): a (1, KV, 1) block spans the array's
        # last two dims, which the TPU tiling rule requires of a block
        # narrower than (8, 128), and it broadcasts along D in the kernel
        in_specs += [pl.BlockSpec((1, KV, 1), scale_map),
                     pl.BlockSpec((1, KV, 1), scale_map)]
        operands += [k_scale.reshape(P, KV, 1), v_scale.reshape(P, KV, 1)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, MP),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, KV, R, D), lambda b, j, *s: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((KV, R), jnp.float32),
            pltpu.VMEM((KV, R), jnp.float32),
            pltpu.VMEM((KV, R, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        name="paged_attention",
        out_shape=jax.ShapeDtypeStruct((B, KV, R, D), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(block_table.reshape(-1).astype(jnp.int32),
      lengths.astype(jnp.int32), *operands)
