"""Paged decode-attention Pallas kernel — K/V read through a block table.

The paged KV cache (serve/paging.py) is the paper's compressed-domain idea
applied to activations-over-time: instead of a dense ``(rows, cache_len, ...)``
slot sized for the worst case, each sequence owns ``ceil(len / page_size)``
fixed-size pages, and a per-row **block table** maps logical page j to a
physical page id — the CSC address-vector indirection of §IV, with pages in
the role of non-zero blocks. This kernel is the decode-attention consumer of
that layout: one query token per row attends to its whole history without the
history ever being gathered into a contiguous buffer in HBM.

Structure — one grid step per row, a loop over **blocks of pages** inside it:

* grid ``(B,)``, in order; the flattened block table and per-row lengths
  ride the scalar prefetch. Row b walks ``row_blocks(len_b)`` blocks of
  ``pages_per_block`` logical pages each — a dynamic trip count, so nothing
  past a row's occupancy costs a step, a DMA or a product;
* the pools stay in HBM (``memory_space=pl.ANY``). Each block gathers its
  physical pages ``bt[b, j]`` with one async copy per page into a
  double-buffered VMEM block of ``pages_per_block`` pages: the next
  block's copies (or the next row's first block) start before the
  current block's math. Pages of the last block past the row's occupancy are
  not copied, and the tail is masked (``tpos < len``);
* per KV head the block runs two products over all of its tokens, and the
  online-softmax ``(m, l, acc)`` state in fp32 VMEM scratch (the psum-SPad
  analogue, identical to local_attention.py) merges block partials; the
  last block normalizes before its ``p·v``, as a softmax does;
* int8 pools keep their payloads int8 in VMEM: the per-(page, KV-head) amax
  scales are gathered through the block table outside the kernel, spread
  over the block's tokens, and applied to the scores (``(q·k)·s_k``) and to
  the probabilities before ``p·v`` — the same values as dequantizing every
  element, at a per-token instead of a per-element multiply;
* a row whose table starts unallocated (a free device row) holds no pages:
  it walks no block and reads zeros.

``pages_per_block`` follows the shapes (``pages_per_block()``): about
``BLOCK_TOKENS`` tokens, halved until two buffers of K and V fit
``VMEM_BUDGET``. ``row_blocks`` is the one skip bound: the kernel's trip
count and the scheduler's ``attn_blocks_*`` counters both evaluate it.

Pages are read as the pool is laid out (``heads_major``): token-major
``(ps, KV, D)`` when a token's heads fill whole 32-bit words, else as a
head-major ``(P, KV, ps, D)`` view, where each head's page is a contiguous
``(ps, D)`` slab — the layout XLA gives such a pool, so the view costs no
copy. GQA is native: q carries (KV, R, D) per row. ``core.dataflow.attn_path``
decides when decode dispatches here vs. the contiguous-ring path
(models/decoding._attn_decode).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e38
BLOCK_TOKENS = 1024             # tokens a block aims at
VMEM_BUDGET = 4 * 2**20         # bytes for the two K and two V block buffers


def pages_per_block(page_size: int, kv_heads: int, head_dim: int,
                    itemsize: int, max_pages: int) -> int:
    """Pages per block for the shapes a call brings: the largest power of
    two with at most ``BLOCK_TOKENS`` tokens whose double-buffered K and V
    blocks fit ``VMEM_BUDGET``, and no larger than ``max_pages`` needs."""
    ppb = 1 << max(0, (BLOCK_TOKENS // page_size).bit_length() - 1)
    while ppb > 1 and (4 * kv_heads * ppb * page_size * head_dim * itemsize
                       > VMEM_BUDGET):
        ppb //= 2
    return min(ppb, 1 << max(0, max_pages - 1).bit_length())


def heads_major(kv_heads: int, itemsize: int) -> bool:
    """Whether the kernel reads pages head-major, ``(KV, ps, D)``.

    A token's KV heads that fill whole 32-bit sublane words (``KV·itemsize``
    a multiple of 4) keep the cache's token-major ``(ps, KV, D)`` page, which
    is how XLA lays such a pool out; fewer heads than that cannot be sliced
    per token, and XLA lays such a pool out head-major already. Either way
    the kernel's view is the pool's own layout, and XLA adds no copy.
    """
    return (kv_heads * itemsize) % 4 != 0


def resident_pages(length, page_size: int, max_pages: int):
    """Pages a row of ``length`` tokens holds, at most ``max_pages``."""
    pages = (length + page_size - 1) // page_size
    return pages - (pages - max_pages) * (pages > max_pages)


def row_blocks(length, first_page, page_size: int, pages_per_block: int,
               max_pages: int):
    """The kernel's skip bound for one row: blocks with real work.

    ``resident_pages`` in blocks of ``pages_per_block``; none when the row's
    first table entry is unallocated. Plain arithmetic, so the kernel
    evaluates it on traced scalars and the host counters on ints or numpy
    arrays — one expression, and the two cannot drift apart.
    """
    pages = resident_pages(length, page_size, max_pages)
    return (pages + pages_per_block - 1) // pages_per_block * (first_page >= 0)


def _paged_kernel(bt_ref, len_ref, q_ref, k_hbm, v_hbm, *rest,
                  page_size: int, max_pages: int, ppb: int, softcap: float,
                  quantized: bool, head_major: bool):
    if quantized:
        (ks_ref, vs_ref, o_ref, kbuf, vbuf, sem, state,
         m_ref, l_ref, acc_ref) = rest
    else:
        o_ref, kbuf, vbuf, sem, state, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    nrows = pl.num_programs(0)
    n_pool = k_hbm.shape[0]
    ps, MP = page_size, max_pages
    T, D = ppb * ps, kbuf.shape[-1]
    KV = kbuf.shape[1] if head_major else kbuf.shape[3]
    SLOT, PREFETCHED = 0, 1             # state: current slot, row prefetched

    def n_pages(row):
        return resident_pages(len_ref[row], ps, MP)

    def n_blocks(row):
        return row_blocks(len_ref[row], bt_ref[row * MP], ps, ppb, MP)

    def for_pages(row, blk, slot, op):
        """``op`` each K and V page copy of block ``blk`` of ``row`` that
        the row holds: one loop over the block's resident pages."""
        def page(p, carry):
            phys = jnp.clip(bt_ref[row * MP + blk * ppb + p], 0, n_pool - 1)
            for i, (hbm, buf) in enumerate(((k_hbm, kbuf), (v_hbm, vbuf))):
                dst = buf.at[slot, :, pl.ds(pl.multiple_of(p * ps, ps), ps),
                             :] if head_major else buf.at[slot, p]
                op(pltpu.make_async_copy(hbm.at[phys], dst, sem.at[i, slot]))
            return carry

        n = jnp.minimum(n_pages(row) - blk * ppb, ppb)
        jax.lax.fori_loop(0, n, page, 0)

    def start(row, blk, slot):
        for_pages(row, blk, slot, lambda c: c.start())

    def wait(row, blk, slot):
        for_pages(row, blk, slot, lambda c: c.wait())

    @pl.when(b == 0)
    def _first_row():
        state[SLOT] = 0
        state[PREFETCHED] = -1
        if not quantized:
            # pages past a row's occupancy are never copied: keep what the
            # masked tail multiplies by p = 0 finite (int8 always is)
            kbuf[...] = jnp.zeros_like(kbuf)
            vbuf[...] = jnp.zeros_like(vbuf)

    nb = n_blocks(b)
    length = len_ref[b]
    limit = jnp.minimum(length, MP * ps)

    @pl.when((nb > 0) & (state[PREFETCHED] != b))
    def _first_block():
        start(b, 0, state[SLOT])

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    if quantized:
        # spreads a block's (KV, ppb) page scales over its T tokens
        tok = jax.lax.broadcasted_iota(jnp.int32, (ppb, T), 1)
        first = jax.lax.broadcasted_iota(jnp.int32, (ppb, T), 0) * ps
        spread = ((tok >= first) & (tok < first + ps)).astype(jnp.float32)

    def head(buf, slot, g):
        x = buf[slot, g] if head_major else buf[slot, :, :, g, :]
        return x.astype(jnp.float32).reshape(T, D)

    def block(i, carry):
        slot = state[SLOT]

        @pl.when(i + 1 < nb)
        def _next_block():
            start(b, i + 1, 1 - slot)

        nxt = jnp.minimum(b + 1, nrows - 1)

        @pl.when((i + 1 == nb) & (b + 1 < nrows) & (n_blocks(nxt) > 0))
        def _next_row():
            start(nxt, 0, 1 - slot)
            state[PREFETCHED] = nxt

        wait(b, i, slot)
        last = i + 1 == nb
        tpos = i * T + jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
        valid = tpos < limit
        if quantized:
            hi = jax.lax.Precision.HIGHEST
            k_tok = jnp.dot(ks_ref[0, i], spread, precision=hi,
                            preferred_element_type=jnp.float32)   # (KV, T)
            v_tok = jnp.dot(vs_ref[0, i], spread, precision=hi,
                            preferred_element_type=jnp.float32)
        for g in range(KV):
            q = q_ref[0, g].astype(jnp.float32)                  # (R, D)
            k = head(kbuf, slot, g)                              # (T, D)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            if quantized:
                s = s * k_tok[g:g + 1]
            s = s * (1.0 / math.sqrt(D))
            if softcap > 0.0:
                s = jnp.tanh(s / softcap) * softcap
            s = jnp.where(valid, s, NEG_INF)                     # (R, T)
            m_prev = m_ref[g]                                    # (R, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.where(s > NEG_INF / 2, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_new = l_ref[g] * corr + jnp.sum(p, axis=-1, keepdims=True)
            # the last block normalizes before its p·v, as a softmax does:
            # a one-block row computes (e / l)·v exactly like the
            # contiguous path's softmax-then-product
            d = jnp.where(last, l_new, 1.0)
            p = p / d
            if quantized:
                p = p * v_tok[g:g + 1]
            v = head(vbuf, slot, g)
            acc_ref[g] = acc_ref[g] * (corr / d) + jnp.dot(
                p, v, preferred_element_type=jnp.float32)
            l_ref[g] = l_new
            m_ref[g] = m_new
        state[SLOT] = 1 - slot
        return carry

    jax.lax.fori_loop(0, nb, block, 0)
    o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def paged_attention_raw(q, k_pool, v_pool, block_table, lengths, *,
                        k_scale=None, v_scale=None, softcap: float = 0.0,
                        out_dtype=jnp.float32, interpret: bool = False):
    """q (B,KV,R,D); k_pool/v_pool (P,ps,KV,D); block_table (B,MP) int32
    (physical page id, or -1 for unallocated); lengths (B,) int32 ≥ 1.

    Returns (B,KV,R,D) ``out_dtype``. Tokens of row b live at pool position
    (block_table[b, t // ps], t % ps) for t < lengths[b]; the kernel never
    reads past a row's occupancy, so unallocated table entries only need to
    be out of the ``pages_for(length)`` prefix. A row whose first entry is
    unallocated holds nothing and returns zeros.

    ``k_scale``/``v_scale`` (P, KV) fp32 switch on the int8 page format:
    pools hold symmetric int8 payloads and each page's per-kv-head amax
    scale is applied inside the block loop.
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
    ppb = pages_per_block(ps, KV, D, k_pool.dtype.itemsize, MP)
    NB = -(-MP // ppb)
    T = ppb * ps
    block_table = block_table.astype(jnp.int32)

    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [pl.BlockSpec((1, KV, R, D), lambda b, *_: (b, 0, 0, 0)),
                any_spec, any_spec]
    head_major = heads_major(KV, k_pool.dtype.itemsize)
    if head_major:
        operands = [q, jnp.swapaxes(k_pool, 1, 2), jnp.swapaxes(v_pool, 1, 2)]
        buf = (2, KV, T, D)
    else:
        operands = [q, k_pool, v_pool]
        buf = (2, ppb, ps, KV, D)
    if quantized:
        # each row's page scales, in block order: (B, NB, KV, ppb)
        pages = jnp.pad(jnp.clip(block_table, 0, P - 1),
                        ((0, 0), (0, NB * ppb - MP)))

        def by_block(scale):
            s = scale[pages] * (1.0 / 127.0)                # (B, NB·ppb, KV)
            return s.reshape(B, NB, ppb, KV).transpose(0, 1, 3, 2)

        spec = pl.BlockSpec((1, NB, KV, ppb), lambda b, *_: (b, 0, 0, 0))
        in_specs += [spec, spec]
        operands += [by_block(k_scale), by_block(v_scale)]
    kernel = functools.partial(_paged_kernel, page_size=ps, max_pages=MP,
                               ppb=ppb, softcap=softcap, quantized=quantized,
                               head_major=head_major)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, KV, R, D), lambda b, *_: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM(buf, k_pool.dtype),
            pltpu.VMEM(buf, v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((2,), jnp.int32),
            pltpu.VMEM((KV, R, 1), jnp.float32),
            pltpu.VMEM((KV, R, 1), jnp.float32),
            pltpu.VMEM((KV, R, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        name="paged_attention",
        out_shape=jax.ShapeDtypeStruct((B, KV, R, D), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(block_table.reshape(-1), lengths.astype(jnp.int32), *operands)
