"""Paged KV decode attention (ISSUE 3): the Pallas block-table kernel vs the
gather-then-softmax oracle and the contiguous-ring reference, across ragged
lengths, page boundaries, GQA/softcap, and multi-codebook configs."""
import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis_compat import given, settings, st

from repro.configs import get_config
from repro.core import dataflow
from repro.kernels import ops, ref
paged = importlib.import_module("repro.kernels.paged_attention")
from repro.models import decoding, layers, transformer as tfm
from repro.serve.paging import PageAllocator


def _paged_case(lengths, page_size, KV=2, R=2, D=16, seed=0, dtype=jnp.float32,
                max_pages=None, free_rows=(), quant=False):
    """Random pools + a permuted block table covering ``lengths``; rows in
    ``free_rows`` get an all -1 table. ``quant`` makes int8 pools and
    returns their (P, KV) scales too."""
    rng = np.random.default_rng(seed)
    B = len(lengths)
    MP = max_pages or max(dataflow.pages_for(n, page_size) for n in lengths)
    P = sum(dataflow.pages_for(n, page_size) for n in lengths) + 1
    q = jnp.asarray(rng.standard_normal((B, KV, R, D)), dtype)
    if quant:
        kp, vp = (jnp.asarray(rng.integers(-127, 128, (P, page_size, KV, D)),
                              jnp.int8) for _ in range(2))
    else:
        kp = jnp.asarray(rng.standard_normal((P, page_size, KV, D)), dtype)
        vp = jnp.asarray(rng.standard_normal((P, page_size, KV, D)), dtype)
    bt = np.full((B, MP), -1, np.int32)
    perm = rng.permutation(P)        # physical pages deliberately non-contiguous
    i = 0
    for b, n in enumerate(lengths):
        if b in free_rows:
            continue
        for j in range(dataflow.pages_for(n, page_size)):
            bt[b, j] = perm[i]
            i += 1
    case = (q, kp, vp, jnp.asarray(bt),
            jnp.asarray(np.asarray(lengths, np.int32)))
    if quant:
        case += tuple(jnp.asarray(rng.uniform(0.5, 3.0, (P, KV)), jnp.float32)
                      for _ in range(2))
    return case


# ------------------------------------------------------------------- kernel
@pytest.mark.parametrize("page_size", [4, 8])
@pytest.mark.parametrize("softcap", [0.0, 30.0])
def test_paged_kernel_matches_oracle_ragged(page_size, softcap):
    """Ragged lengths hitting len % ps in {0, 1, ps-1} plus mid-page."""
    lengths = [page_size, page_size + 1, 3 * page_size - 1, 2 * page_size + 2]
    q, kp, vp, bt, lens = _paged_case(lengths, page_size)
    B, KV, R, D = q.shape
    out = ops.paged_attention(q.reshape(B, 1, KV * R, D), kp, vp, bt, lens,
                              softcap=softcap)
    expect = ref.paged_attention_ref(q, kp, vp, bt, lens, softcap=softcap
                                     ).reshape(B, 1, KV * R, D)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-5, atol=1e-5)


def test_paged_kernel_single_page_and_full_table():
    """Boundary grids: one page total, and every table entry allocated."""
    for lengths in ([3], [8, 8]):
        q, kp, vp, bt, lens = _paged_case(lengths, 8, seed=3)
        B, KV, R, D = q.shape
        out = ops.paged_attention(q.reshape(B, 1, KV * R, D), kp, vp, bt, lens)
        expect = ref.paged_attention_ref(q, kp, vp, bt, lens
                                         ).reshape(B, 1, KV * R, D)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=1e-5, atol=1e-5)


def test_paged_kernel_matches_contiguous_decode_attention():
    """Paged read == layers.decode_attention over the same (scattered) KV."""
    cfg = get_config("qwen2.5-3b-reduced")
    rng = np.random.default_rng(1)
    B, KV, H, D = 3, cfg.num_kv_heads, cfg.num_heads, cfg.head_dim
    cache_len, ps = 32, 8
    MP = cache_len // ps
    lengths = np.asarray([4, 8, 19], np.int32)
    q = jnp.asarray(rng.standard_normal((B, 1, H, D)), jnp.float32)
    k_rows = jnp.asarray(rng.standard_normal((B, cache_len, KV, D)), jnp.float32)
    v_rows = jnp.asarray(rng.standard_normal((B, cache_len, KV, D)), jnp.float32)
    mask = jnp.arange(cache_len)[None, :] < jnp.asarray(lengths)[:, None]
    ctx_ref = layers.decode_attention(q, k_rows, v_rows, mask, cfg)

    bt = np.full((B, MP), -1, np.int32)
    nxt = 0
    for b, n in enumerate(lengths):
        for j in range(dataflow.pages_for(int(n), ps)):
            bt[b, j] = nxt
            nxt += 1
    pool = jnp.zeros((nxt + 1, ps, KV, D), jnp.float32)
    pk = decoding.scatter_rows_to_pages(pool, k_rows, jnp.asarray(bt),
                                        jnp.asarray(lengths))
    pv = decoding.scatter_rows_to_pages(pool, v_rows, jnp.asarray(bt),
                                        jnp.asarray(lengths))
    ctx_pg = ops.paged_attention(q, pk, pv, jnp.asarray(bt),
                                 jnp.asarray(lengths))
    # decode_attention rounds its fp32 context to the compute dtype (bf16)
    # on return; the kernel output must round to the identical values
    np.testing.assert_array_equal(
        np.asarray(ctx_pg.astype(ctx_ref.dtype), np.float32),
        np.asarray(ctx_ref, np.float32))


def test_paged_kernel_work_steps_proxy():
    """The skip bound, in blocks: a row walks ceil(ceil(len/ps) / ppb)
    blocks, a row without pages none, and ragged rows walk strictly fewer
    blocks than the padded (rows, max_pages / ppb) grid."""
    ps, ppb, MP = 8, 2, 6
    lengths = np.asarray([1, 8, 9, 17, 40])
    blocks = paged.row_blocks(lengths, 0, ps, ppb, MP)
    np.testing.assert_array_equal(blocks, [1, 1, 1, 2, 3])
    np.testing.assert_array_equal(
        blocks, [-(-dataflow.pages_for(n, ps) // ppb) for n in lengths])
    assert blocks.sum() < len(lengths) * -(-MP // ppb)
    # a free row (table starts unallocated) walks nothing; lengths past
    # the table clamp to max_pages
    assert paged.row_blocks(40, -1, ps, ppb, MP) == 0
    assert paged.row_blocks(10_000, 3, ps, ppb, MP) == -(-MP // ppb)
    # one expression on ints, numpy arrays and traced scalars alike
    assert int(jax.jit(lambda n: paged.row_blocks(n, jnp.int32(0), ps, ppb,
                                                  MP))(jnp.int32(17))) == 2


@pytest.mark.parametrize("block_pages", [2, 4])
@pytest.mark.parametrize("softcap", [0.0, 30.0])
@pytest.mark.parametrize("quant", [False, True])
@pytest.mark.parametrize("KV", [1, 2, 8])
def test_paged_kernel_block_edges(monkeypatch, KV, quant, softcap,
                                  block_pages):
    """Blocks of pages: lengths at a block edge and ±1, a max_pages that is
    not a multiple of the block, -1 entries past occupancy, and a free row
    (table all -1) that reads zeros; fp and int8 pools, softcap on and off,
    KV 1, 2 and 8."""
    ps = 4
    T = block_pages * ps
    monkeypatch.setattr(paged, "BLOCK_TOKENS", T)
    lengths = [T - 1, T, T + 1, 2 * T, 2 * T + 1, T]
    MP = 2 * block_pages + 1
    assert paged.pages_per_block(ps, KV, 16, 4, MP) == block_pages
    case = _paged_case(lengths, ps, KV=KV, seed=KV, max_pages=MP,
                       free_rows=(5,), quant=quant)
    q, kp, vp, bt, lens = case[:5]
    scales = dict(zip(("k_scale", "v_scale"), case[5:]))
    out = paged.paged_attention_raw(q, kp, vp, bt, lens, softcap=softcap,
                                    interpret=True, **scales)
    expect = ref.paged_attention_ref(q, kp, vp, bt, lens, softcap=softcap,
                                     **scales)
    np.testing.assert_allclose(np.asarray(out[:5]), np.asarray(expect[:5]),
                               rtol=1e-5, atol=1e-5)
    assert not np.asarray(out[5]).any()


def test_pages_per_block_follows_shapes():
    """~1024 tokens a block, halved until two K and two V buffers fit the
    VMEM budget, capped by what max_pages needs; pages are read head-major
    only where a token's heads do not fill a 32-bit word."""
    ppb = paged.pages_per_block
    assert ppb(64, 2, 128, 1, 64) == 16           # qwen2.5-3b, int8 pool
    assert ppb(64, 8, 128, 1, 208) == 16          # mistral-nemo, int8 pool
    assert ppb(64, 8, 128, 2, 208) == 8           # bf16 pool
    assert ppb(64, 8, 128, 4, 208) == 4           # fp32 pool
    assert ppb(48, 2, 128, 1, 64) == 16           # power of two below 1024/48
    assert ppb(4, 2, 16, 4, 3) == 4               # no larger than the table
    assert ppb(64, 2, 128, 1, 1) == 1
    for ps, kv, d, item, mp in [(64, 2, 128, 1, 64), (64, 8, 128, 2, 208)]:
        n = ppb(ps, kv, d, item, mp)
        assert n & (n - 1) == 0
        assert 4 * kv * n * ps * d * item <= paged.VMEM_BUDGET
    assert paged.heads_major(2, 1) and paged.heads_major(1, 1)
    assert paged.heads_major(1, 2)
    assert not paged.heads_major(8, 1) and not paged.heads_major(4, 1)
    assert not paged.heads_major(2, 2) and not paged.heads_major(1, 4)


@settings(max_examples=20, deadline=None)
@given(st.lists(st.integers(min_value=1, max_value=40), min_size=1,
                max_size=4),
       st.sampled_from([4, 8]))
def test_paged_kernel_property_ragged(lengths, page_size):
    """Property: kernel == oracle for arbitrary ragged lengths/page sizes."""
    q, kp, vp, bt, lens = _paged_case(lengths, page_size, seed=7)
    B, KV, R, D = q.shape
    out = ops.paged_attention(q.reshape(B, 1, KV * R, D), kp, vp, bt, lens)
    expect = ref.paged_attention_ref(q, kp, vp, bt, lens
                                     ).reshape(B, 1, KV * R, D)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-5, atol=1e-5)


# ------------------------------------------------------ model-level routing
def _paged_cache_from_prefill(cfg, row_cache, bt, lengths, rows, cache_len,
                              num_pages, page_size):
    """Scatter a prefill(-batched) row cache into a fresh paged cache."""
    pc = decoding.init_paged_cache(cfg, rows, cache_len, num_pages, page_size)

    def merge(c_entry, row_entry, stacked):
        if decoding.is_paged_entry(c_entry):
            def scat(pool, rows_kv):
                return decoding.scatter_rows_to_pages(pool, rows_kv, bt,
                                                      lengths)
            f = jax.vmap(scat) if stacked else scat
            return {"pk": f(c_entry["pk"], row_entry["k"]),
                    "pv": f(c_entry["pv"], row_entry["v"])}
        return row_entry

    out = {}
    for part in ("blocks", "rem"):
        if part in pc:
            out[part] = {k: merge(pc[part][k], row_cache[part][k],
                                  stacked=(part == "blocks"))
                         for k in pc[part]}
    return out


@pytest.mark.parametrize("arch", ["qwen2.5-3b-reduced", "gemma2-2b-reduced"])
def test_serve_step_paged_matches_contiguous(arch):
    """serve_step through the paged route is bit-identical to the contiguous
    route (global layers paged; gemma2's local layers stay ring either way)."""
    cfg = get_config(arch)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    rows, cache_len, ps = 2, 32, 8
    MP = cache_len // ps
    prompts = [[5, 6, 7], [9, 8, 7, 6, 5, 4]]
    S = max(len(p) for p in prompts)
    toks = np.zeros((rows, S), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
    lengths = jnp.asarray([len(p) for p in prompts], jnp.int32)
    lb, cb = decoding.prefill_batched(params, jnp.asarray(toks), lengths,
                                      cfg, cache_len)

    pager = PageAllocator(rows * MP, ps)
    for i, p in enumerate(prompts):
        assert pager.ensure(i, len(p) + 2)
    bt = jnp.asarray(pager.block_table_rows([0, 1], MP))
    paged = _paged_cache_from_prefill(cfg, cb, bt, lengths, rows, cache_len,
                                      rows * MP, ps)
    nxt = jnp.argmax(lb[:, -1], -1)[:, None]
    pos = lengths
    l_ref, c_ref = decoding.serve_step(params, cb, nxt, pos, cfg)
    l_pg, c_pg = decoding.serve_step(params, paged, nxt, pos, cfg,
                                     block_table=bt)
    np.testing.assert_array_equal(np.asarray(l_ref), np.asarray(l_pg))
    # second step exercises the decode-time page write
    nxt2 = jnp.argmax(l_ref[:, -1], -1)[:, None]
    l_ref2, _ = decoding.serve_step(params, c_ref, nxt2, pos + 1, cfg)
    l_pg2, _ = decoding.serve_step(params, c_pg, nxt2, pos + 1, cfg,
                                   block_table=bt)
    np.testing.assert_array_equal(np.asarray(l_ref2), np.asarray(l_pg2))


def test_serve_step_paged_multi_codebook():
    """Multi-codebook (4-d logits) route: musicgen-style K=4 codebooks
    through the paged cache match the contiguous path."""
    cfg = dataclasses.replace(get_config("musicgen-large-reduced"),
                              cross_attn_cond=0)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    rows, cache_len, ps, S = 2, 16, 4, 5
    MP = cache_len // ps
    K = cfg.num_codebooks
    assert K > 1
    rng = np.random.default_rng(2)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (rows, K, S)), jnp.int32)
    logits, cb = decoding.prefill(params, toks, cfg, cache_len)
    assert logits.ndim == 4                       # (B, 1, K, V) — 4-d logits

    lengths = jnp.full((rows,), S, jnp.int32)
    pager = PageAllocator(rows * MP, ps)
    for i in range(rows):
        assert pager.ensure(i, S + 2)
    bt = jnp.asarray(pager.block_table_rows(list(range(rows)), MP))
    paged = _paged_cache_from_prefill(cfg, cb, bt, lengths, rows, cache_len,
                                      rows * MP, ps)
    nxt = jnp.argmax(logits[:, -1], -1)[..., None]      # (B, K, 1)
    pos = lengths
    l_ref, _ = decoding.serve_step(params, cb, nxt, pos, cfg)
    l_pg, _ = decoding.serve_step(params, paged, nxt, pos, cfg,
                                  block_table=bt)
    assert l_ref.shape[-2] == K
    np.testing.assert_array_equal(np.asarray(l_ref), np.asarray(l_pg))


def test_unallocated_table_entries_drop_writes():
    """Writes past a row's block table are dropped, not wrapped: a pos whose
    page is -1 must leave the pool untouched."""
    cfg = get_config("qwen2.5-3b-reduced")
    KV, D = cfg.num_kv_heads, cfg.head_dim
    pool = jnp.zeros((4, 4, KV, D), jnp.float32)
    rows_kv = jnp.ones((1, 8, KV, D), jnp.float32)
    bt = jnp.asarray([[2, -1]], jnp.int32)
    out = decoding.scatter_rows_to_pages(pool, rows_kv, bt,
                                         jnp.asarray([8], jnp.int32))
    # first page (physical 2) written, second page's 4 tokens dropped
    assert float(jnp.sum(out)) == 4 * KV * D
    assert float(jnp.sum(out[2])) == 4 * KV * D


# ------------------------------------------------------------ dispatch rule
def test_attn_path_occupancy_rule():
    ps = dataflow.PAGE_SIZE
    # short caches never page; low occupancy pages; near-full stays dense
    assert dataflow.attn_path(ps, ps // 2) == "contiguous"
    assert dataflow.attn_path(16 * ps, 4 * ps) == "paged"
    assert dataflow.attn_path(16 * ps, 15 * ps + 1) == "contiguous"
    # the boundary follows PAGED_OCCUPANCY_MAX on page-rounded occupancy
    cache = 16 * ps
    lim = int(dataflow.PAGED_OCCUPANCY_MAX * 16)
    assert dataflow.attn_path(cache, lim * ps) == "paged"
    assert dataflow.attn_path(cache, lim * ps + 1) == "contiguous"


def test_paged_vs_dense_token_accounting():
    lens = [10, 100, 64]
    ps = 64
    assert dataflow.paged_kv_tokens(lens, ps) == 64 + 128 + 64
    assert dataflow.dense_kv_tokens(3, 512) == 1536
    assert dataflow.paged_kv_tokens(lens, ps) < dataflow.dense_kv_tokens(3, 512)
