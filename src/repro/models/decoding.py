"""KV/state caches + single-token decode and prefill paths.

Cache layout mirrors the block layout: entries for the scanned period-blocks are
stacked on a leading ``num_periods`` axis (so decode also scans), remainder
layers keep unstacked entries. Sliding-window and chunked layers use ring
buffers of size ``window``/``chunk`` — decode memory is bounded regardless of
context length (this is what makes ``long_500k`` runnable for those archs).

Ring invariant: slot ``i`` holds the token at the largest position ``p ≡ i
(mod m)`` with ``p ≤ pos``; validity masks are recomputed from ``pos`` each step.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers, moe as moe_lib, rglru as rglru_lib, ssm as ssm_lib
from repro.models import transformer as tfm
from repro.models.layers import COMPUTE_DTYPE, cast_compute, rms_norm


def _attn_cache_capacity(cfg, kind: str, cache_len: int) -> int:
    if kind == "local":
        return min(cfg.window_size, cache_len)
    if kind == "chunked":
        return min(cfg.chunk_size, cache_len)
    return cache_len


def _init_entry(cfg, kind: str, batch: int, cache_len: int):
    if kind in ("global", "local", "chunked"):
        cap = _attn_cache_capacity(cfg, kind, cache_len)
        shape = (batch, cap, cfg.num_kv_heads, cfg.head_dim)
        return {"k": jnp.zeros(shape, COMPUTE_DTYPE),
                "v": jnp.zeros(shape, COMPUTE_DTYPE)}
    if kind == "ssm":
        return ssm_lib.init_ssm_state(batch, cfg)
    if kind == "rglru":
        return rglru_lib.init_rglru_state(batch, cfg)
    raise ValueError(kind)


def init_cache(cfg, batch: int, cache_len: int):
    kinds = tfm.slot_kinds(cfg)
    period = tfm.scan_period(cfg)
    nper = tfm.num_scan_periods(cfg)
    rem = tfm.num_remainder(cfg)
    cache: Dict = {}
    if nper:
        def one_period():
            return {f"slot{j}": _init_entry(cfg, kinds[j][0], batch, cache_len)
                    for j in range(period)}
        cache["blocks"] = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (nper,) + x.shape).copy(), one_period())
    if rem:
        cache["rem"] = {f"rem{j}": _init_entry(cfg, kinds[j][0], batch, cache_len)
                        for j in range(rem)}
    return cache


def abstract_cache(cfg, batch: int, cache_len: int):
    return jax.eval_shape(lambda: init_cache(cfg, batch, cache_len))


# ------------------------------------------------------------- paged cache
# Global-attention entries swap the dense (rows, cache_len, ...) slot for a
# shared pool of fixed-size pages, addressed through a per-row block table
# (the CSC address-vector analogue — serve/paging.py owns the host-side
# accounting, kernels/paged_attention.py the device-side read). Ring
# (local/chunked) and recurrent (ssm/rglru) entries keep their bounded
# per-row state: their memory never scales with context, so paging them
# would add indirection with nothing to reclaim.
#
# kv_quant='int8' stores page payloads as symmetric int8 with per-(page,
# kv-head) fp32 amax scales riding the block table ({pk,pv}_scale): the
# whole-page granularity keeps the dequant inside the kernel's page loop
# (one scale broadcast per DMA'd page) and the scale tables negligible
# next to the payload halving.
def _init_paged_entry(cfg, num_pages: int, page_size: int,
                      kv_quant: str = "fp"):
    from repro.core import dataflow as _df
    assert kv_quant in _df.KV_QUANT_DTYPES, kv_quant
    shape = (num_pages, page_size, cfg.num_kv_heads, cfg.head_dim)
    if kv_quant == "int8":
        sshape = (num_pages, cfg.num_kv_heads)
        return {"pk": jnp.zeros(shape, jnp.int8),
                "pv": jnp.zeros(shape, jnp.int8),
                "pk_scale": jnp.zeros(sshape, jnp.float32),
                "pv_scale": jnp.zeros(sshape, jnp.float32)}
    return {"pk": jnp.zeros(shape, COMPUTE_DTYPE),
            "pv": jnp.zeros(shape, COMPUTE_DTYPE)}


def is_paged_entry(entry) -> bool:
    return isinstance(entry, dict) and "pk" in entry


def is_quantized_entry(entry) -> bool:
    return isinstance(entry, dict) and "pk_scale" in entry


def init_paged_cache(cfg, rows: int, cache_len: int, num_pages: int,
                     page_size: Optional[int] = None,
                     kv_quant: Optional[str] = None):
    """Like init_cache, but 'global' entries become (num_pages, page_size,
    KV, D) pools; every other kind keeps its (rows, ...) per-row state.
    ``kv_quant='int8'`` stores pool payloads int8 with per-page scales.

    ``page_size``/``kv_quant`` default from the active ServePlan when a
    serving engine has one activated (core.plan — the single owner of the
    PAGE_SIZE/quant decisions), else from the core.dataflow constants."""
    from repro.core import plan as _plan
    if page_size is None:
        page_size = _plan.page_size_default(cache_len)
    if kv_quant is None:
        pl = _plan.active_plan()
        kv_quant = pl.kv_quant if pl is not None else "fp"
    kinds = tfm.slot_kinds(cfg)
    period = tfm.scan_period(cfg)
    nper = tfm.num_scan_periods(cfg)
    rem = tfm.num_remainder(cfg)

    def entry(kind):
        if kind == "global":
            return _init_paged_entry(cfg, num_pages, page_size, kv_quant)
        return _init_entry(cfg, kind, rows, cache_len)

    cache: Dict = {}
    if nper:
        one = {f"slot{j}": entry(kinds[j][0]) for j in range(period)}
        cache["blocks"] = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (nper,) + x.shape).copy(), one)
    if rem:
        cache["rem"] = {f"rem{j}": entry(kinds[j][0]) for j in range(rem)}
    return cache


# ------------------------------------------------- page quantization helpers
def quantize_to_i8(x, scale):
    """Symmetric int8: q = round(x / scale * 127), scale an amax broadcastable
    to x. A zero scale (empty page / all-zero token) quantizes to zeros."""
    s = jnp.where(scale > 0, scale, 1.0)
    q = jnp.round(x.astype(jnp.float32) / s * 127.0)
    return jnp.clip(q, -127.0, 127.0).astype(jnp.int8)


def dequantize_i8(q, scale):
    return q.astype(jnp.float32) * (scale * (1.0 / 127.0))


def quantize_paged_entry(entry, num_pages: Optional[int] = None):
    """Requantize a resident fp page pool to the int8 layout in place — the
    device half of the guard's int8 degradation rung (serve/scheduler.py).

    Each page gets the same per-(page, kv-head) amax scale scheme the int8
    prefill/append paths use, so the paged-attention dequant and
    ``_append_token_i8``'s requant-on-loud-token logic work on the result
    unchanged. ``num_pages`` > the current pool grows the page axis with
    zero pages (zero scale == empty page by convention) — int8 pages cost
    half the HBM, so the same footprint holds ~2× the pages; existing
    physical page ids keep their contents and block tables stay valid.
    Handles both stacked ``(nper, P, ps, KV, D)`` and unstacked
    ``(P, ps, KV, D)`` pools (page axis -4 either way).
    """
    assert is_paged_entry(entry) and not is_quantized_entry(entry), entry

    def conv(pool):
        x = pool.astype(jnp.float32)
        scale = jnp.abs(x).max(axis=(-3, -1))          # (..., P, KV)
        q = quantize_to_i8(x, scale[..., None, :, None])
        if num_pages is not None and num_pages > pool.shape[-4]:
            pad = num_pages - pool.shape[-4]
            qw = [(0, 0)] * q.ndim
            qw[-4] = (0, pad)
            sw = [(0, 0)] * scale.ndim
            sw[-2] = (0, pad)
            q = jnp.pad(q, qw)
            scale = jnp.pad(scale, sw)
        return q, scale

    pk, ks = conv(entry["pk"])
    pv, vs = conv(entry["pv"])
    return {"pk": pk, "pv": pv, "pk_scale": ks, "pv_scale": vs}


def scatter_rows_to_pages(pool, rows_kv, block_table_rows, lengths,
                          start=None):
    """Write per-row contiguous KV (B,S,KV,D) into a page pool (P,ps,KV,D).

    Token t of row b lands at (block_table_rows[b, t // ps], t % ps) for
    start[b] <= t < lengths[b]; positions before ``start`` (pages adopted
    read-only from a shared prefix chain), pad positions, and unallocated
    (-1) table entries are routed out of range and dropped. This is the
    page-native prefill write (prefill_batched's paged mode scatters each
    layer's (B, tier) K/V straight into pages during the scan) and is
    symmetric with the paged kernel's read addressing.
    """
    P, ps = pool.shape[:2]
    B, S = rows_kv.shape[:2]
    s = jnp.arange(S, dtype=jnp.int32)
    page = jnp.take_along_axis(
        block_table_rows, jnp.broadcast_to(s // ps, (B, S)), axis=1)
    valid = (s[None, :] < lengths[:, None]) & (page >= 0)
    if start is not None:
        valid &= s[None, :] >= start[:, None]
    page = jnp.where(valid, page, P)                 # out of range -> dropped
    off = jnp.broadcast_to(s % ps, (B, S))
    return pool.at[page, off].set(rows_kv.astype(pool.dtype), mode="drop")


def quantize_rows_to_pages(pool, scales, rows_kv, block_table_rows, lengths,
                           start=None):
    """int8 variant of scatter_rows_to_pages: (pool, scales) -> updated.

    Per written (row, logical page, kv-head) the amax over the page's tokens
    becomes that physical page's scale (plain .set — prefill writes every
    page it touches from offset 0, pages are row-exclusive, and overwriting
    resets any stale scale a previous holder left). ``start`` must be
    page-aligned or equal to the row's length (the adoption contract:
    shared prefixes cover whole pages or the whole prompt).
    """
    P, ps, KV, D = pool.shape
    B, S = rows_kv.shape[:2]
    s = jnp.arange(S, dtype=jnp.int32)
    bt = block_table_rows
    page = jnp.take_along_axis(bt, jnp.broadcast_to(s // ps, (B, S)), axis=1)
    valid = (s[None, :] < lengths[:, None]) & (page >= 0)
    if start is not None:
        valid &= s[None, :] >= start[:, None]
    # per-(row, logical page, kv) amax over the tokens actually written
    nlp = -(-S // ps)
    a = jnp.abs(jnp.where(valid[..., None, None],
                          rows_kv.astype(jnp.float32), 0.0))
    a = jnp.pad(a, ((0, 0), (0, nlp * ps - S), (0, 0), (0, 0)))
    a = a.reshape(B, nlp, ps, KV, D).max(axis=(2, 4))        # (B, nlp, KV)
    wrote = jnp.pad(valid, ((0, 0), (0, nlp * ps - S))
                    ).reshape(B, nlp, ps).any(axis=2)        # (B, nlp)
    phys = jnp.where(wrote & (bt[:, :nlp] >= 0), bt[:, :nlp], P)
    new_scales = scales.at[phys.reshape(-1)].set(
        a.reshape(-1, KV), mode="drop")
    # quantize each token with its destination page's (fresh) scale
    tok_scale = jnp.take_along_axis(
        a, jnp.broadcast_to((s // ps)[None, :, None], (B, S, KV)), axis=1)
    q = quantize_to_i8(rows_kv, tok_scale[..., None])
    page = jnp.where(valid, page, P)
    off = jnp.broadcast_to(s % ps, (B, S))
    return pool.at[page, off].set(q, mode="drop"), new_scales


def paged_prefill_write(entry, k, v, block_table_rows, lengths, start=None):
    """Write a prefill layer's (B, S, KV, D) K/V straight into its page pool
    entry (fp or int8+scales), honoring the shared-prefix ``start`` mask."""
    if is_quantized_entry(entry):
        pk, ks = quantize_rows_to_pages(entry["pk"], entry["pk_scale"], k,
                                        block_table_rows, lengths, start)
        pv, vs = quantize_rows_to_pages(entry["pv"], entry["pv_scale"], v,
                                        block_table_rows, lengths, start)
        return {"pk": pk, "pv": pv, "pk_scale": ks, "pv_scale": vs}
    return {"pk": scatter_rows_to_pages(entry["pk"], k, block_table_rows,
                                        lengths, start),
            "pv": scatter_rows_to_pages(entry["pv"], v, block_table_rows,
                                        lengths, start)}


def _append_token_i8(pool, scales, tok, page, off):
    """Append one (B, KV, D) fp token per row into int8 pages at (page, off).

    Per-page amax scales must cover every token in the page, so a token
    louder than the page's current scale triggers an in-place **requant** of
    that page (q' = round(q · s_old/s_new) — bounded, monotone error; the
    common quiet-token case is an exact no-op since ratio == 1). A page's
    first token (off == 0) ignores whatever stale scale a previous holder
    left — pages come back from the pool content-dirty but are always
    re-scaled before anything in them is readable.
    """
    P, ps, KV, D = pool.shape
    B = tok.shape[0]
    valid = page >= 0
    pidx = jnp.clip(page, 0, P - 1)
    s_old = scales[pidx]                                       # (B, KV)
    s_old = jnp.where((off == 0)[:, None], 0.0, s_old)
    amax = jnp.abs(tok.astype(jnp.float32)).max(axis=-1)       # (B, KV)
    s_new = jnp.maximum(s_old, amax)
    ratio = jnp.where(s_new > 0, s_old / jnp.where(s_new > 0, s_new, 1.0),
                      1.0)                                     # <= 1
    pg = pool[pidx].astype(jnp.float32)                        # (B, ps, KV, D)
    pg = jnp.round(pg * ratio[:, None, :, None])
    q_tok = quantize_to_i8(tok, s_new[..., None]).astype(jnp.float32)
    sel = (jnp.arange(ps)[None, :] == off[:, None])[..., None, None]
    pg = jnp.where(sel, q_tok[:, None], pg)
    drop = jnp.where(valid, pidx, P)
    pool = pool.at[drop].set(
        jnp.clip(pg, -127.0, 127.0).astype(jnp.int8), mode="drop")
    scales = scales.at[drop].set(s_new, mode="drop")
    return pool, scales


def _paged_append(entry, k_tok, v_tok, block_table, posv):
    """Decode-time single-token append into a paged entry (fp or int8).

    The caller (scheduler CoW guard) guarantees the destination page is
    private (refcount 1) — shared pages are materialized before the chunk.
    """
    P, ps = entry["pk"].shape[:2]
    page = jnp.take_along_axis(block_table, (posv // ps)[:, None],
                               axis=1)[:, 0]
    off = posv % ps
    if is_quantized_entry(entry):
        pk, ks = _append_token_i8(entry["pk"], entry["pk_scale"], k_tok,
                                  page, off)
        pv, vs = _append_token_i8(entry["pv"], entry["pv_scale"], v_tok,
                                  page, off)
        return {"pk": pk, "pv": pv, "pk_scale": ks, "pv_scale": vs}
    dropped = jnp.where(page >= 0, page, P)        # unallocated -> dropped
    return {"pk": entry["pk"].at[dropped, off].set(
                k_tok.astype(entry["pk"].dtype), mode="drop"),
            "pv": entry["pv"].at[dropped, off].set(
                v_tok.astype(entry["pv"].dtype), mode="drop")}


# -------------------------------------------------------------- ring helpers
def _ring_positions(pos, m: int):
    """Absolute position held by each of the m ring slots at time ``pos``."""
    i = jnp.arange(m)
    return pos - jnp.mod(pos - i, m)


def _valid_mask(cfg, kind: str, cap: int, pos):
    """pos scalar or (B,) — per-slot positions for the device-resident decode
    loop (serve.engine). Returns (1, cap) or (B, cap)."""
    p = jnp.asarray(pos)[..., None]          # (1,) -> (cap,) or (B,1) -> (B,cap)
    i = jnp.arange(cap)
    if kind == "global":
        m = i <= p
    else:
        slot_pos = p - jnp.mod(p - i, cap)   # _ring_positions, broadcast form
        if kind == "local":
            m = slot_pos >= 0
        else:
            chunk_start = (p // cfg.chunk_size) * cfg.chunk_size
            m = slot_pos >= chunk_start
    return m if m.ndim == 2 else m[None, :]


# --------------------------------------------------------------- decode block
def _positions_2d(pos, B: int):
    """Scalar or (B,) pos -> (B,1) int32 position matrix."""
    pos = jnp.asarray(pos)
    if pos.ndim == 0:
        return jnp.broadcast_to(pos, (B, 1)).astype(jnp.int32)
    return pos[:, None].astype(jnp.int32)


def _attn_decode(p, x, kind, cache_entry, pos, cfg, block_table=None):
    """pos scalar (cohort decode) or (B,) (per-slot, the continuous-batching
    engine): each slot writes its own ring/cache position. A paged entry
    ({pk, pv} pool, is_paged_entry) takes the block-table path instead: the
    token is scattered into its page and attention reads the history through
    kernels.paged_attention (dispatch decided host-side by
    core.dataflow.attn_path — the serve scheduler's paged mode)."""
    B = x.shape[0]
    q, k, v = layers.attn_qkv(p, x, cfg)              # q (B,1,H,D), k/v (B,1,KV,D)
    if cfg.qk_norm:
        q = layers.head_rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = layers.head_rms_norm(k, p["k_norm"], cfg.norm_eps)
    if cfg.pos_embed == "rope":
        theta = tfm._rope_theta_for(cfg, kind)
        positions = _positions_2d(pos, B)
        q = layers.rope(q, positions, theta)
        k = layers.rope(k, positions, theta)
    # mesh resolution (ISSUE 10): tp > 1 runs attention per local KV-head
    # shard (the shard-explicit single-jit program; bit-identical to tp=1
    # by per-head independence — see sharding.tensor_parallel)
    from repro.core import plan as _plan
    tp = getattr(_plan.active_plan(), "tp", 1) or 1
    if is_paged_entry(cache_entry):
        from repro.sharding import tensor_parallel as _tpar
        assert block_table is not None, "paged cache entry needs a block table"
        pos = jnp.asarray(pos)
        posv = jnp.broadcast_to(pos, (B,)).astype(jnp.int32)
        # named scopes tag the step's parts in the compiled program's op
        # metadata, where a device op of a profile is traced back to them
        with jax.named_scope("kv_append"):
            new_entry = _paged_append(cache_entry, k[:, 0], v[:, 0],
                                      block_table, posv)
        scales = {}
        if is_quantized_entry(new_entry):
            scales = dict(k_scale=new_entry["pk_scale"],
                          v_scale=new_entry["pv_scale"])
        with jax.named_scope("paged_attention"):
            ctx = _tpar.sharded_paged_attention(
                q, new_entry["pk"], new_entry["pv"], block_table, posv + 1,
                tp, softcap=cfg.attn_logit_softcap, **scales)
        return (layers.attn_out(p, ctx.astype(layers.COMPUTE_DTYPE)),
                new_entry)
    cap = cache_entry["k"].shape[1]
    pos = jnp.asarray(pos)
    if pos.ndim == 0:
        idx = pos % cap if kind != "global" else pos
        k_cache = jax.lax.dynamic_update_slice_in_dim(cache_entry["k"], k,
                                                      idx, axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(cache_entry["v"], v,
                                                      idx, axis=1)
    else:
        # per-slot write: one-hot select along the cap axis (vectorized form
        # of dynamic_update_slice; same clamp-at-cap semantics for 'global')
        idx = pos % cap if kind != "global" else jnp.minimum(pos, cap - 1)
        sel = (jnp.arange(cap)[None, :] == idx[:, None])[..., None, None]
        k_cache = jnp.where(sel, k, cache_entry["k"])
        v_cache = jnp.where(sel, v, cache_entry["v"])
    mask = _valid_mask(cfg, kind, cap, pos)
    if tp > 1:
        from repro.sharding import tensor_parallel as _tpar
        ctx = _tpar.sharded_decode_attention(
            q, k_cache, v_cache, jnp.broadcast_to(mask, (B, cap)), cfg, tp)
    else:
        ctx = layers.decode_attention(q, k_cache, v_cache,
                                      jnp.broadcast_to(mask, (B, cap)), cfg)
    return layers.attn_out(p, ctx), {"k": k_cache, "v": v_cache}


def apply_block_decode(p, x, cond, kind, is_moe, cfg, cache_entry, pos,
                       block_table=None):
    h = rms_norm(x, p["pre_norm"], cfg.norm_eps)
    if kind in ("global", "local", "chunked"):
        y, new_entry = _attn_decode(p["attn"], h, kind, cache_entry, pos, cfg,
                                    block_table)
    elif kind == "ssm":
        y, new_entry = ssm_lib.ssm_block_decode(p["ssm"], h, cache_entry, cfg)
    elif kind == "rglru":
        y, new_entry = rglru_lib.rglru_block_decode(p["rglru"], h, cache_entry, cfg)
    if cfg.use_post_norm:
        y = rms_norm(y, p["post_norm"], cfg.norm_eps)
    x = x + y
    if cfg.cross_attn_cond and kind in ("global", "local", "chunked"):
        hc = rms_norm(x, p["pre_norm_cross"], cfg.norm_eps)
        x = x + layers.cross_attention(p["cross_attn"], hc, cond, cfg)
    if kind != "ssm":
        with jax.named_scope("mlp"):
            h = rms_norm(x, p["pre_norm_mlp"], cfg.norm_eps)
            if is_moe:
                y, _ = moe_lib.moe_layer(p["moe"], h, cfg)
            else:
                y = layers.mlp(p["mlp"], h, cfg)
            if cfg.use_post_norm:
                y = rms_norm(y, p["post_norm_mlp"], cfg.norm_eps)
            x = x + y
    return x, new_entry


def serve_step(params, cache, tokens, pos, cfg, cond=None, hints=None,
               block_table=None):
    """One decode step. tokens (B,1) or (B,K,1); pos scalar int32 (shared
    across the batch) or (B,) int32 (per-slot positions — the continuous
    batching engine's device-resident loop). ``block_table`` (B, max_pages)
    int32 routes paged cache entries (init_paged_cache) through the paged
    attention kernel. Returns (logits fp32, new_cache)."""
    x = tfm.embed_tokens(params, tokens, cfg)
    if hints is not None:
        x = hints.constrain_act(x)
    B = x.shape[0]
    if cfg.pos_embed == "sinusoidal":
        positions = _positions_2d(pos, B)
        x = x + layers.sinusoidal_pos(positions, cfg.d_model).astype(COMPUTE_DTYPE)
    kinds = tfm.slot_kinds(cfg)
    period = tfm.scan_period(cfg)

    new_cache: Dict = {}
    if "blocks" in params:
        def body(x, inp):
            pp, pc = inp
            npc = {}
            for j in range(period):
                x, npc[f"slot{j}"] = apply_block_decode(
                    pp[f"slot{j}"], x, cond, *kinds[j], cfg,
                    pc[f"slot{j}"], pos, block_table)
                if hints is not None:
                    x = hints.constrain_act(x)
            return x, npc
        x, new_cache["blocks"] = jax.lax.scan(
            body, x, (params["blocks"], cache["blocks"]))
    if "rem" in params:
        new_cache["rem"] = {}
        for j in range(tfm.num_remainder(cfg)):
            x, new_cache["rem"][f"rem{j}"] = apply_block_decode(
                params["rem"][f"rem{j}"], x, cond, *kinds[j], cfg,
                cache["rem"][f"rem{j}"], pos, block_table)
    with jax.named_scope("lm_head"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = tfm.lm_logits(params, x, cfg)
    return logits, new_cache


def verify_step(params, cache, tokens, pos, cfg, cond=None, hints=None,
                block_table=None):
    """Score k candidate tokens per row in ONE dispatch — the speculative-
    decoding verifier (ISSUE 9).

    tokens (B, k) int32: row b's candidate continuation; tokens[b, 0] lands
    at position ``pos[b]``. Returns (logits fp32 (B, k, ...vocab), new_cache)
    where logits[b, i] conditions on the row's history plus tokens[b, :i+1]
    — exactly what ``serve_step`` would return after consuming those i+1
    tokens sequentially, bit-identical for fp page pools (asserted in
    tests/test_spec_decode.py the way paged==contiguous was).

    Mechanism: the k positions flatten into the batch axis. Page pools are
    row-count-free (addressed purely through block tables), so replicating
    each row's block table k times gives k "virtual rows" sharing one page
    chain: every flattened row appends its token at its own (page, offset)
    — disjoint targets, one scatter — and reads with per-row length
    ``pos + i + 1``, which exposes exactly the appends of its own prefix
    (later candidates sit past the length bound and are masked). That makes
    the single dispatch causal over the candidate block with no transient
    (B, k, cache_len) attention mask and no second write pass.

    Only valid for configs whose every layer is global attention on a paged
    cache (the plan's ``spec`` gate): ring/recurrent entries carry per-row
    state that the flattening cannot replicate. Quantized (int8) pools take
    a sequential k-step fallback instead — per-page amax scales make the
    append order observable (a louder later token requants the whole page),
    so the flattened scatter would race whole-page rewrites; the fallback
    keeps pools and logits bit-identical to sequential ``serve_step`` calls
    at k× dispatch cost, which is why the plan speculates on fp pools only.
    """
    assert block_table is not None, "verify_step requires a paged cache"
    kinds = {kk for kk, _ in tfm.slot_kinds(cfg)}
    assert kinds == {"global"}, \
        f"verify_step needs an all-global-attention config, got {kinds}"
    B, k = tokens.shape
    posv = jnp.broadcast_to(jnp.asarray(pos), (B,)).astype(jnp.int32)

    quantized = any(is_quantized_entry(e)
                    for e in jax.tree.leaves(cache, is_leaf=is_paged_entry))
    if quantized:
        outs = []
        for i in range(k):
            lg, cache = serve_step(params, cache, tokens[:, i:i + 1],
                                   posv + i, cfg, cond=cond, hints=hints,
                                   block_table=block_table)
            outs.append(lg)
        return jnp.concatenate(outs, axis=1), cache

    posf = (posv[:, None]
            + jnp.arange(k, dtype=jnp.int32)[None, :]).reshape(-1)
    tokf = tokens.reshape(-1)[:, None]                       # (B*k, 1)
    btf = jnp.repeat(block_table, k, axis=0)                 # (B*k, MP)
    logits, new_cache = serve_step(params, cache, tokf, posf, cfg, cond=cond,
                                   hints=hints, block_table=btf)
    return logits.reshape((B, k) + logits.shape[2:]), new_cache


# -------------------------------------------------------------------- prefill
def _gather_ring(full, m: int):
    """full (B,S,...) -> ring (B,m,...) honoring the ring invariant at pos=S-1."""
    S = full.shape[1]
    i = jnp.arange(m)
    p = (S - 1) - jnp.mod((S - 1) - i, m)
    return jnp.take(full, jnp.clip(p, 0, S - 1), axis=1)


def _gather_ring_ragged(full, m: int, lengths):
    """Per-row ring gather: row b honors the ring invariant at pos=lengths[b]-1.

    The batched-prefill analogue of _gather_ring for right-padded batches:
    each row's ring slots are filled from its *own* last positions, so pad
    tokens past a row's length never enter the ring. Slots that would map to
    negative positions (prompt shorter than the ring) clip to 0 — their data
    is garbage-but-masked, exactly like _gather_ring's clip (the decode-side
    _valid_mask recomputes validity from pos).
    """
    S = full.shape[1]
    i = jnp.arange(m)
    last = (lengths - 1)[:, None]                    # (B,1)
    p = last - jnp.mod(last - i[None, :], m)         # (B,m)
    p = jnp.clip(p, 0, S - 1)
    idx = p.reshape(p.shape + (1,) * (full.ndim - 2))
    return jnp.take_along_axis(full, idx, axis=1)


@dataclasses.dataclass
class PagedPrefill:
    """Page-native prefill-write routing (the paged output mode of
    prefill_batched). When present, global-attention K/V is scattered
    straight into the page pools of ``cache`` through per-row block tables
    *during the layer scan* — the dense (B, cache_len, ...) slot-shaped
    transient of the scatter-after-prefill path never exists — and every
    per-row entry (ring, recurrent) is merged into its device row at
    ``slots``. The returned cache is the full-width cache, refill-complete.

    ``write_start`` (B,) masks writes before each row's shared-prefix
    boundary (copy-on-write prefix sharing: adopted pages are read-only and
    already hold identical content). None writes from token 0.
    """
    cache: Dict
    block_table_rows: "jnp.ndarray"      # (B, max_pages) physical page ids
    slots: "jnp.ndarray"                 # (B,) device rows being refilled
    write_start: Optional["jnp.ndarray"] = None


def _merge_rows(cache_entry, row_entry, slots):
    """Merge B-row prefill state into its full-width per-row cache entry."""
    return jax.tree.map(
        lambda c, s: c.at[slots].set(s.astype(c.dtype)),
        cache_entry, row_entry)


def _attn_prefill(p, x, kind, positions, cfg, cache_len: int, lengths=None,
                  cache_entry=None, paged: Optional[PagedPrefill] = None):
    q, k, v = layers.attn_qkv(p, x, cfg)
    if cfg.qk_norm:
        q = layers.head_rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = layers.head_rms_norm(k, p["k_norm"], cfg.norm_eps)
    if cfg.pos_embed == "rope":
        theta = tfm._rope_theta_for(cfg, kind)
        q = layers.rope(q, positions, theta)
        k = layers.rope(k, positions, theta)
    if kind == "local":
        ctx = layers.local_attention(q, k, v, cfg)
    elif kind == "chunked":
        ctx = layers.chunked_attention(q, k, v, cfg)
    else:
        ctx = layers.full_causal_attention(q, k, v, cfg)
    cap = _attn_cache_capacity(cfg, kind, cache_len)
    S = k.shape[1]
    if paged is not None and is_paged_entry(cache_entry):
        # page-native write: (B, tier) K/V lands in pool pages as it is
        # produced — no (B, cache_len) padding, no post-prefill scatter
        entry = paged_prefill_write(cache_entry, k, v,
                                    paged.block_table_rows, lengths,
                                    paged.write_start)
    elif kind == "global":
        # pad rows of a right-padded batch leave pad-KV at positions >= that
        # row's length; decode's _valid_mask (i <= pos) never exposes them and
        # the serve loop overwrites them in order as pos advances.
        pad = cap - S
        entry = {"k": jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0))),
                 "v": jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))}
    elif lengths is None:
        entry = {"k": _gather_ring(k, cap), "v": _gather_ring(v, cap)}
    else:
        entry = {"k": _gather_ring_ragged(k, cap, lengths),
                 "v": _gather_ring_ragged(v, cap, lengths)}
    if paged is not None and not is_paged_entry(cache_entry):
        entry = _merge_rows(cache_entry, entry, paged.slots)
    return layers.attn_out(p, ctx), entry


def apply_block_prefill(p, x, cond, kind, is_moe, cfg, positions, cache_len,
                        lengths=None, cache_entry=None,
                        paged: Optional[PagedPrefill] = None):
    h = rms_norm(x, p["pre_norm"], cfg.norm_eps)
    if kind in ("global", "local", "chunked"):
        y, entry = _attn_prefill(p["attn"], h, kind, positions, cfg, cache_len,
                                 lengths, cache_entry, paged)
    elif kind == "ssm":
        y, entry = ssm_lib.ssm_block(p["ssm"], h, cfg, return_state=True)
        if paged is not None:
            entry = _merge_rows(cache_entry, entry, paged.slots)
    elif kind == "rglru":
        y, entry = rglru_lib.rglru_block(p["rglru"], h, cfg, return_state=True)
        if paged is not None:
            entry = _merge_rows(cache_entry, entry, paged.slots)
    if cfg.use_post_norm:
        y = rms_norm(y, p["post_norm"], cfg.norm_eps)
    x = x + y
    if cfg.cross_attn_cond and kind in ("global", "local", "chunked"):
        hc = rms_norm(x, p["pre_norm_cross"], cfg.norm_eps)
        x = x + layers.cross_attention(p["cross_attn"], hc, cond, cfg)
    if kind != "ssm":
        h = rms_norm(x, p["pre_norm_mlp"], cfg.norm_eps)
        if is_moe:
            y, _ = moe_lib.moe_layer(p["moe"], h, cfg)
        else:
            y = layers.mlp(p["mlp"], h, cfg)
        if cfg.use_post_norm:
            y = rms_norm(y, p["post_norm_mlp"], cfg.norm_eps)
        x = x + y
    return x, entry


def _prefill_impl(params, tokens, cfg, cache_len: int, lengths=None, *,
                  patch_embeds=None, cond=None, hints=None,
                  paged: Optional[PagedPrefill] = None):
    x = tfm.embed_tokens(params, tokens, cfg)
    if cfg.frontend == "vision" and patch_embeds is not None:
        x = jnp.concatenate([patch_embeds.astype(COMPUTE_DTYPE), x], axis=1)
    if hints is not None:
        x = hints.constrain_act(x)
    B, S = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    if cfg.pos_embed == "sinusoidal":
        x = x + layers.sinusoidal_pos(positions, cfg.d_model).astype(COMPUTE_DTYPE)
    kinds = tfm.slot_kinds(cfg)
    period = tfm.scan_period(cfg)

    cache: Dict = {}
    if "blocks" in params:
        if paged is not None:
            # paged output mode: scan over (params, cache) pairs so each
            # layer writes its K/V into the period's page pool (or merges
            # its per-row state at ``slots``) as the scan visits it
            def body(x, inp):
                pp, pc = inp
                entries = {}
                for j in range(period):
                    x, entries[f"slot{j}"] = apply_block_prefill(
                        pp[f"slot{j}"], x, cond, *kinds[j], cfg, positions,
                        cache_len, lengths, pc[f"slot{j}"], paged)
                    if hints is not None:
                        x = hints.constrain_act(x)
                return x, entries
            x, cache["blocks"] = jax.lax.scan(
                body, x, (params["blocks"], paged.cache["blocks"]))
        else:
            def body(x, pp):
                entries = {}
                for j in range(period):
                    x, entries[f"slot{j}"] = apply_block_prefill(
                        pp[f"slot{j}"], x, cond, *kinds[j], cfg, positions,
                        cache_len, lengths)
                    if hints is not None:
                        x = hints.constrain_act(x)
                return x, entries
            x, cache["blocks"] = jax.lax.scan(body, x, params["blocks"])
    if "rem" in params:
        cache["rem"] = {}
        for j in range(tfm.num_remainder(cfg)):
            x, cache["rem"][f"rem{j}"] = apply_block_prefill(
                params["rem"][f"rem{j}"], x, cond, *kinds[j], cfg, positions,
                cache_len, lengths,
                paged.cache["rem"][f"rem{j}"] if paged is not None else None,
                paged)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if lengths is None:
        x_last = x[:, -1:]
    else:
        # per-row last real position of the right-padded batch
        idx = (lengths - 1)[:, None, None]
        x_last = jnp.take_along_axis(x, jnp.broadcast_to(
            idx, (B, 1, x.shape[-1])), axis=1)
    logits = tfm.lm_logits(params, x_last, cfg)
    return logits, cache


def prefill(params, tokens, cfg, cache_len: int, *, patch_embeds=None,
            cond=None, hints=None):
    """Forward over the prompt, building the cache. Returns
    (last-position logits fp32, cache)."""
    return _prefill_impl(params, tokens, cfg, cache_len, None,
                         patch_embeds=patch_embeds, cond=cond, hints=hints)


def prefill_batched(params, tokens, lengths, cfg, cache_len: int, *,
                    cond=None, hints=None,
                    paged: Optional[PagedPrefill] = None):
    """Batched prefill over right-padded prompts of unequal length.

    tokens (B, S) right-padded to a common tier length S; lengths (B,) int32
    actual prompt lengths. Returns (per-row last-*real*-position logits
    (B,1,...), cache) where every cache entry honors each row's own length:
    ring entries gather per-row (``_gather_ring_ragged``), global entries
    rely on decode's pos-derived validity mask to hide pad positions.

    ``paged`` (PagedPrefill) switches on the page-native output mode: the
    returned cache is ``paged.cache`` with global K/V written straight into
    its page pools through per-row block tables during the layer scan and
    per-row entries merged at ``paged.slots`` — no (B, cache_len) dense
    transient, no post-prefill scatter, bit-identical pool contents to the
    scatter-after-prefill path (asserted in tests/test_paged_prefill_cow.py).

    Causality makes the padded forward exact for the real prefix of every
    attention row. NOT valid for recurrent kinds (ssm/rglru) when any
    length < S — pad tokens would pollute the carried state; callers
    (serve.engine) bucket those archs by exact length so lengths == S.
    Vision patch embeds are unsupported here: the per-row last-logits gather
    and ragged ring gather do not carry the ``num_patches`` offset of the
    concatenated sequence (no serving caller passes patches today).
    """
    assert cfg.frontend != "vision" or cfg.num_patches == 0, \
        "prefill_batched does not support vision patch offsets"
    return _prefill_impl(params, tokens, cfg, cache_len,
                         jnp.asarray(lengths, jnp.int32),
                         cond=cond, hints=hints, paged=paged)
