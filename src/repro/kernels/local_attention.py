"""Flash-style sliding-window causal attention Pallas kernel.

The compact-DNN hot-spot: gemma2/gemma3/mixtral run most layers with a bounded
attention window, so the kernel only visits the O(S·w) diagonal band instead
of O(S²). Online-softmax running (m, l, acc) state lives in VMEM scratch (the
psum-SPad analogue); K/V tiles stream HBM→VMEM along the band.

Grid: (B, H, nq, nk_per_q) where nk_per_q covers exactly the window band for
one query tile. The K/V index map computes the *logical* (possibly negative)
band block and clamps it into range; the kernel recomputes the unclamped
position to mask out-of-band/out-of-sequence keys, so clamp-duplicated tiles
contribute nothing. GQA is handled by mapping head h to KV head h // R in the
index maps — no K/V replication in HBM.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -2.0e38


def _band_start(iq: int, bq: int, bk: int, nk_per_q: int):
    """Logical first k-block of the band for query tile iq (may be negative)."""
    last = (iq * bq + bq - 1) // bk
    return last - (nk_per_q - 1)


def _swa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                bq: int, bkv: int, nk_per_q: int, window: int, seq_len: int,
                softcap: float):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)              # (bq, D)
    k = k_ref[0, 0].astype(jnp.float32)              # (bkv, D)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
    s = s * (1.0 / math.sqrt(q.shape[-1]))
    if softcap > 0.0:
        s = jnp.tanh(s / softcap) * softcap

    # Positions from the *logical* (unclamped) block index: clamp-duplicated
    # tiles get fully-masked scores.
    kblk = _band_start(iq, bq, bkv, nk_per_q) + ik
    qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
    kpos = kblk * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
    rel = qpos - kpos
    mask = (rel >= 0) & (rel < window) & (kpos >= 0) & (kpos < seq_len)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.where(s > NEG_INF / 2, jnp.exp(s - m_new[:, None]), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ik == nk_per_q - 1)
    def _done():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)[:, None]
                       ).astype(o_ref.dtype)


def sliding_window_attention_raw(q, k, v, *, window: int, bq: int = 128,
                                 bkv: int = 128, softcap: float = 0.0,
                                 out_dtype=jnp.float32,
                                 interpret: bool = False):
    """q (B,H,S,D); k,v (B,KV,S,D), H % KV == 0, S % bq == S % bkv == 0.

    Returns (B,H,S,D). Pad/transpose handled by ops.sliding_window_attention.
    """
    B, H, S, D = q.shape
    KV = k.shape[1]
    R = H // KV
    assert S % bq == 0 and S % bkv == 0, (S, bq, bkv)
    nq = S // bq
    nk_per_q = (window - 1 + bq) // bkv + 1       # covers the band + diagonal

    def kv_index(b, h, iq, ik):
        blk = _band_start(iq, bq, bkv, nk_per_q) + ik
        return (b, h // R, jnp.clip(blk, 0, S // bkv - 1), 0)

    kernel = functools.partial(
        _swa_kernel, bq=bq, bkv=bkv, nk_per_q=nk_per_q, window=window,
        seq_len=S, softcap=softcap)

    return pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk_per_q),
        name="local_attention",
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bkv, D), kv_index),
            pl.BlockSpec((1, 1, bkv, D), kv_index),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
