"""Core neural-net layers (pure JAX, functional params-in/params-out style).

Everything computes in bf16 with fp32 accumulation (``preferred_element_type``),
normalizations and softmax in fp32 — the TPU analogue of the paper's
8b MAC / 20b psum precision pair (DESIGN.md §7). Parameters are held in bf16,
the dtype every matmul casts its weight to and the 2 B/param the serving plan
counts; training keeps its own fp32 master copy (train.loop).
"""
from __future__ import annotations

import contextvars
import math
from typing import Optional

import jax
import jax.numpy as jnp

COMPUTE_DTYPE = jnp.bfloat16
PARAM_DTYPE = jnp.bfloat16
ACCUM_DTYPE = jnp.float32


def cast_compute(x):
    return x.astype(COMPUTE_DTYPE)


# ------------------------------------------------------- sharding-hints context
# Set around tracing by launch/cell.py (sharding.autoshard.ShardingHints).
# Layer internals pin their projection outputs to the planner's NoC mode via
# constrain_tokens; a None context (CPU smoke tests) is a no-op.
_HINTS: contextvars.ContextVar = contextvars.ContextVar("hints", default=None)


def set_hints(hints):
    return _HINTS.set(hints)


def reset_hints(token):
    _HINTS.reset(token)


def constrain(x, tp_dim: Optional[int] = None, tp_check=None,
              batch_dim: int = 0, tp_candidates=None,
              widen_batch: bool = False):
    h = _HINTS.get()
    if h is None:
        return x
    return h.constrain_tokens(x, tp_dim=tp_dim, tp_check=tp_check,
                              batch_dim=batch_dim,
                              tp_candidates=tp_candidates,
                              widen_batch=widen_batch)


# --------------------------------------------------------------------------- init
def dense_init(rng, shape, in_axis=0):
    fan_in = shape[in_axis] if isinstance(in_axis, int) else 1
    std = 1.0 / math.sqrt(max(fan_in, 1))
    return (jax.random.normal(rng, shape, dtype=jnp.float32) * std).astype(PARAM_DTYPE)


def embed_init(rng, shape):
    return (jax.random.normal(rng, shape, dtype=jnp.float32)).astype(PARAM_DTYPE)


# --------------------------------------------------------------------------- norm
def rms_norm(x, scale, eps: float):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def head_rms_norm(x, scale, eps: float):
    """qk-norm: normalize over the head_dim axis of (..., D)."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


# --------------------------------------------------------------------------- rope
def rope(x, positions, theta: float):
    """Rotary embedding. x: (..., S, H, D); positions: (..., S) int32."""
    d = x.shape[-1]
    half = d // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., :, None].astype(jnp.float32) * freq  # (..., S, half)
    sin = jnp.sin(angles)[..., :, None, :]  # broadcast over heads
    cos = jnp.cos(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_pos(positions, d_model: int):
    """(..., S) int32 -> (..., S, d) sinusoidal table (musicgen)."""
    half = d_model // 2
    freq = jnp.exp(-math.log(10_000.0) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freq
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------- softcap
def softcap(logits, cap: float):
    if cap and cap > 0.0:
        logits = jnp.tanh(logits / cap) * cap
    return logits


# ------------------------------------------------------------------- attention
NEG_INF = -2.0e38


def _split_heads(x, n, d):
    return x.reshape(x.shape[:-1] + (n, d))


def attn_qkv(params, x, cfg):
    """Project to q,k,v. x: (B,S,d). Returns q (B,S,H,D), k/v (B,S,KV,D)."""
    q = jnp.einsum("bsd,dhk->bshk", x, cast_compute(params["wq"]),
                   preferred_element_type=ACCUM_DTYPE)
    k = jnp.einsum("bsd,dhk->bshk", x, cast_compute(params["wk"]),
                   preferred_element_type=ACCUM_DTYPE)
    v = jnp.einsum("bsd,dhk->bshk", x, cast_compute(params["wv"]),
                   preferred_element_type=ACCUM_DTYPE)
    if cfg.qkv_bias:
        q = q + params["bq"].astype(ACCUM_DTYPE)
        k = k + params["bk"].astype(ACCUM_DTYPE)
        v = v + params["bv"].astype(ACCUM_DTYPE)
    # TP over heads only when q AND kv head counts both divide the model axis
    # (keeps the GQA grouping consistent); else heads stay replicated — the
    # paper's diminished-M fragmentation (Table I). NOTE a widen-batch
    # fall-back (batch over the model axis for attention) was tried and
    # REFUTED: XLA lowers the layout change as all-gathers, costing ~10× the
    # replicated compute it saves (EXPERIMENTS.md §Perf, hypothesis log).
    tpc = (cfg.num_heads, cfg.num_kv_heads)
    q = constrain(q.astype(COMPUTE_DTYPE), tp_dim=2, tp_check=tpc)
    k = constrain(k.astype(COMPUTE_DTYPE), tp_dim=2, tp_check=tpc)
    v = constrain(v.astype(COMPUTE_DTYPE), tp_dim=2, tp_check=tpc)
    return q, k, v


def attn_out(params, ctx):
    """ctx: (B,S,H,D) -> (B,S,d). Row-parallel output in bf16 so the TP
    partial-sum all-reduce carries 2 bytes/elt (Megatron-style; MXU still
    accumulates fp32 internally) — §Perf iteration C2."""
    return jnp.einsum("bshk,hkd->bsd", ctx, cast_compute(params["wo"]),
                      preferred_element_type=COMPUTE_DTYPE)


def _gqa_scores(q, k, cap):
    """q (B,S,KV,R,D), k (B,T,KV,D) -> (B,KV,R,S,T) fp32 logits."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bsgrd,btgd->bgrst", q, k, preferred_element_type=jnp.float32)
    return softcap(s * scale, cap)


def _gqa_ctx(p, v):
    """p (B,KV,R,S,T) fp32, v (B,T,KV,D) -> (B,S,KV,R,D).

    p stays fp32: decode carries a single query row, so the PV product is
    tiny and fp32 probabilities keep this jnp fallback numerically aligned
    with the paged decode kernel's fp32 online-softmax accumulator
    (kernels/paged_attention.py) — the dispatch can switch paths per batch
    without shifting logits by a bf16 quantization step.
    """
    return jnp.einsum("bgrst,btgd->bsgrd", p, v.astype(jnp.float32),
                      preferred_element_type=jnp.float32)


def _flash_call(q, k, v, cfg, mode: str, msize: int):
    """Layout shim onto models.flash (custom-VJP, O(S) residuals).

    q (B,S,H,D); k,v (B,S,KV,D) -> (B,S,H,D). The (B,KV,R,S,D) internal layout
    keeps the GQA grouping explicit so TP-on-heads constraints survive.

    Sequence-sharded path (§Perf hillclimb, the paper's Eyexam-step-4 fix):
    when the head counts do NOT divide the model axis (gemma2 8H, qwen 2KV,
    mixtral 8KV ...), plain TP would leave the model axis idle and replicate
    attention compute ×model. Instead the q rows are sharded along S over the
    model axis under shard_map (K/V replicated — each chip attends its own
    query rows; flash rows are independent). dK/dV are psum'd by shard_map AD.
    """
    from repro.models import flash as flash_lib
    B, S, H, D = q.shape
    KV = k.shape[2]
    R = H // KV
    qf = q.reshape(B, S, KV, R, D).transpose(0, 2, 3, 1, 4)
    kf = k.transpose(0, 2, 1, 3)
    vf = v.transpose(0, 2, 1, 3)
    blk = 512
    while blk > S:
        blk //= 2
    blk = max(blk, 16)

    h = _HINTS.get()
    ms = h.model_size if h is not None else 1
    heads_tp = (H % ms == 0 and KV % ms == 0)
    use_seq = (h is not None and h.tp and ms > 1 and not heads_tp
               and S % ms == 0 and (S // ms) >= 128)
    if use_seq:
        from jax.sharding import PartitionSpec as P
        b_ax = h.act[0]
        S_loc = S // ms

        def body(q_loc, k_full, v_full):
            off = jax.lax.axis_index("model") * S_loc
            qpos = off + jnp.arange(S_loc, dtype=jnp.int32)
            return flash_lib.flash_attention(
                q_loc, k_full, v_full, mode, msize,
                cfg.attn_logit_softcap, min(blk, S_loc), blk, qpos=qpos)

        out = jax.shard_map(
            body, mesh=h.mesh,
            in_specs=(P(b_ax, None, None, "model", None),
                      P(b_ax, None, None, None),
                      P(b_ax, None, None, None)),
            out_specs=P(b_ax, None, None, "model", None),
            check_vma=False)(qf, kf, vf)
    else:
        out = flash_lib.flash_attention(qf, kf, vf, mode, msize,
                                        cfg.attn_logit_softcap, blk, blk)
        out = constrain(out, tp_dim=1, tp_check=(KV, H))
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, S, H, D)
    return out.astype(COMPUTE_DTYPE)


def full_causal_attention(q, k, v, cfg):
    """Full causal attention via blocked flash (no S×S materialization; FLOP
    upper bound 2× causal minimum — above-diagonal blocks are masked)."""
    return _flash_call(q, k, v, cfg, "causal", q.shape[1])


def local_attention(q, k, v, cfg):
    """Sliding-window causal attention, window w = cfg.window_size. Flash
    visits only the O(S·w) band."""
    w = cfg.window_size
    if q.shape[1] <= w:
        return full_causal_attention(q, k, v, cfg)
    return _flash_call(q, k, v, cfg, "window", w)


def chunked_attention(q, k, v, cfg):
    """llama4 iRoPE chunked attention: causal within fixed chunks."""
    c = cfg.chunk_size
    if q.shape[1] <= c:
        return full_causal_attention(q, k, v, cfg)
    return _flash_call(q, k, v, cfg, "chunk", c)


def decode_attention(q, k_cache, v_cache, valid_mask, cfg):
    """One-token attention against a cache.

    q (B,1,H,D); k_cache/v_cache (B,T,KV,D); valid_mask (B,T) bool.
    """
    B, _, H, D = q.shape
    KV = k_cache.shape[2]
    R = H // KV
    qr = q.reshape(B, 1, KV, R, D)
    s = _gqa_scores(qr, k_cache, cfg.attn_logit_softcap)  # (B,KV,R,1,T)
    s = jnp.where(valid_mask[:, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    ctx = _gqa_ctx(p, v_cache)  # (B,1,KV,R,D)
    return ctx.reshape(B, 1, KV * R, D).astype(COMPUTE_DTYPE)


def cross_attention(params, x, cond, cfg):
    """Cross-attention to a (stubbed) conditioning sequence. x (B,S,d), cond (B,T,d)."""
    H, D = cfg.num_heads, cfg.head_dim
    q = jnp.einsum("bsd,dhk->bshk", x, cast_compute(params["wq"]),
                   preferred_element_type=ACCUM_DTYPE).astype(COMPUTE_DTYPE)
    k = jnp.einsum("btd,dhk->bthk", cond, cast_compute(params["wk"]),
                   preferred_element_type=ACCUM_DTYPE).astype(COMPUTE_DTYPE)
    v = jnp.einsum("btd,dhk->bthk", cond, cast_compute(params["wv"]),
                   preferred_element_type=ACCUM_DTYPE).astype(COMPUTE_DTYPE)
    scale = 1.0 / math.sqrt(D)
    s = jnp.einsum("bshk,bthk->bhst", q, k,
                   preferred_element_type=jnp.float32) * scale
    p = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bhst,bthk->bshk", p.astype(COMPUTE_DTYPE), v,
                     preferred_element_type=jnp.float32).astype(COMPUTE_DTYPE)
    return attn_out(params, ctx)


# ------------------------------------------------------------------------- MLP
def _packed_proj(x, packed, n_out: int, activation: Optional[str] = None):
    """(B,S,d) · BCSC-packed weight -> (B,S,n_out) via the sparse kernels.

    M = B·S rows: decode shapes (M ≤ dataflow.GEMV_M_MAX) hit the bcsc_gemv
    scratch-accumulator kernel with the activation fused into the epilogue;
    prefill/training shapes take the BCSC GEMM kernel. Zero weight blocks are
    skipped entirely — the serve-path realization of the paper's Sparse PE.
    """
    from repro.kernels import ops as _ops   # deferred: keep layer import light
    B, S, d = x.shape
    y = _ops.bcsc_apply_packed(x.reshape(B * S, d), packed, n_out=n_out,
                               activation=activation,
                               out_dtype=jnp.float32)
    return y.reshape(B, S, n_out)


def mlp(params, x, cfg, d_ff: Optional[int] = None):
    """GeGLU/SwiGLU MLP, Megatron-TP pattern: up-projections column-sharded
    over the model axis (grouped-multicast mode), down-projection row-sharded
    with a psum — the hidden h stays (batch, seq, d_ff/model) per chip.

    Any projection stored BCSC-packed (serve.sparse.sparsify_mlp_params)
    bypasses the einsum and runs the sparse kernel with the activation fused
    into its epilogue; dense weights keep the exact original path. When EVERY
    projection of the layer is packed and the dataflow rule allows it, the
    whole MLP collapses into the fused bcsc_mlp megakernel — one pallas_call,
    hidden activation in VMEM scratch, per-layer actual nnzb (never the
    padded stack count).

    Dispatch reads the active ServePlan (core.plan — the engines activate it
    around their jitted programs) and falls back to the core.dataflow rule
    when none is active; both resolve to the same crossover."""
    from repro.core import plan as _plan
    from repro.kernels.ops import is_packed
    act_name = "silu" if cfg.mlp_act == "silu" else "gelu"
    act = jax.nn.silu if cfg.mlp_act == "silu" else \
        (lambda t: jax.nn.gelu(t, approximate=True))
    ff = d_ff or (cfg.dense_d_ff if (cfg.moe and cfg.dense_d_ff) else cfg.d_ff)
    d = x.shape[-1]

    names = ("wg", "wu", "wd") if cfg.mlp_gated else ("w1", "w2")
    if all(is_packed(params[n]) for n in names):
        B, S, _ = x.shape
        if _plan.route_mlp(B * S, ff, d, gated=cfg.mlp_gated) == "fused":
            from repro.kernels import ops as _ops
            up2 = params["wu"] if cfg.mlp_gated else None
            y = _ops.bcsc_mlp_packed(
                x.reshape(B * S, d), params[names[0]], up2, params[names[-1]],
                d_ff=ff, n_out=d, activation=act_name,
                counts=params.get("_bcsc_counts"), out_dtype=jnp.float32)
            return constrain(y.reshape(B, S, d).astype(COMPUTE_DTYPE))

    if cfg.mlp_gated:
        wg, wu = params["wg"], params["wu"]
        g_act = _packed_proj(x, wg, ff, act_name) if is_packed(wg) else \
            act(jnp.einsum("bsd,df->bsf", x, cast_compute(wg),
                           preferred_element_type=ACCUM_DTYPE))
        u = _packed_proj(x, wu, ff) if is_packed(wu) else \
            jnp.einsum("bsd,df->bsf", x, cast_compute(wu),
                       preferred_element_type=ACCUM_DTYPE)
        h = constrain((g_act * u).astype(COMPUTE_DTYPE), tp_dim=2)
    else:
        w1 = params["w1"]
        h1 = _packed_proj(x, w1, ff, act_name) if is_packed(w1) else \
            act(jnp.einsum("bsd,df->bsf", x, cast_compute(w1),
                           preferred_element_type=ACCUM_DTYPE))
        h = constrain(h1.astype(COMPUTE_DTYPE), tp_dim=2)
    wd = params["wd"] if cfg.mlp_gated else params["w2"]
    # row-parallel down-proj in bf16: TP all-reduce payload halves (§Perf C2)
    if is_packed(wd):
        out = _packed_proj(h, wd, d).astype(COMPUTE_DTYPE)
    else:
        out = jnp.einsum("bsf,fd->bsd", h, cast_compute(wd),
                         preferred_element_type=COMPUTE_DTYPE)
    return constrain(out)


# ------------------------------------------------------------------ param init
def init_attn_params(rng, cfg, cross: bool = False):
    d, H, KV, D = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(rng, 8)
    p = {
        "wq": dense_init(ks[0], (d, H, D)),
        "wk": dense_init(ks[1], (d, KV, D)),
        "wv": dense_init(ks[2], (d, KV, D)),
        "wo": dense_init(ks[3], (H, D, d), in_axis=0),
    }
    if cross:
        p["wk"] = dense_init(ks[1], (d, H, D))
        p["wv"] = dense_init(ks[2], (d, H, D))
    if cfg.qkv_bias and not cross:
        p["bq"] = jnp.zeros((H, D), PARAM_DTYPE)
        p["bk"] = jnp.zeros((KV, D), PARAM_DTYPE)
        p["bv"] = jnp.zeros((KV, D), PARAM_DTYPE)
    if cfg.qk_norm and not cross:
        p["q_norm"] = jnp.zeros((D,), PARAM_DTYPE)
        p["k_norm"] = jnp.zeros((D,), PARAM_DTYPE)
    return p


def init_mlp_params(rng, cfg, d_ff: int):
    d = cfg.d_model
    ks = jax.random.split(rng, 3)
    if cfg.mlp_gated:
        return {"wg": dense_init(ks[0], (d, d_ff)),
                "wu": dense_init(ks[1], (d, d_ff)),
                "wd": dense_init(ks[2], (d_ff, d))}
    return {"w1": dense_init(ks[0], (d, d_ff)),
            "w2": dense_init(ks[1], (d_ff, d))}
