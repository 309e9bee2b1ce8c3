"""Generic decoder LM assembled from an ArchConfig.

Layers are applied through ``lax.scan`` over *pattern periods* (stacked params),
so HLO size — and thus AOT compile time for the 512-device dry-run — is O(one
period), not O(num_layers). Remainder layers (e.g. recurrentgemma's 26 = 8×3+2)
are applied unstacked after the scan.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers, moe as moe_lib, rglru as rglru_lib, ssm as ssm_lib
from repro.models.layers import (ACCUM_DTYPE, COMPUTE_DTYPE, PARAM_DTYPE,
                                 cast_compute, rms_norm)

MOE_AUX_WEIGHT = 0.01
Z_LOSS_WEIGHT = 1e-4
LOSS_CHUNK = 512          # seq chunk for the vocab-sized logits (memory bound)


# --------------------------------------------------------------------- layout
def scan_period(cfg) -> int:
    p = cfg.pattern_period
    if cfg.moe:
        p = math.lcm(p, cfg.moe_every)
    return p


def num_scan_periods(cfg) -> int:
    return cfg.num_layers // scan_period(cfg)


def num_remainder(cfg) -> int:
    return cfg.num_layers % scan_period(cfg)


def slot_kinds(cfg):
    """Static (kind, is_moe) description for each slot in a scan period."""
    p = scan_period(cfg)
    return [(cfg.layer_kind(j), cfg.is_moe_layer(j)) for j in range(p)]


# ------------------------------------------------------------------ param init
def _init_block(rng, cfg, kind: str, is_moe: bool):
    d = cfg.d_model
    ks = jax.random.split(rng, 6)
    p: Dict = {"pre_norm": jnp.zeros((d,), PARAM_DTYPE),
               "pre_norm_mlp": jnp.zeros((d,), PARAM_DTYPE)}
    if cfg.use_post_norm:
        p["post_norm"] = jnp.zeros((d,), PARAM_DTYPE)
        p["post_norm_mlp"] = jnp.zeros((d,), PARAM_DTYPE)
    if kind in ("global", "local", "chunked"):
        p["attn"] = layers.init_attn_params(ks[0], cfg)
        if cfg.cross_attn_cond:
            p["cross_attn"] = layers.init_attn_params(ks[1], cfg, cross=True)
            p["pre_norm_cross"] = jnp.zeros((d,), PARAM_DTYPE)
    elif kind == "ssm":
        p["ssm"] = ssm_lib.init_ssm_params(ks[0], cfg)
    elif kind == "rglru":
        p["rglru"] = rglru_lib.init_rglru_params(ks[0], cfg)
    if kind != "ssm":
        if is_moe:
            p["moe"] = moe_lib.init_moe_params(ks[2], cfg)
        else:
            ff = cfg.dense_d_ff if (cfg.moe and cfg.dense_d_ff) else cfg.d_ff
            p["mlp"] = layers.init_mlp_params(ks[2], cfg, ff)
    return p


@functools.partial(jax.jit, static_argnums=1)
def init_params(rng, cfg):
    """Random parameters for ``cfg``, built inside one jitted program so
    only the output pytree is allocated on the device."""
    period = scan_period(cfg)
    nper = num_scan_periods(cfg)
    rem = num_remainder(cfg)
    kinds = slot_kinds(cfg)
    k_embed, k_head, k_blocks, k_rem = jax.random.split(rng, 4)

    Vp, d, K = cfg.vocab_padded, cfg.d_model, cfg.num_codebooks
    params: Dict = {
        "embed": layers.embed_init(k_embed, (K, Vp, d)) if K > 1
        else layers.embed_init(k_embed, (Vp, d)),
        "final_norm": jnp.zeros((d,), PARAM_DTYPE),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = (layers.dense_init(k_head, (K, d, Vp), in_axis=1)
                             if K > 1 else layers.dense_init(k_head, (d, Vp)))

    def init_period(rng_p):
        kk = jax.random.split(rng_p, period)
        return {f"slot{j}": _init_block(kk[j], cfg, *kinds[j])
                for j in range(period)}

    if nper:
        params["blocks"] = jax.vmap(init_period)(jax.random.split(k_blocks, nper))
    if rem:
        kk = jax.random.split(k_rem, rem)
        params["rem"] = {f"rem{j}": _init_block(kk[j], cfg, *kinds[j])
                         for j in range(rem)}
    return params


def abstract_params(cfg):
    """ShapeDtypeStruct pytree — no allocation (for the dry-run)."""
    return jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))


# ------------------------------------------------------------------- embedding
def embed_tokens(params, tokens, cfg):
    """tokens (B,S) or (B,K,S) -> (B,S,d)."""
    if cfg.num_codebooks > 1:
        # sum the K codebook embeddings (musicgen)
        x = jnp.zeros(tokens.shape[:1] + tokens.shape[2:] + (cfg.d_model,),
                      jnp.float32)
        for k in range(cfg.num_codebooks):
            x = x + params["embed"][k][tokens[:, k]].astype(jnp.float32)
    else:
        x = params["embed"][tokens].astype(jnp.float32)
    if cfg.embed_scale:
        x = x * math.sqrt(cfg.d_model)
    return x.astype(COMPUTE_DTYPE)


def lm_logits(params, x, cfg):
    """x (B,S,d) -> logits fp32 (B,S,Vp) or (B,S,K,Vp)."""
    if cfg.num_codebooks > 1:
        w = params["lm_head"]  # (K,d,Vp)
        logits = jnp.einsum("bsd,kdv->bskv", x, cast_compute(w),
                            preferred_element_type=jnp.float32)
    elif cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x, cast_compute(params["embed"]),
                            preferred_element_type=jnp.float32)
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, cast_compute(params["lm_head"]),
                            preferred_element_type=jnp.float32)
    logits = layers.softcap(logits, cfg.final_logit_softcap)
    if cfg.vocab_padded != cfg.vocab_size:   # mask pad vocab
        pad_mask = jnp.arange(logits.shape[-1]) >= cfg.vocab_size
        logits = jnp.where(pad_mask, layers.NEG_INF, logits)
    return logits


# ------------------------------------------------------------------ block apply
def _rope_theta_for(cfg, kind: str) -> float:
    if kind == "local" and cfg.local_rope_theta > 0:
        return cfg.local_rope_theta
    return cfg.rope_theta


def _attn_train(p, x, cond, kind, positions, cfg):
    q, k, v = layers.attn_qkv(p, x, cfg)
    if cfg.qk_norm:
        q = layers.head_rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = layers.head_rms_norm(k, p["k_norm"], cfg.norm_eps)
    if cfg.pos_embed == "rope":
        theta = _rope_theta_for(cfg, kind)
        q = layers.rope(q, positions, theta)
        k = layers.rope(k, positions, theta)
    if kind == "local":
        ctx = layers.local_attention(q, k, v, cfg)
    elif kind == "chunked":
        ctx = layers.chunked_attention(q, k, v, cfg)
    else:
        ctx = layers.full_causal_attention(q, k, v, cfg)
    return layers.attn_out(p, ctx)


def apply_block(p, x, cond, kind, is_moe, cfg, positions):
    """One decoder block (training / prefill form). x (B,S,d)."""
    aux = jnp.zeros((), jnp.float32)
    h = rms_norm(x, p["pre_norm"], cfg.norm_eps)
    if kind in ("global", "local", "chunked"):
        y = _attn_train(p["attn"], h, cond, kind, positions, cfg)
    elif kind == "ssm":
        y = ssm_lib.ssm_block(p["ssm"], h, cfg)
    elif kind == "rglru":
        y = rglru_lib.rglru_block(p["rglru"], h, cfg)
    if cfg.use_post_norm:
        y = rms_norm(y, p["post_norm"], cfg.norm_eps)
    x = x + y
    if cfg.cross_attn_cond and kind in ("global", "local", "chunked"):
        hc = rms_norm(x, p["pre_norm_cross"], cfg.norm_eps)
        x = x + layers.cross_attention(p["cross_attn"], hc, cond, cfg)
    if kind != "ssm":
        h = rms_norm(x, p["pre_norm_mlp"], cfg.norm_eps)
        if is_moe:
            y, aux = moe_lib.moe_layer(p["moe"], h, cfg)
        else:
            y = layers.mlp(p["mlp"], h, cfg)
        if cfg.use_post_norm:
            y = rms_norm(y, p["post_norm_mlp"], cfg.norm_eps)
        x = x + y
    return x, aux


# --------------------------------------------------------------------- forward
def forward(params, tokens, cfg, *, patch_embeds=None, cond=None,
            remat_policy: str = "none", hints=None):
    """Training/prefill forward. Returns final hidden states (B,S,d).

    ``hints`` (sharding.autoshard.ShardingHints) pins activations to the
    planner's iact-NoC mode inside the jitted program — without it XLA's
    propagation may re-shard activations onto the weight layout.
    """
    x = embed_tokens(params, tokens, cfg)
    if cfg.frontend == "vision" and patch_embeds is not None:
        x = jnp.concatenate([patch_embeds.astype(COMPUTE_DTYPE), x], axis=1)
    if hints is not None:
        x = hints.constrain_act(x)
    B, S = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    if cfg.pos_embed == "sinusoidal":
        x = x + layers.sinusoidal_pos(positions, cfg.d_model).astype(COMPUTE_DTYPE)

    kinds = slot_kinds(cfg)
    period = scan_period(cfg)

    def period_fn(x, period_params):
        aux = jnp.zeros((), jnp.float32)
        for j in range(period):
            x, a = apply_block(period_params[f"slot{j}"], x, cond,
                               *kinds[j], cfg, positions)
            if hints is not None:
                x = hints.constrain_act(x)
            aux = aux + a
        return x, aux

    if remat_policy == "full":
        period_fn = jax.checkpoint(period_fn)
    elif remat_policy == "dots":
        period_fn = jax.checkpoint(
            period_fn, policy=jax.checkpoint_policies.checkpoint_dots)
    elif remat_policy == "dots_no_batch":
        period_fn = jax.checkpoint(
            period_fn,
            policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)

    aux_total = jnp.zeros((), jnp.float32)
    if "blocks" in params:
        x, auxs = jax.lax.scan(lambda c, pp: period_fn(c, pp),
                               x, params["blocks"])
        aux_total = aux_total + jnp.sum(auxs)
    if "rem" in params:
        for j in range(num_remainder(cfg)):
            x, a = apply_block(params["rem"][f"rem{j}"], x, cond,
                               *kinds[j], cfg, positions)
            aux_total = aux_total + a
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, aux_total


# ------------------------------------------------------------------------ loss
def _xent_chunk(params, x_chunk, labels_chunk, cfg, hints=None):
    logits = lm_logits(params, x_chunk, cfg)         # fp32
    if hints is not None:
        logits = hints.constrain_logits(logits)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    if cfg.num_codebooks > 1:                         # labels (B,K,C) -> (B,C,K)
        lbl = jnp.swapaxes(labels_chunk, 1, 2)
    else:
        lbl = labels_chunk
    valid = lbl >= 0
    lbl_safe = jnp.maximum(lbl, 0)
    picked = jnp.take_along_axis(logits, lbl_safe[..., None], axis=-1)[..., 0]
    nll = jnp.where(valid, lse - picked, 0.0)
    zloss = jnp.where(valid, jnp.square(lse), 0.0)
    return (jnp.sum(nll), jnp.sum(zloss), jnp.sum(valid),
            jnp.sum(jnp.where(valid, (jnp.argmax(logits, -1) == lbl), False)))


def loss_from_hidden(params, x, labels, cfg, hints=None):
    """Chunked softmax-xent over the (huge) vocab — never materializes the full
    (B,S,V) logits; scans LOSS_CHUNK positions at a time."""
    B, S = x.shape[:2]
    c = min(LOSS_CHUNK, S)
    while S % c:
        c //= 2
    n = S // c
    xr = jnp.moveaxis(x.reshape(B, n, c, -1), 1, 0)           # (n,B,c,d)
    if cfg.num_codebooks > 1:
        lr = jnp.moveaxis(labels.reshape(B, labels.shape[1], n, c), 2, 0)
    else:
        lr = jnp.moveaxis(labels.reshape(B, n, c), 1, 0)      # (n,B,c)

    # remat: the chunk's (B,c,V) logits would otherwise be SAVED per scan step
    # for the backward (GBs at 256k vocab) — recompute them instead
    xent = jax.checkpoint(
        lambda xc, lc: _xent_chunk(params, xc, lc, cfg, hints))

    def step(carry, inp):
        xc, lc = inp
        nll, zl, cnt, acc = xent(xc, lc)
        return (carry[0] + nll, carry[1] + zl, carry[2] + cnt,
                carry[3] + acc), None

    init = (jnp.zeros(()), jnp.zeros(()), jnp.zeros(()), jnp.zeros(()))
    (nll, zl, cnt, acc), _ = jax.lax.scan(step, init, (xr, lr))
    cnt = jnp.maximum(cnt, 1.0)
    return nll / cnt, zl / cnt, acc / cnt


def loss_fn(params, batch, cfg, *, remat_policy: str = "none", hints=None):
    """Full training loss. batch: tokens/labels (+patch_embeds/cond)."""
    x, aux = forward(params, batch["tokens"], cfg,
                     patch_embeds=batch.get("patch_embeds"),
                     cond=batch.get("cond"), remat_policy=remat_policy,
                     hints=hints)
    labels = batch["labels"]
    if cfg.frontend == "vision":                      # loss only on text tokens
        x = x[:, cfg.num_patches:]
    loss, zloss, acc = loss_from_hidden(params, x, labels, cfg, hints)
    total = loss + Z_LOSS_WEIGHT * zloss + MOE_AUX_WEIGHT * aux
    metrics = {"loss": loss, "z_loss": zloss, "moe_aux": aux, "accuracy": acc}
    return total, metrics
