"""What every block's plain float32 reference shares.

The helpers a block's layer is built from (RMSNorm with gain ``1 + ln``,
rotary positions on the two halves of each head, blocked causal
grouped-query attention, the control's rounding) and the decoder around
the layers: the embedding, the layer loop, a final RMSNorm and a tied or
separate head. The layer is the block's (``bench/blocks/<block>.py``),
handed in as ``layer(lw, i, x, dims, quant)``. Every matrix product runs
in float32 at ``Precision.HIGHEST``; nothing is cached, paged, batched or
quantised. It imports nothing of the program.

``logits_at`` runs one sequence layer by layer (one call per layer,
queries in blocks), so that it fits beside the served weights, and returns
the logits at the positions asked for. ``quant="control"`` is the control:
the same forward one precision step below what the configuration states,
every linear layer's operands rounded to int8 (bf16 -> int8; weights per
output channel, activations per token) and the cached keys and values to
int4 (int8 -> int4; per token and KV head).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512


def bucket(n: int) -> int:
    """Padded sequence length: a multiple of 1024 up to 4096, then of 2048
    (few compiled shapes; pad positions come after every real one)."""
    step = 1024 if n <= 4096 else 2048
    return -(-n // step) * step


def _fake_int(x, axis, levels):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / levels
    s = jnp.where(s > 0, s, 1.0)
    return jnp.round(x / s) * s


def _mm(a, w, quant):
    """a (..., k) @ w (k, n) in float32; the control rounds both to int8."""
    if quant == "control":
        a = _fake_int(a, -1, 127.0)
        w = _fake_int(w, 0, 127.0)
    return jnp.matmul(a, w, precision=HI)


def _rms(x, ln, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + ln)


def _rope(x, pos, theta):
    """x (S, h, D): rotate the halves (x1, x2) by angle pos * theta^(-2i/D)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v):
    """Causal GQA. q (S, H, D), k/v (S, KV, D) -> (S, H*D)."""
    S, H, D = q.shape
    KV = k.shape[1]
    qg = q.reshape(S // QUERY_BLOCK, QUERY_BLOCK, KV, H // KV, D)
    keys = jnp.arange(S)

    def block(args):
        qb, b = args
        s = jnp.einsum("qgrd,tgd->grqt", qb, k, precision=HI) / math.sqrt(D)
        rows = b * QUERY_BLOCK + jnp.arange(QUERY_BLOCK)
        s = jnp.where(keys[None, None, None, :] <= rows[None, None, :, None],
                      s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("grqt,tgd->qgrd", p, v, precision=HI)

    out = jax.lax.map(block, (qg, jnp.arange(S // QUERY_BLOCK)))
    return out.reshape(S, H * D)


@functools.partial(jax.jit, static_argnums=(2,))
def _embed(table, tokens, dims):
    return table[tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _head(wts, x, rows, dims, quant):
    h = _rms(x[rows], wts["final_norm"].astype(jnp.float32), dims.eps)
    if dims.tied:
        table = wts["embed"][:dims.vocab].astype(jnp.float32).T
    else:
        table = wts["lm_head"][:, :dims.vocab].astype(jnp.float32)
    return _mm(h, table, quant)


def logits_at(layer, wts, dims, tokens, positions, quant=None):
    """Logits (len(positions), vocab) of the sequence ``tokens`` at
    ``positions`` (each the position whose next token they predict),
    through the block's ``layer`` at each of ``dims.layers``."""
    tokens = np.asarray(tokens, np.int32)
    S = bucket(len(tokens))
    padded = np.zeros((S,), np.int32)
    padded[:len(tokens)] = tokens
    x = _embed(wts["embed"], jnp.asarray(padded), dims)
    for i in range(dims.layers):
        x = layer(wts["layers"], jnp.int32(i), x, dims, quant)
    head = {k: wts[k] for k in ("embed", "final_norm", "lm_head") if k in wts}
    n = len(positions)
    rows = np.zeros((-(-n // 256) * 256,), np.int32)     # few head shapes
    rows[:n] = positions
    return _head(head, x, jnp.asarray(rows), dims, quant)[:n]


def served_positions(prompt_len: int, n_served: int) -> np.ndarray:
    """Position whose logits chose each served token."""
    return np.arange(prompt_len - 1, prompt_len - 1 + n_served)


def gaps(ref_logits, tokens) -> np.ndarray:
    """By how much each token's reference logit lies below the reference's
    best at its position (0 where the token is the reference's argmax)."""
    ref = np.asarray(ref_logits, np.float64)
    tokens = np.asarray(tokens, np.int64)
    return ref.max(axis=-1) - ref[np.arange(len(tokens)), tokens]


def request_gaps(layer, wts, dims, prompt, served, quant=None) -> np.ndarray:
    """Gaps of the served tokens of one request, teacher-forced: the
    reference reads the prompt and every served token but the last.
    With ``quant`` it returns the gaps of the tokens that the lower
    precision puts first at the same positions instead."""
    served = np.asarray(served, np.int64)
    seq = np.concatenate([np.asarray(prompt, np.int64), served[:-1]])
    pos = served_positions(len(prompt), len(served))
    ref = logits_at(layer, wts, dims, seq, pos)
    if quant is None:
        return gaps(ref, served)
    low = logits_at(layer, wts, dims, seq, pos, quant)
    return gaps(ref, np.asarray(jnp.argmax(low, axis=-1)))
