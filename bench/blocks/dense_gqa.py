"""The dense GQA block (Qwen2, Mistral): pre-norm RMSNorm, grouped-query
attention with rotary positions, optional q/k/v biases, a gated SiLU MLP.

A configuration file names its block under ``"block"``; the harness loads
``bench/blocks/<block>.py`` by path (``spec.load_block``) and reaches the
block only through what this module defines. Every block module keeps
this contract:

- ``Dims.from_file(c)`` returns the sizes the harness reads: ``layers``,
  ``d``, ``heads``, ``kv_heads``, ``head_dim``, ``vocab``, ``eps``,
  ``tied``; it is hashable, as the reference's jitted calls take it as a
  static argument. The same ``Dims`` gives the yardstick's counts of the
  block: ``token_matmul_params()``, the weights one token is multiplied
  by through every layer (for experts, those the token is routed to on
  this chip); ``paged_layers``, the layers whose attention is the
  ``paged_attention`` kernel; ``context_flops(n)``, the q.k and p.v
  operations of one query at context ``n`` over all layers (a window
  layer reads ``min(n, window)``); ``prompt_context_flops(n)``, the causal
  total of a prompt of ``n`` tokens.
- ``program_config(c)`` returns the program's ``ArchConfig`` with every
  size in the file set on the registry's entry; it raises where the
  program's block is not the file's block.
- ``plain_weights(params)`` returns the reference's view of the weights.
- ``request_gaps(wts, dims, prompt, served, quant=None)`` runs the block's
  float32 reference at ``HIGHEST`` (``benchlib.reference``), and its
  control with ``quant="control"``.

What every block shares stays in ``benchlib``: the seeded weights
(``model.make_params``), the reference's helpers and layer loop
(``reference``), and the peaks and the paged kernel's per-call counts
(``roofline``). The reference imports nothing of the program.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict

import jax
import jax.numpy as jnp

from benchlib import reference
from benchlib.reference import _attention, _fake_int, _mm, _rms, _rope

# configuration-file key -> ArchConfig field
FIELDS = {
    "num_hidden_layers": "num_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
}


@dataclasses.dataclass(frozen=True)
class Dims:
    """The plain sizes the reference and the yardstick read."""
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    tied: bool
    rope_theta: float
    eps: float
    qkv_bias: bool

    @classmethod
    def from_file(cls, c: Dict) -> "Dims":
        return cls(layers=c["num_hidden_layers"], d=c["hidden_size"],
                   heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"],
                   head_dim=c.get("head_dim",
                                  c["hidden_size"] // c["num_attention_heads"]),
                   d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                   tied=bool(c["tie_word_embeddings"]),
                   rope_theta=float(c["rope_theta"]),
                   eps=float(c["rms_norm_eps"]),
                   qkv_bias=bool(c["assumed"]["qkv_bias"]))

    def layer_matmul_params(self) -> int:
        """Weights one layer multiplies a token by (q, k, v, o, gate, up,
        down)."""
        d, hd = self.d, self.head_dim
        return (d * self.heads * hd * 2 + d * self.kv_heads * hd * 2
                + 3 * d * self.d_ff)

    def token_matmul_params(self) -> int:
        return self.layers * self.layer_matmul_params()

    @property
    def paged_layers(self) -> int:
        """Every layer's attention is the paged kernel."""
        return self.layers

    def context_flops(self, n: int) -> int:
        return self.layers * 4 * self.heads * self.head_dim * int(n)

    def prompt_context_flops(self, n: int) -> int:
        """Position p reads p + 1 keys, in every layer."""
        n = int(n)
        return self.layers * 4 * self.heads * self.head_dim \
            * n * (n + 1) // 2

    def weight_bytes(self, vocab_rows: int) -> int:
        """bf16 bytes of every weight, with ``vocab_rows`` rows of the
        embedding (and of the head, where it is not tied)."""
        d, hd = self.d, self.head_dim
        per_layer = self.layer_matmul_params() + 2 * d
        if self.qkv_bias:
            per_layer += (self.heads + 2 * self.kv_heads) * hd
        embed = vocab_rows * d * (1 if self.tied else 2)
        return 2 * (self.layers * per_layer + embed + d)


def program_config(c: Dict):
    """The program's ``ArchConfig`` for configuration file ``c``."""
    from repro.configs import get_config
    base = get_config(c["registry"])
    repl = {}
    for key, field in FIELDS.items():
        if key == "head_dim" and key not in c:
            continue
        repl[field] = type(getattr(base, field))(c[key])
    arch = dataclasses.replace(base, **repl)
    if not (arch.mlp_act == c["hidden_act"] and arch.mlp_gated
            and arch.qkv_bias == c["assumed"]["qkv_bias"]
            and arch.attn_pattern == ("global",)):
        raise ValueError(f"{c['registry']}: the program's block is not the "
                         "configuration's (gated silu MLP, global attention)")
    arch.validate()
    return arch


def plain_weights(params) -> Dict:
    """The program's parameter tree under plain names, without a copy.

    The program keeps each layer's arrays stacked on a leading layer axis
    under ``blocks/slot0`` and stores a norm gain g as g - 1; the
    reference reads ``ln*`` as that offset and adds 1 itself."""
    b = params["blocks"]["slot0"]
    a, m = b["attn"], b["mlp"]
    layers = {"ln1": b["pre_norm"], "ln2": b["pre_norm_mlp"],
              "wq": a["wq"], "wk": a["wk"], "wv": a["wv"], "wo": a["wo"],
              "wg": m["wg"], "wu": m["wu"], "wd": m["wd"]}
    for k in ("bq", "bk", "bv"):
        if k in a:
            layers[k] = a[k]
    out = {"embed": params["embed"], "final_norm": params["final_norm"],
           "layers": layers}
    if "lm_head" in params:
        out["lm_head"] = params["lm_head"]
    return out


@functools.partial(jax.jit, static_argnums=(3, 4))
def _layer(lw, i, x, dims, quant):
    def w(name):
        return jax.lax.dynamic_index_in_dim(lw[name], i, 0,
                                            keepdims=False).astype(jnp.float32)

    S, d = x.shape
    H, KV, D = dims.heads, dims.kv_heads, dims.head_dim
    pos = jnp.arange(S)
    h = _rms(x, w("ln1"), dims.eps)
    q = _mm(h, w("wq").reshape(d, H * D), quant)
    k = _mm(h, w("wk").reshape(d, KV * D), quant)
    v = _mm(h, w("wv").reshape(d, KV * D), quant)
    if dims.qkv_bias:
        q = q + w("bq").reshape(-1)
        k = k + w("bk").reshape(-1)
        v = v + w("bv").reshape(-1)
    q = _rope(q.reshape(S, H, D), pos, dims.rope_theta)
    k = _rope(k.reshape(S, KV, D), pos, dims.rope_theta)
    v = v.reshape(S, KV, D)
    if quant == "control":
        k, v = _fake_int(k, -1, 7.0), _fake_int(v, -1, 7.0)
    ctx = _attention(q, k, v)
    x = x + _mm(ctx, w("wo").reshape(H * D, d), quant)
    h = _rms(x, w("ln2"), dims.eps)
    gate = jax.nn.silu(_mm(h, w("wg"), quant))
    x = x + _mm(gate * _mm(h, w("wu"), quant), w("wd"), quant)
    return x


def request_gaps(wts, dims, prompt, served, quant=None):
    return reference.request_gaps(_layer, wts, dims, prompt, served, quant)
