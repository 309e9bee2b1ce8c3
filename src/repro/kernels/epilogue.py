"""Fused bias+activation epilogue shared by the matmul kernels (DESIGN.md §3).

The paper's PE applies ReLU while psums drain from the SPad — the epilogue
rides the accumulator flush instead of costing a second pass over the output.
The TPU analogue: apply bias+activation to the fp32 VMEM accumulator tile in
the same grid step that writes ``o_ref``, so the activation never round-trips
through HBM. ``rs_matmul`` (dense GEMM), ``bcsc_gemv`` (sparse decode) and the
jnp fallback for the BCSC GEMM path all share this one definition, which keeps
the fused and unfused paths numerically aligned for the oracle tests.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

ACTIVATIONS = (None, "none", "relu", "silu", "gelu")


def fused_epilogue(acc, bias=None, activation: Optional[str] = None):
    """acc: fp32 accumulator tile. bias: broadcastable to acc or None.

    Runs entirely in fp32 (the psum precision, DESIGN.md §7); callers cast to
    the output dtype afterwards.
    """
    acc = acc.astype(jnp.float32)
    if bias is not None:
        acc = acc + bias.astype(jnp.float32)
    if activation in (None, "none"):
        return acc
    if activation == "relu":
        return jnp.maximum(acc, 0.0)
    if activation == "silu":
        return jax.nn.silu(acc)
    if activation == "gelu":
        return jax.nn.gelu(acc, approximate=True)
    raise ValueError(f"unknown activation {activation!r}; one of {ACTIVATIONS}")
