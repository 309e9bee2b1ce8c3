"""The yardstick: published chip peaks, and the operations and bytes that
the served work needs, computed from shapes.

Peaks are keyed by JAX's ``device_kind``; a kind that is not in the table
is an error, never a default.
"""
from __future__ import annotations

import dataclasses
from typing import Iterable, Sequence

PEAKS_SOURCE = ("Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
                "393 TOP/s int8, 16 GB HBM at 819 GB/s per chip")


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float
    int8_ops: float
    hbm_bw: float
    hbm_bytes: float
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(bf16_flops=197e12, int8_ops=393e12, hbm_bw=819e9,
                         hbm_bytes=16e9, source=PEAKS_SOURCE),
}


def peaks(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device_kind "
                         f"{device_kind!r}; add them, with their source, to "
                         "bench/benchlib/roofline.py") from None


def pages_for(n: int, page_size: int) -> int:
    return -(-int(n) // page_size)


# ---------------------------------------------------------- paged attention
def paged_attention_bytes(dims, lengths: Iterable[int], page_size: int,
                          kv_bytes: int) -> int:
    """HBM bytes one call of the paged decode kernel (one layer) must move
    for rows of ``lengths`` resident tokens: each row's pages of K and V at
    the pool's element size, their per-page scales when the pool is int8
    (one float32 per KV head, K and V), the bf16 query in, the float32
    context out and the row's block-table entries."""
    KV, D, H = dims.kv_heads, dims.head_dim, dims.heads
    total = 0
    for n in lengths:
        p = pages_for(n, page_size)
        total += 2 * p * page_size * KV * D * kv_bytes
        if kv_bytes == 1:
            total += 2 * p * KV * 4
        total += H * D * 2 + H * D * 4 + p * 4
    return total


def paged_attention_flops(dims, lengths: Iterable[int]) -> int:
    """q.k and p.v of one call (one layer): 4 * H * D per resident token."""
    return sum(4 * dims.heads * dims.head_dim * int(n) for n in lengths)


def least_time(flops: float, nbytes: float, pk: Peaks):
    """(seconds, bound): the larger of flops at the bf16 peak and bytes at
    the HBM bandwidth, and which of the two it is."""
    tc, tm = flops / pk.bf16_flops, nbytes / pk.hbm_bw
    return (tc, "compute") if tc >= tm else (tm, "memory")


# ------------------------------------------------------------- whole steps
# The block's ``Dims`` (bench/blocks/<block>.py) gives the counts that
# depend on its layers: weights per token, and attention over a context.
def token_flops(dims) -> int:
    """Matrix-product operations per token through every layer (no
    attention scores, no head)."""
    return 2 * dims.token_matmul_params()


def head_flops(dims) -> int:
    return 2 * dims.d * dims.vocab


def decode_step_flops(dims, lengths: Sequence[int]) -> int:
    """One decode step of live rows at ``lengths`` (tokens resident after
    the step's append): layers, head and attention over the context."""
    per_row = token_flops(dims) + head_flops(dims)
    return (len(lengths) * per_row
            + sum(dims.context_flops(n) for n in lengths))


def prefill_flops(dims, prompt_lengths: Sequence[int]) -> int:
    """Prefill of real prompt tokens: layers for every token, causal
    attention and the head at the last."""
    total = 0
    for n in prompt_lengths:
        n = int(n)
        total += n * token_flops(dims) + head_flops(dims)
        total += dims.prompt_context_flops(n)
    return total
