"""Device-side tensor-/expert-parallel decode math (ISSUE 10).

The sharded serving program is *shard-explicit single-jit*: one jit trace
contains an explicit loop over the mesh's model axis, and each iteration
computes exactly what one device computes from its local shard — the
paged-attention kernel reads only the local KV-head slice of the pool, the
MoE expert einsums read only the local expert slice. The collectives lower
to canonical-device-order concatenation, which is exact (no cross-device
float reduction ever happens), so sharded execution is bit-identical to
single-device **by construction**:

* per-KV-head locality (tp): every op in both attention paths treats the
  KV-head axis as a batch axis — q·k reduces over D per head, the online
  softmax (paged kernel) and the plain softmax (contiguous path) normalize
  per (kv_head, group) lane, and both lay q out as contiguous
  ``(KV, H/KV)`` groups — so computing heads in tp contiguous chunks and
  concatenating the contexts equals computing them at once; the full
  ``wo`` projection then runs on the gathered tensor unchanged.
* expert-as-batch (ep): the decode MoE einsums (``bsd,edf->ebsf`` and
  ``ebsf,efd->ebsd``) treat E as a pure batch axis, so per-shard expert
  slices concatenated along E equal the full einsum and the gate-weighted
  combine (``ebsd,bse->bsd``) runs on the gathered full-E tensor with
  exact 0.0 gates for unselected experts.

On a real mesh the loop body is what each device executes with the pool's
KV axis (and the experts' E axis) device-local — ``serve.shard`` supplies
the partition specs — and :func:`all_gather` is the wire collective.
tests/test_shard_serve.py asserts per-token bit-identity; the CI mesh8 job
re-runs the suite on a forced 8-device host platform.
"""
from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp


def shard_slice(x, axis: int, shard: int, n: int):
    """The local ``shard``-of-``n`` slice of ``x`` along ``axis`` (equal
    contiguous chunks; ``x.shape[axis]`` must divide by ``n``)."""
    size = x.shape[axis]
    assert size % n == 0, (size, n, axis)
    per = size // n
    return jax.lax.slice_in_dim(x, shard * per, (shard + 1) * per, axis=axis)


def all_gather(parts: List, axis: int):
    """The activation all-gather, lowered to canonical-device-order
    concatenation — exact, which is the whole bit-identity argument. Once
    shards live on their own devices this is the one per-step wire
    collective (the plan's ``noc_acts`` decision prices it)."""
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=axis)


def sharded_paged_attention(q, pk, pv, block_table, lengths, tp: int, *,
                            softcap: float = 0.0,
                            k_scale: Optional[jnp.ndarray] = None,
                            v_scale: Optional[jnp.ndarray] = None):
    """Paged decode attention over tp local KV shards, contexts gathered.

    q (B,1,H,D); pk/pv (P, page_size, KV, D); scales (P, KV). Each shard s
    runs the unmodified paged kernel on KV-head slice s and the matching
    contiguous q-head group — reading ONLY its local 1/tp of the pool —
    then head contexts are all-gathered for the full output projection.
    """
    from repro.kernels import ops as _ops   # deferred: keep import light

    if tp <= 1:
        kw = {} if k_scale is None else dict(k_scale=k_scale,
                                             v_scale=v_scale)
        return _ops.paged_attention(q, pk, pv, block_table, lengths,
                                    softcap=softcap, **kw)
    parts = []
    for s in range(tp):
        kw = {}
        if k_scale is not None:
            kw = dict(k_scale=shard_slice(k_scale, 1, s, tp),
                      v_scale=shard_slice(v_scale, 1, s, tp))
        parts.append(_ops.paged_attention(
            shard_slice(q, 2, s, tp),
            shard_slice(pk, 2, s, tp), shard_slice(pv, 2, s, tp),
            block_table, lengths, softcap=softcap, **kw))
    return all_gather(parts, axis=2)


def sharded_decode_attention(q, k_cache, v_cache, valid_mask, cfg, tp: int):
    """Contiguous-path decode attention (``layers.decode_attention``) over
    tp KV-head shards — the ring/local-window analogue of
    :func:`sharded_paged_attention`, so tp plans shard every attention
    kind, not just the paged pool."""
    from repro.models import layers

    if tp <= 1:
        return layers.decode_attention(q, k_cache, v_cache, valid_mask, cfg)
    parts = [layers.decode_attention(
        shard_slice(q, 2, s, tp),
        shard_slice(k_cache, 2, s, tp), shard_slice(v_cache, 2, s, tp),
        valid_mask, cfg) for s in range(tp)]
    return all_gather(parts, axis=2)


def sharded_expert_mlp(x, wg, wu, wd, *, act, cast, ep: int,
                       accum_dtype, compute_dtype):
    """The decode-time dense-all-experts MLP over ep expert shards.

    x (B,S,d); wg/wu (E,d,f); wd (E,f,d). Shard s computes the einsums for
    its contiguous E/ep expert slice only — the weights a real EP device
    holds — and the full-E activation is gathered along the (batch) expert
    axis for the caller's gate-weighted combine. Returns out (E,B,S,d).
    """
    E = wg.shape[0]
    assert E % ep == 0, (E, ep)
    chunks = []
    for s in range(ep):
        g = jnp.einsum("bsd,edf->ebsf", x, cast(shard_slice(wg, 0, s, ep)),
                       preferred_element_type=accum_dtype)
        u = jnp.einsum("bsd,edf->ebsf", x, cast(shard_slice(wu, 0, s, ep)),
                       preferred_element_type=accum_dtype)
        h = (act(g) * u).astype(compute_dtype)
        chunks.append(jnp.einsum("ebsf,efd->ebsd", h,
                                 cast(shard_slice(wd, 0, s, ep)),
                                 preferred_element_type=accum_dtype))
    return all_gather(chunks, axis=0)
