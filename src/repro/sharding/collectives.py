"""Hierarchical collectives on the (pod, data, model) mesh.

The paper's two-level NoC (all-to-all inside a cluster, mesh between clusters)
motivates the classic hierarchical all-reduce: reduce-scatter inside the pod,
all-reduce the shards across pods, all-gather inside the pod. Inter-pod traffic
drops by the intra-pod fan-in — the HM-NoC scaling argument (§III-D).

Implemented with shard_map + jax.lax collectives; validated in tests against a
flat psum.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def hierarchical_psum(x, pod_axis: str = "pod", inner_axis: str = "data"):
    """All-reduce over (pod × inner) as RS(inner) → AR(pod) → AG(inner).

    Equivalent to ``jax.lax.psum(x, (pod_axis, inner_axis))`` but inter-pod
    traffic carries only 1/inner of the payload. Call inside shard_map."""
    n_inner = jax.lax.axis_size(inner_axis)
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % n_inner
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    shard = jax.lax.psum_scatter(flat, inner_axis, scatter_dimension=0,
                                 tiled=True)
    shard = jax.lax.psum(shard, pod_axis)
    out = jax.lax.all_gather(shard, inner_axis, axis=0, tiled=True)
    if pad:
        out = out[:-pad]
    return out.reshape(x.shape)


def allreduce_stacked(mesh: Mesh, x):
    """Sum per-replica values stacked on dim 0 over the data-parallel axes.

    x: (n_dp, ...) sharded over ('pod','data'); returns the (replicated) sum.
    Uses the hierarchical schedule when a pod axis exists.
    """
    has_pod = "pod" in mesh.axis_names
    axes = ("pod", "data") if has_pod else ("data",)

    def body(xs):                     # xs: (1, ...) local slice
        v = xs[0]
        if has_pod:
            return hierarchical_psum(v, "pod", "data")
        return jax.lax.psum(v, "data")

    return jax.shard_map(body, mesh=mesh, in_specs=P(axes), out_specs=P(),
                         check_vma=False)(x)


def ring_allgather(x, axis_name: str):
    """All-gather via (n-1) collective-permutes — an explicit ring schedule
    whose hops XLA can overlap with compute. Call inside shard_map; gathers
    along a new leading dim ordered by source index."""
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    chunks = [x]
    cur = x
    for _ in range(n - 1):
        cur = jax.lax.ppermute(cur, axis_name, perm)
        chunks.append(cur)
    stacked = jnp.stack(chunks)       # position j holds data from (idx - j) % n
    src = (idx - jnp.arange(n)) % n
    out = jnp.zeros_like(stacked)
    out = out.at[src].set(stacked)
    return out
