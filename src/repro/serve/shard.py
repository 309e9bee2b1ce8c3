"""Mesh-sharded serving (ISSUE 10): the serve mesh + distributed page pool.

Eyeriss v2's hierarchical mesh reconfigures the NoC per data type to match
each data type's reuse; this module applies the same move at cluster
scale. The ``ServePlan``'s mesh resolution stage (``core.plan``) freezes
the parallelism — tp shards attention KV heads, ep shards the MoE expert
axis — and one ``hmmesh.Mode`` per data type:

=============  ====================  =======================================
data type      NoC mode              why
=============  ====================  =======================================
weights        BROADCAST             decode is weight-stream bound; a
                                     sharded store would re-gather onto the
                                     critical path every step
KV pages       GROUPED_MC (local)    attention is per-KV-head local: each
                                     device streams only its 1/tp slice,
                                     zero collective bytes
activations    UNICAST→all-gather    head contexts are produced as unique
                                     1/tp slices and gathered full-width —
                                     token-sized, the only per-step traffic
experts        INTERLEAVED_MC        the expert axis is a batch axis in the
                                     decode einsums; E/ep weights resident
                                     per device, combine on the gathered
                                     full-E tensor
=============  ====================  =======================================

This module owns the host side: :class:`ServeMesh` (the resolved mesh),
:class:`ShardedPagePool` (per-device
``PageAllocator``\\ s in lockstep over one distributed address space — the
block table), partition specs that subsume what ``launch/cell``'s planner
chose for the launch path, and the analytic collective accounting the
scheduler publishes under the ``collective`` trace category. The device
side — per-shard kernels and the exact concat collectives that make
sharded execution bit-identical to single-device — lives in
``sharding.tensor_parallel``. DESIGN.md §17 carries the full argument.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence

from repro.core import hmmesh
from repro.serve import paging


# -------------------------------------------------------------- serve mesh
@dataclasses.dataclass(frozen=True)
class ServeMesh:
    """The resolved serving mesh: ``tp`` × ``ep`` shards. The sharded
    program is shard-explicit math inside one jit (``sharding.
    tensor_parallel``), so today every shard runs on the one device that
    holds the weights and the pool; :func:`sharding_stats` reports where
    they actually live."""
    tp: int = 1
    ep: int = 1

    @classmethod
    def from_plan(cls, plan) -> "ServeMesh":
        return cls(tp=getattr(plan, "tp", 1) or 1,
                   ep=getattr(plan, "ep", 1) or 1)

    @property
    def devices(self) -> int:
        return self.tp * self.ep

    @property
    def trivial(self) -> bool:
        return self.devices == 1

    def describe(self) -> str:
        if self.trivial:
            return "tp=1 ep=1 (1 device)"
        return (f"tp={self.tp} ep={self.ep} ({self.devices} shards emulated "
                "inside one program on one device)")


def devices_of(tree) -> List[str]:
    """The devices that hold the arrays of ``tree``, sorted."""
    import jax
    return sorted({str(d) for x in jax.tree.leaves(tree)
                   for d in x.devices()})


# --------------------------------------------------------- partition specs
def partition_specs(plan) -> Dict[str, Dict]:
    """Per-data-type placement, subsuming the ``launch/cell`` sharding
    planner into the frozen plan: the same ``hmmesh.Mode`` vocabulary
    ``core.planner``/``sharding.autoshard`` used for the launch path, now
    read off the ServePlan's mesh decisions. Each entry names the mode and
    the ``jax.sharding.PartitionSpec`` that would realize it on a
    ``("ep", "tp")`` device mesh (KV pools are (P, page_size, KV, D):
    head axis 2 shards over tp; expert weights are (E, d, f): expert axis
    0 shards over ep; everything else replicates)."""
    from jax.sharding import PartitionSpec as P
    tp = getattr(plan, "tp", 1) or 1
    ep = getattr(plan, "ep", 1) or 1
    return {
        "weights": {"mode": hmmesh.Mode.BROADCAST, "spec": P()},
        "kv_pages": {"mode": hmmesh.Mode.GROUPED_MC,
                     "spec": P(None, None, "tp" if tp > 1 else None, None)},
        "activations": {"mode": hmmesh.Mode.BROADCAST, "spec": P(),
                        "note": "produced UNICAST per shard, all-gathered"},
        "experts": {"mode": hmmesh.Mode.INTERLEAVED_MC,
                    "spec": P("ep" if ep > 1 else None, None, None)},
    }


# ------------------------------------------------------ sharded page pool
# PageAllocator methods that mutate allocator state: applied to every
# shard in lockstep, results asserted identical (the distributed half of
# the pool-invariant audit).
_MUTATING = ("grow", "ensure", "set_length", "free", "adopt_prefix",
             "register_prefix", "fork_chain", "commit_fork", "abort_fork",
             "cow_page")
# Read-only queries: any shard answers (metadata is replicated); shard 0
# is the canonical reader.
_READONLY = ("available", "pages_of", "table", "live_requests", "pages_for",
             "refcount", "snapshot", "fingerprint", "match_prefix",
             "shared_pages_in", "block_table_rows", "num_pages", "page_size",
             "in_use")


class ShardedPagePool:
    """``tp`` per-device :class:`~repro.serve.paging.PageAllocator`\\ s over
    ONE distributed address space.

    Page *frames* are device-local — frame ``p`` on device ``d`` stores the
    local 1/tp KV-head slice of logical page ``p`` — while the allocation
    metadata (free lists, refcounts, the chained prefix index, block
    tables) is replicated: every mutating call applies to all shards and
    must return the same result on each (asserted — lockstep is the
    invariant that makes one block-table row resolve to valid local frames
    on every device). CoW prefix sharing and the degradation ladder
    therefore run per device pool with zero cross-device coordination, and
    the scheduler uses this class exactly like a single ``PageAllocator``.
    """

    def __init__(self, num_pages: int, page_size: int, shards: int):
        assert shards >= 1, shards
        self.shards = tuple(paging.PageAllocator(num_pages, page_size)
                            for _ in range(shards))

    def __getattr__(self, name: str):
        if name.startswith("_") or name == "shards":
            raise AttributeError(name)
        if name in _MUTATING:
            def lockstep(*a, __name=name, **kw):
                results = [getattr(s, __name)(*a, **kw) for s in self.shards]
                first = results[0]
                assert all(r == first for r in results[1:]), (
                    f"sharded pool divergence in {__name}: {results} — "
                    "per-device allocators fell out of lockstep")
                return first
            return lockstep
        if name in _READONLY:
            return getattr(self.shards[0], name)
        raise AttributeError(name)

    # ----------------------------------------------------------- telemetry
    def lockstep_divergence(self) -> int:
        """Shards whose full snapshot differs from shard 0 (0 = healthy).
        Published as the ``shard_lockstep_divergence`` gauge and checked by
        the per-window pool audit."""
        fps = [s.fingerprint() for s in self.shards]
        return sum(1 for fp in fps[1:] if fp != fps[0])

    def observe(self, metrics) -> None:
        """Publish the canonical pool gauges plus the shard-tagged extras
        (max/min per-device occupancy and the lockstep divergence count)."""
        self.shards[0].observe(metrics)
        used = [s.in_use for s in self.shards]
        metrics.gauge("shard_pages_used_max", max(used))
        metrics.gauge("shard_pages_used_min", min(used))
        metrics.gauge("shard_lockstep_divergence",
                      self.lockstep_divergence())

    def stats(self) -> Dict[str, float]:
        st = self.shards[0].stats()
        st["shards"] = len(self.shards)
        st["lockstep_divergence"] = self.lockstep_divergence()
        return st


def make_pool(plan):
    """The plan's page pool: a :class:`ShardedPagePool` (one allocator per
    tp device) for sharded paged plans, else a plain PageAllocator."""
    tp = getattr(plan, "tp", 1) or 1
    if getattr(plan, "sharded", False) and plan.paged and tp > 1:
        return ShardedPagePool(plan.num_pages, plan.page_size, shards=tp)
    return paging.PageAllocator(plan.num_pages, plan.page_size)


# ------------------------------------------------- collective accounting
def chunk_collectives(plan, *, steps: int, tokens: int) -> Dict[str, int]:
    """Analytic collective traffic for one decode chunk, from the plan's
    mesh decisions: one head-context all-gather per attention layer per
    step (tp), one expert gather per MoE layer per step (ep). The
    scheduler counts these under the frozen ``collective_*`` metric keys
    and traces them in the ``collective`` category — the measurement half
    of drift detection for the mesh decision."""
    dec = {d.name: d for d in getattr(plan, "decisions", ())}
    mesh = dec.get("mesh")
    if mesh is None:
        return {}
    acts = dec.get("noc_acts")
    n_attn = int(acts.numbers.get("attn_layers", 0)) if acts else 0
    n_moe = int(dec["noc_experts"].numbers.get("moe_layers", 0)) \
        if "noc_experts" in dec else 0
    ops_per_step = (n_attn if plan.tp > 1 else 0) \
        + (n_moe if plan.ep > 1 else 0)
    per_tok = int(mesh.numbers.get("allgather_bytes_per_token", 0))
    return {"collective_ops": int(steps) * ops_per_step,
            "collective_allgather_bytes": per_tok * int(tokens)}


def per_device_kv_bytes(cfg, plan) -> int:
    """Bytes of the paged KV pool ONE tp device holds (its local 1/tp
    KV-head slice of every page frame). Both the fp payload and the int8
    per-(page, head) scales are linear in the head axis, and plan
    resolution enforced tp | num_kv_heads, so the division is exact — the
    ``sharded-pool-bytes-per-device`` perf gate checks measured bytes
    against this."""
    from repro.serve import kvcache
    if not plan.paged:
        return 0
    total = kvcache.kv_page_bytes(cfg, plan.page_size, plan.kv_quant) \
        * plan.num_pages
    return total // (plan.tp if plan.tp > 1 else 1)


def sharding_stats(cfg, plan, pool=None, params=None,
                   pool_devices: Optional[Sequence[str]] = None) -> Dict:
    """One report block for examples/bench: the resolved mesh, per-device
    pool bytes, (when a pool is passed) live shard occupancy, and where
    the weights (``params``) and the KV pool (``pool_devices``, recorded
    by the scheduler) actually live."""
    from repro.serve import kvcache
    mesh = ServeMesh.from_plan(plan)
    single = kvcache.kv_page_bytes(cfg, plan.page_size, plan.kv_quant) \
        * plan.num_pages if plan.paged else 0
    out = {"tp": mesh.tp, "ep": mesh.ep, "devices": mesh.devices,
           "weights_devices": devices_of(params) if params is not None
           else [],
           "pool_devices": list(pool_devices or []),
           "kv_bytes_single_device": single,
           "kv_bytes_per_device": per_device_kv_bytes(cfg, plan)}
    if isinstance(pool, ShardedPagePool):
        out["shards"] = len(pool.shards)
        out["shard_pages_used"] = [s.in_use for s in pool.shards]
        out["lockstep_divergence"] = pool.lockstep_divergence()
    return out
