"""Mesh-sharded serving (ISSUE 10): mesh parsing + plan-time validation,
the plan's mesh/NoC-mode/pool Decisions, the ShardedPagePool lockstep
invariant, per-device pool byte accounting, acceptance-adaptive spec_k,
golden sharded-plan snapshots, and the tentpole acceptance — sharded
``LLM.stream()`` bit-identical to single-device per emitted token (tp=2
attention sharding and ep=4 expert sharding; re-asserted on a forced
8-device host mesh in a subprocess, the CI mesh8 configuration)."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.core import hmmesh, plan as plan_lib
from repro.serve import shard
from repro.serve.facade import LLM
from repro.serve.paging import PageAllocator

GOLDEN = os.path.join(os.path.dirname(__file__), os.pardir, "scripts",
                      "golden_plans.json")

PLAN_KW = dict(hbm_budget_bytes=1 << 30, expected_batch=3,
               expected_len_dist={"mean": 10, "max": 64}, page_size=4,
               sync_every=4)


def _params(cfg, seed=0):
    from repro.models import transformer as tfm
    return tfm.init_params(jax.random.PRNGKey(seed), cfg)


# ---------------------------------------------------------- mesh parsing
def test_parse_mesh_forms():
    assert plan_lib.parse_mesh(None) == (1, 1)
    assert plan_lib.parse_mesh("") == (1, 1)
    assert plan_lib.parse_mesh({}) == (1, 1)
    assert plan_lib.parse_mesh("tp=2,ep=4") == (2, 4)
    assert plan_lib.parse_mesh("ep=4,tp=2") == (2, 4)
    assert plan_lib.parse_mesh("tp=2") == (2, 1)
    assert plan_lib.parse_mesh({"ep": 4}) == (1, 4)
    assert plan_lib.parse_mesh((2, 4)) == (2, 4)
    with pytest.raises(ValueError, match="mesh"):
        plan_lib.parse_mesh("tp=2,dp=4")
    with pytest.raises(ValueError, match="mesh"):
        plan_lib.parse_mesh("tp2")
    with pytest.raises(ValueError, match=">= 1"):
        plan_lib.parse_mesh("tp=0")


def test_mesh_validation_raises_at_plan_time():
    cfg = get_config("gemma2-2b-reduced")       # 2 KV heads, no MoE
    with pytest.raises(ValueError, match="num_kv_heads"):
        plan_lib.plan_serve(cfg, mesh="tp=3", **PLAN_KW)
    with pytest.raises(ValueError, match="no\nexperts|no experts"):
        plan_lib.plan_serve(cfg, mesh="ep=2", **PLAN_KW)
    moe = get_config("mixtral-8x7b-reduced")    # 4 experts
    with pytest.raises(ValueError, match="num_experts"):
        plan_lib.plan_serve(moe, mesh="ep=3", **PLAN_KW)
    rec = get_config("mamba2-130m-reduced")     # recurrent: no head axis
    with pytest.raises(ValueError, match="recurrent"):
        plan_lib.plan_serve(rec, mesh="tp=2", **PLAN_KW)
    with pytest.raises(ValueError, match="drain engine"):
        plan_lib._resolve(
            cfg, cfg.name, 2, 64, mean_len=10, page_size=4, num_pages=None,
            attn_path="paged", share_prefix=None, kv_quant=None,
            sync_every=4, sparsity_stats=None, drain_only=True,
            mesh="tp=2")


# --------------------------------------------------- plan mesh decisions
def test_plan_explain_renders_mesh_and_noc_modes():
    cfg = get_config("mixtral-8x7b-reduced")
    plan = plan_lib.plan_serve(cfg, mesh="tp=2,ep=2", **PLAN_KW)
    assert (plan.tp, plan.ep) == (2, 2)
    assert plan.sharded and plan.mesh_devices == 4
    names = [d.name for d in plan.decisions]
    # the single-device decision list is a strict prefix: mesh-less plans
    # keep the pinned 8-name list (test_plan.py), sharded plans append
    assert names[:8] == ["capacity", "matmul", "mlp", "attention",
                        "kv_quant", "spec", "degrade", "prefill"]
    assert "mesh" in names and "noc_weights" in names
    assert "noc_kv" in names and "noc_acts" in names
    assert "noc_experts" in names           # ep>1 on an MoE arch
    rep = plan.explain()
    assert "mesh=tp2xep2" in rep
    assert "[bound: collective]" in rep     # the fourth roofline bound
    assert str(hmmesh.Mode.BROADCAST.value) in rep \
        or "BROADCAST" in rep               # weights stay replicated
    mesh_d = {d.name: d for d in plan.decisions}["mesh"]
    assert mesh_d.numbers["devices"] == 4
    assert mesh_d.numbers["allgather_bytes_per_token"] > 0


def test_unsharded_plan_has_no_mesh_decisions():
    cfg = get_config("gemma2-2b-reduced")
    plan = plan_lib.plan_serve(cfg, **PLAN_KW)
    assert not plan.sharded and plan.tp == plan.ep == 1
    assert [d.name for d in plan.decisions] == \
        ["capacity", "matmul", "mlp", "attention", "kv_quant", "spec",
         "degrade", "prefill"]
    assert "mesh" not in plan.explain()


def test_replan_never_re_meshes():
    cfg = get_config("gemma2-2b-reduced")
    base = plan_lib.plan_serve(cfg, mesh="tp=2", **PLAN_KW)
    swapped = plan_lib.replan_from_lengths(cfg, base, [20, 30, 40, 50] * 8)
    assert (swapped.tp, swapped.ep) == (base.tp, base.ep) == (2, 1)


# -------------------------------------------------------- partition specs
def test_partition_specs_subsume_launch_planner():
    from jax.sharding import PartitionSpec as P
    from repro.launch import cell
    cfg = get_config("mixtral-8x7b-reduced")
    plan = plan_lib.plan_serve(cfg, mesh="tp=2,ep=2", **PLAN_KW)
    specs = shard.partition_specs(plan)
    assert specs["weights"]["mode"] is hmmesh.Mode.BROADCAST
    assert specs["kv_pages"]["mode"] is hmmesh.Mode.GROUPED_MC
    assert specs["kv_pages"]["spec"] == P(None, None, "tp", None)
    assert specs["experts"]["mode"] is hmmesh.Mode.INTERLEAVED_MC
    assert specs["experts"]["spec"] == P("ep", None, None)
    # the launch path reads the same placement off the frozen plan
    assert cell.serve_partition_specs(plan) == specs


def test_serve_mesh_backing():
    mesh = shard.ServeMesh(tp=2, ep=4)
    assert mesh.devices == 8 and not mesh.trivial
    assert shard.ServeMesh().trivial
    # the shards are emulated in one program, whatever the host has
    assert "emulated inside one program on one device" in mesh.describe()
    assert shard.ServeMesh().describe() == "tp=1 ep=1 (1 device)"
    x = jax.device_put(jnp.zeros(3), jax.devices()[0])
    assert shard.devices_of({"a": x, "b": [x]}) == [str(jax.devices()[0])]


# ------------------------------------------------------ sharded page pool
def test_sharded_pool_lockstep_and_divergence():
    pool = shard.ShardedPagePool(8, 4, shards=2)
    assert pool.num_pages == 8 and pool.page_size == 4
    assert pool.ensure(0, 10)               # lockstep mutation on all shards
    assert pool.pages_of(0) == 3
    assert all(s.pages_of(0) == 3 for s in pool.shards)
    pool.set_length(0, 10)
    assert pool.lockstep_divergence() == 0
    assert pool.stats()["shards"] == 2
    # out-of-band mutation of one shard IS divergence — the audit sees it
    pool.shards[1].ensure(99, 4)
    assert pool.lockstep_divergence() == 1
    # and the next lockstep call whose outcome differs across shards trips
    # the assertion: shard1 has one page fewer free, so a 5-page ensure
    # succeeds on shard0 but fails all-or-nothing on shard1
    with pytest.raises(AssertionError, match="lockstep"):
        pool.ensure(100, 20)


def test_sharded_pool_observe_publishes_shard_gauges():
    from repro.serve import telemetry
    pool = shard.ShardedPagePool(8, 4, shards=2)
    pool.ensure(0, 8)
    m = telemetry.MetricsRegistry()
    pool.observe(m)
    assert m.gauges["shard_pages_used_max"] == 2
    assert m.gauges["shard_pages_used_min"] == 2
    assert m.gauges["shard_lockstep_divergence"] == 0
    assert m.gauges["pages_used"] == 2      # canonical gauges still flow


def test_make_pool_dispatch():
    cfg = get_config("gemma2-2b-reduced")
    sharded = plan_lib.plan_serve(cfg, mesh="tp=2", **PLAN_KW)
    single = plan_lib.plan_serve(cfg, **PLAN_KW)
    assert isinstance(shard.make_pool(sharded), shard.ShardedPagePool)
    assert isinstance(shard.make_pool(single), PageAllocator)


def test_per_device_kv_bytes_exact_fraction():
    from repro.serve import kvcache
    cfg = get_config("gemma2-2b-reduced")
    plan = plan_lib.plan_serve(cfg, mesh="tp=2", **PLAN_KW)
    assert plan.paged
    total = kvcache.kv_page_bytes(cfg, plan.page_size, plan.kv_quant) \
        * plan.num_pages
    assert shard.per_device_kv_bytes(cfg, plan) * 2 == total  # exact 1/tp
    pool_d = {d.name: d for d in plan.decisions}["pool_shard"]
    assert pool_d.numbers["pool_bytes_per_device"] > 0


def test_chunk_collectives_counts():
    cfg = get_config("mixtral-8x7b-reduced")
    plan = plan_lib.plan_serve(cfg, mesh="tp=2,ep=2", **PLAN_KW)
    cc = shard.chunk_collectives(plan, steps=4, tokens=6)
    assert cc["collective_ops"] > 0
    assert cc["collective_allgather_bytes"] == 6 * {
        d.name: d for d in plan.decisions
    }["mesh"].numbers["allgather_bytes_per_token"]
    single = plan_lib.plan_serve(cfg, **PLAN_KW)
    assert shard.chunk_collectives(single, steps=4, tokens=6) == {}


# ------------------------------------------- acceptance-adaptive spec_k
SPEC_ARCH = "qwen2.5-3b-reduced"            # all-global: spec-eligible
SPEC_KW = dict(hbm_budget_bytes=1 << 30, expected_batch=2,
               expected_len_dist={"mean": 24, "max": 64}, page_size=8,
               attn_path="paged")


def test_replan_spec_k_steps_down_on_low_acceptance():
    cfg = get_config(SPEC_ARCH)
    base = plan_lib.plan_serve(cfg, **SPEC_KW, spec_k=4)
    assert base.spec_k == 4
    low = plan_lib.replan_spec_k(cfg, base, drafted_tokens=400,
                                 accepted_tokens=40)
    assert low.spec_k < base.spec_k         # drafts miss: k steps down
    d = {d.name: d for d in low.decisions}["spec"]
    assert "measured" in d.why
    assert d.numbers["alpha_measured"] < 0.5


def test_replan_spec_k_steps_up_and_guards():
    cfg = get_config(SPEC_ARCH)
    base = plan_lib.plan_serve(cfg, **SPEC_KW, spec_k=4)
    high = plan_lib.replan_spec_k(cfg, base, drafted_tokens=400,
                                  accepted_tokens=340)
    assert high.spec_k >= base.spec_k       # drafts hit: k grows (or holds)
    # too few samples: unchanged object, no decision churn
    assert plan_lib.replan_spec_k(cfg, base, drafted_tokens=10,
                                  accepted_tokens=2) is base
    # speculation off: nothing to adapt
    off = plan_lib.plan_serve(cfg, **SPEC_KW)
    if off.spec_k == 0:
        assert plan_lib.replan_spec_k(cfg, off, drafted_tokens=400,
                                      accepted_tokens=40) is off


# -------------------------------------------------- golden sharded plans
def test_golden_sharded_plan_snapshot_stable():
    """snapshot_sharded_plan for both ISSUE-10 configs × both mesh shapes
    matches scripts/golden_plans.json["__sharded__"] — the same gate
    perf_guard enforces in CI (sharded-plan-snapshot-stable)."""
    golden = json.load(open(GOLDEN))["__sharded__"]
    assert sorted(golden) == sorted(plan_lib.SHARDED_SNAPSHOT_CONFIGS)
    for arch in plan_lib.SHARDED_SNAPSHOT_CONFIGS:
        assert sorted(golden[arch]) \
            == sorted(plan_lib.SHARDED_SNAPSHOT_MESHES)
        for mesh in plan_lib.SHARDED_SNAPSHOT_MESHES:
            got = json.loads(
                plan_lib.snapshot_sharded_plan(arch, mesh).to_json())
            assert got == golden[arch][mesh], \
                f"sharded plan drift for {arch} @ {mesh}"


# --------------------------------------------- tentpole: bit-identity e2e
def _stream_outputs(cfg, params, plan, reqs, seed=42):
    llm = LLM(cfg, params, plan)
    done = llm.stream(reqs, rng=jax.random.PRNGKey(seed))
    return [r.out for r in done], llm


def test_stream_tp2_bit_identical_to_single_device():
    cfg = get_config("gemma2-2b-reduced")
    params = _params(cfg)
    reqs = [([5, 7, 11], 12), ([3, 2, 9, 4], 10)]
    p1 = plan_lib.plan_serve(cfg, **PLAN_KW)
    p2 = plan_lib.plan_serve(cfg, mesh="tp=2", **PLAN_KW)
    assert p1.paged and p2.paged
    o1, _ = _stream_outputs(cfg, params, p1, reqs)
    o2, llm2 = _stream_outputs(cfg, params, p2, reqs)
    assert o1 == o2                         # per-token bit-identity
    rep = llm2.sharding_report()
    assert rep["tp"] == 2 and rep["shards"] == 2
    # honest placement: two logical shards, weights and pool on one device
    assert rep["weights_devices"] == rep["pool_devices"] \
        == [str(jax.devices()[0])]
    assert rep["lockstep_divergence"] == 0
    assert rep["kv_bytes_per_device"] * 2 == rep["kv_bytes_single_device"]
    snap = llm2.telemetry().metrics.snapshot()
    assert snap.counters["collective_allgather_bytes"] > 0
    assert snap.gauges["shard_lockstep_divergence"] == 0
    cats = {e.cat for e in llm2.telemetry().tracer.events}
    assert "collective" in cats


def test_stream_ep4_bit_identical_to_single_device():
    cfg = get_config("mixtral-8x7b-reduced")
    params = _params(cfg, seed=1)
    reqs = [([5, 7, 11], 10), ([3, 2, 9, 4], 8)]
    p1 = plan_lib.plan_serve(cfg, **PLAN_KW)
    p2 = plan_lib.plan_serve(cfg, mesh="ep=4", **PLAN_KW)
    o1, _ = _stream_outputs(cfg, params, p1, reqs, seed=7)
    o2, llm2 = _stream_outputs(cfg, params, p2, reqs, seed=7)
    assert o1 == o2
    snap = llm2.telemetry().metrics.snapshot()
    assert snap.counters["collective_ops"] > 0      # expert gathers counted


# ------------------------------------- forced 8-device host mesh (mesh8)
_MESH8 = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
from repro.configs import get_config
from repro.core import plan as plan_lib
from repro.models import transformer as tfm
from repro.serve.facade import LLM

assert jax.device_count() == 8
KW = dict(hbm_budget_bytes=1 << 30, expected_batch=3,
          expected_len_dist={"mean": 10, "max": 64}, page_size=4,
          sync_every=4)
for arch, mesh in (("gemma2-2b-reduced", "tp=2"),
                   ("mixtral-8x7b-reduced", "ep=4")):
    cfg = get_config(arch)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    reqs = [([5, 7, 11], 8), ([3, 2, 9, 4], 6)]
    o1 = [r.out for r in LLM(cfg, params, plan_lib.plan_serve(cfg, **KW))
          .stream(reqs, rng=jax.random.PRNGKey(3))]
    plan = plan_lib.plan_serve(cfg, mesh=mesh, **KW)
    llm = LLM(cfg, params, plan)
    o2 = [r.out for r in llm.stream(reqs, rng=jax.random.PRNGKey(3))]
    assert o1 == o2, (arch, mesh, o1, o2)
    # eight devices exist, yet the emulated shards all run on device 0
    rep = llm.sharding_report()
    assert rep["pool_devices"] == rep["weights_devices"] \
        == [str(jax.devices()[0])], rep
print("MESH8_OK")
"""


def test_sharded_stream_bit_identical_on_forced_8_device_mesh():
    """On a forced 8-device host platform sharded stream() stays
    bit-identical to single-device, and the report says that the pool and
    the weights still sit on one device."""
    r = subprocess.run([sys.executable, "-c", _MESH8],
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr
    assert "MESH8_OK" in r.stdout
