"""Wall-clock-free perf regression guard (ISSUE 2 + ISSUE 3 CI tooling).

Runs after the sparse-decode benchmark in CI and fails the build when the
fused bcsc_mlp megakernel stops beating the two-call path on the
deterministic cost proxies — grid steps and HBM-bytes-moved — which hold in
interpret mode on CPU exactly as they do compiled on TPU (they count work,
not time). Wall-clock tokens/sec is *reported* by the benchmark but never
gated here: CI runners are too noisy for a timing gate.

Checks:
  1. fused grid steps  <= two-call grid steps        (within this run)
  2. fused HBM bytes   <  two-call HBM bytes         (strict, within run)
  3. fused HBM bytes   <  PR 1 recorded baseline     (strict, cross-PR)
  4. fused launches    <  two-call launches
  5. the batch-1 e2e ratio and per-phase breakdown are present (the
     benchmark actually measured what the JSON claims)
  6. paged KV (ISSUE 3): paged cache bytes strictly below the dense slot
     cache at 50% mean occupancy, and the paged decode kernel's blocks
     within ceil(ceil(len/page_size) / pages_per_block) per row (its block
     loop's trip count) and below the padded grid
  7. arrivals (ISSUE 3): continuous batching beats the drain-the-chunk
     baseline on goodput at high length variance — gated because both sides
     run on the deterministic virtual step clock, not wall time
  8. page-native KV (ISSUE 4): the paged prefill path's per-layer KV buffer
     is strictly smaller than the scatter path's dense (B, cache_len)
     transient (byte accounting — the allocation the refactor deleted);
     shared-prefix workloads admit strictly MORE concurrent requests than
     unshared admission at the same pool size, peak at fewer pages, and
     produce identical outputs; int8 KV pages record a quantized-vs-fp
     byte ratio strictly below 1
  9. chaos/overload (ISSUE 6): the degradation ladder sheds no more than
     admission-control-only shedding (and shed rate stays <= 0.5), degraded
     goodput stays within 5% of (in practice above) the shed-only floor,
     and the injected-fault run keeps every request terminal with a clean
     pool audit and bit-identical surviving tokens — all on the virtual
     step clock
 10. replica failover (ISSUE 7): killing 1 of N replicas mid-sweep leaves
     non-migrated survivors bit-identical and every rid terminal, fleet
     goodput holds >= 0.9x the fault-free run (the recompute tax bound),
     and prefix-affinity placement achieves strictly more CoW page sharing
     on shared-prompt traffic than affinity-free placement
 11. plan snapshot (ISSUE 5): the resolved ServePlans for the seed configs
     (core.plan.snapshot_plan — fixed budget/shape inputs) match
     scripts/golden_plans.json exactly. Any drift in a dispatch decision,
     threshold, pool size, or bound rationale fails CI until the golden
     file is regenerated deliberately:
        PYTHONPATH=src python -c "import json; from repro.core import plan;
        json.dump({a: plan.snapshot_plan(a).as_dict() for a in
        plan.SNAPSHOT_CONFIGS}, open('scripts/golden_plans.json','w'),
        indent=2, sort_keys=True)"
 12. speculative decode (ISSUE 9): batch-1 draft/verify speculation retires
     >= 1.5x tokens per decode dispatch (one flattened k-position verify
     per round vs one step per baseline token — the deterministic-clock
     speedup; wall seconds are reported but never gated), and the greedy
     token streams are bit-identical to the sequential baseline at every
     benchmarked batch size
 13. mesh-sharded serving (ISSUE 10): sharded stream() (tp attention
     shards, ep expert shards) emits token streams bit-identical to
     single-device, the per-device KV pool holds <= 1/tp of the
     single-device pool plus one page frame of rounding slack, and the
     sharded ServePlans for mixtral-8x7b / llama4-maverick-400b-a17b at
     both canonical mesh shapes match golden_plans.json["__sharded__"]
     exactly. Regenerate the golden (deliberately) with:
        PYTHONPATH=src python -c "import json; from repro.core import plan;
        g = {a: plan.snapshot_plan(a).as_dict() for a in
             plan.SNAPSHOT_CONFIGS};
        g['__sharded__'] = {a: {m: plan.snapshot_sharded_plan(a, m)
            .as_dict() for m in plan.SHARDED_SNAPSHOT_MESHES}
            for a in plan.SHARDED_SNAPSHOT_CONFIGS};
        json.dump(g, open('scripts/golden_plans.json','w'), indent=2,
        sort_keys=True)"

    PYTHONPATH=src python scripts/perf_guard.py [BENCH_sparse_decode.json]
"""
from __future__ import annotations

import json
import os
import sys

# PR 1's two-call path at the benchmark config (qwen2.5-3b-reduced, 0.75
# block sparsity, bm=8, 16x16 blocks): every projection kernel walked the
# padded stack capacity and round-tripped the hidden activation through HBM.
# These are the mlp_proxy "two_call" numbers for that packing — the recorded
# baseline the fused path must strictly beat.
PR1_TWO_CALL_HBM_BYTES = 99_072
PR1_TWO_CALL_GRID_STEPS = 96


def main(path: str = "BENCH_sparse_decode.json") -> int:
    data = json.load(open(path))
    mp = data["mlp_proxy"]
    fused, two = mp["fused"], mp["two_call"]
    failures = []

    def check(name, ok, detail):
        print(f"  [{'ok' if ok else 'FAIL'}] {name}: {detail}")
        if not ok:
            failures.append(name)

    print(f"perf guard on {path} "
          f"(arch {mp['arch']}, sparsity {mp['sparsity']})")
    check("grid-steps", fused["grid_steps"] <= two["grid_steps"],
          f"fused {fused['grid_steps']} <= two-call {two['grid_steps']}")
    check("hbm-bytes", fused["hbm_bytes"] < two["hbm_bytes"],
          f"fused {fused['hbm_bytes']} < two-call {two['hbm_bytes']}")
    check("hbm-bytes-vs-pr1", fused["hbm_bytes"] < PR1_TWO_CALL_HBM_BYTES,
          f"fused {fused['hbm_bytes']} < PR1 baseline "
          f"{PR1_TWO_CALL_HBM_BYTES}")
    check("grid-steps-vs-pr1",
          fused["grid_steps"] <= PR1_TWO_CALL_GRID_STEPS,
          f"fused {fused['grid_steps']} <= PR1 baseline "
          f"{PR1_TWO_CALL_GRID_STEPS}")
    check("kernel-launches",
          fused["kernel_launches"] < two["kernel_launches"],
          f"fused {fused['kernel_launches']} < two-call "
          f"{two['kernel_launches']}")

    pg = data.get("paged", {})
    if pg:
        check("paged-hbm-bytes", pg["paged_bytes"] < pg["dense_slot_bytes"],
              f"paged {pg['paged_bytes']} < dense slot "
              f"{pg['dense_slot_bytes']} at {pg['mean_occupancy']:.0%} "
              f"occupancy ({pg['bytes_ratio']:.2f}x)")
        # work_blocks comes from the kernel's own skip expression
        # (kernels.paged_attention.row_blocks — the trip count of its block
        # loop), ceil_blocks from core.dataflow's pages: a kernel-side skip
        # regression moves the left side and trips one of these
        check("paged-grid-steps", pg["work_blocks"] <= pg["ceil_blocks"],
              f"kernel blocks {pg['work_blocks']} <= spec "
              f"ceil(ceil(len/ps)/{pg['pages_per_block']}) sum "
              f"{pg['ceil_blocks']}")
        check("paged-skip-saves-steps",
              pg["work_blocks"] < pg["padded_grid_blocks"],
              f"kernel blocks {pg['work_blocks']} < padded grid "
              f"{pg['padded_grid_blocks']} (ragged rows must skip)")
    else:
        print("  [--] paged section absent; paged gates skipped")

    ar = data.get("arrivals", {})
    if ar:
        hv = ar["cases"]["high_variance"]
        check("continuous-beats-drain",
              hv["goodput_ratio"] > 1.0,
              f"scheduler/drain goodput x{hv['goodput_ratio']:.2f} at "
              f"variance x{ar['variance_ratio']:.0f} (virtual-step clock)")
        check("arrival-latency-reported",
              all(k in hv["scheduler"] for k in
                  ("latency_p50_steps", "latency_p99_steps")),
              f"p50 {hv['scheduler'].get('latency_p50_steps')} "
              f"p99 {hv['scheduler'].get('latency_p99_steps')}")
    else:
        print("  [--] arrivals section absent (--no-arrivals run); "
              "goodput gate skipped")

    sp = data.get("shared_prefix", {})
    if sp:
        pt = sp["prefill_transient"]
        check("paged-prefill-transient",
              pt["paged_path_bytes"] < pt["scatter_path_bytes"],
              f"page-native {pt['paged_path_bytes']} B (tier {pt['tier']}) "
              f"< scatter-path dense transient {pt['scatter_path_bytes']} B")
        sh, un = sp["shared"], sp["unshared"]
        check("shared-prefix-concurrency",
              sh["peak_live_rows"] > un["peak_live_rows"],
              f"shared admits {sh['peak_live_rows']} concurrent > unshared "
              f"{un['peak_live_rows']} at {sp['num_pages']} pages")
        check("shared-prefix-pages",
              sh["pages_peak"]["pages_used"] < un["pages_peak"]["pages_used"],
              f"shared peaks at {sh['pages_peak']['pages_used']} pages < "
              f"unshared {un['pages_peak']['pages_used']}")
        check("shared-prefix-outputs-identical",
              sp["outputs_identical"],
              "CoW sharing is output-transparent")
        kq = sp["kv_quant"]
        check("kv-quant-bytes-ratio",
              0.0 < kq["int8_vs_fp_ratio"] < 1.0,
              f"int8 pages {kq['int8_cache_bytes']} B / fp "
              f"{kq['fp_cache_bytes']} B = {kq['int8_vs_fp_ratio']:.2f}")
    else:
        print("  [--] shared_prefix section absent; page-native gates "
              "skipped")

    ch = data.get("chaos", {})
    if ch:
        so, la, fa = ch["shed_only"], ch["ladder"], ch["faulted"]
        check("shed-rate-bounded",
              la["shed_rate"] <= so["shed_rate"] and so["shed_rate"] <= 0.5,
              f"ladder {la['shed_rate']:.2f} <= shed-only "
              f"{so['shed_rate']:.2f} <= 0.5 of {ch['n_requests']} requests")
        check("degraded-goodput-floor",
              la["goodput_tokens_per_step"] >=
              0.95 * so["goodput_tokens_per_step"],
              f"ladder {la['goodput_tokens_per_step']:.3f} tok/step >= 0.95"
              f" x shed-only {so['goodput_tokens_per_step']:.3f} "
              f"(x{ch['goodput_vs_shed_only']:.2f}, final kv "
              f"{la['kv_quant_final']})")
        check("chaos-terminal-outcomes",
              all(r["all_terminal"] and r["pool_audit_clean"]
                  for r in (so, la, fa)),
              "every request terminal + per-sync pool audits clean in all "
              "three runs")
        check("chaos-survivors-bit-identical",
              ch["survivors_bit_identical"] and ch["survivors_compared"] > 0,
              f"{ch['survivors_compared']} requests ok in both faulted and "
              f"fault-free runs, tokens identical "
              f"(injected: {fa['chaos_injected']})")
    else:
        print("  [--] chaos section absent; overload/degradation gates "
              "skipped")

    rf = data.get("replica_failover", {})
    if rf:
        ff, ki = rf["fault_free"], rf["killed"]
        check("failover-survivors-bit-identical",
              rf["survivors_bit_identical"] and rf["survivors_compared"] > 0
              and all(r["all_terminal"] for r in (ff, ki)),
              f"{rf['survivors_compared']} non-migrated survivors "
              f"bit-identical after killing 1 of {rf['replicas']} replicas "
              f"at step {rf['kill_step']:g}; every rid terminal in both "
              f"runs (migrated identical: {rf['migrated_bit_identical']})")
        check("failover-goodput-floor",
              rf["failover_goodput_ratio"] >= 0.9,
              f"killed {ki['goodput_tokens_per_step']:.3f} tok/step >= 0.9 "
              f"x fault-free {ff['goodput_tokens_per_step']:.3f} "
              f"(x{rf['failover_goodput_ratio']:.2f} with "
              f"{ki['migrated_requests']} migrations)")
        check("router-prefix-affinity",
              rf["fault_free"]["shared_tokens_admitted"] >
              rf["no_affinity"]["shared_tokens_admitted"],
              f"affinity placement shares "
              f"{rf['fault_free']['shared_tokens_admitted']} prompt tokens "
              f"from adopted pages vs "
              f"{rf['no_affinity']['shared_tokens_admitted']} without "
              f"(x{rf['affinity_sharing_ratio']:.1f})")
    else:
        print("  [--] replica_failover section absent; failover gates "
              "skipped")

    tl = data.get("telemetry", {})
    if tl:
        check("trace-deterministic",
              tl["trace_deterministic"] and tl["span_count"] > 0,
              f"two same-seed chaos runs, identical trace signatures with "
              f"wall-clock stripped ({tl['span_count']} spans, injected "
              f"{tl['chaos_injected_kinds']})")
        # both directions: the accurate plan stays clean AND the
        # mispredicted plan fires naming the paging decision — a drift
        # detector that never fires is as dead as one that always fires
        check("plan-drift-clean",
              not tl["clean_drift"]["confirmed"]
              and tl["clean_drift"]["compared"] > 0
              and tl["forced_names_attention"],
              f"accurate plan: {tl['clean_drift']['compared']} comparisons "
              f"clean over {tl['clean_drift']['windows']} windows; "
              f"mispredicted plan confirms "
              f"{tl['forced_drift']['confirmed']}")
    else:
        print("  [--] telemetry section absent; trace/drift gates skipped")

    plans = data.get("plans", {})
    if plans:
        golden_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                   "golden_plans.json")
        golden = json.load(open(golden_path))
        # round-trip the bench side through JSON text so tuple-vs-list and
        # int-vs-float representation can never cause a spurious diff
        plans = json.loads(json.dumps(plans))
        drifted = []
        # both directions: a bench plan without a golden counterpart (new
        # snapshot config, golden not regenerated) is drift too. "__"-keys
        # hold auxiliary snapshot families (e.g. __sharded__) gated below.
        for arch in sorted(k for k in set(golden) | set(plans)
                           if not k.startswith("__")):
            want, got = golden.get(arch), plans.get(arch)
            if got != want:
                if want is None or got is None:
                    keys = "missing from golden" if want is None \
                        else "missing from bench"
                else:
                    keys = ", ".join(sorted(
                        k for k in set(want) | set(got)
                        if got.get(k) != want.get(k)))
                drifted.append(f"{arch}({keys})")
        check("plan-snapshot-stable", not drifted,
              f"{len(golden)} seed plans match scripts/golden_plans.json"
              if not drifted else f"drifted: {'; '.join(drifted)}")
    else:
        print("  [--] plans section absent; plan-snapshot gate skipped")

    splans = data.get("sharded_plans", {})
    if splans:
        golden_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                   "golden_plans.json")
        golden_sharded = json.load(open(golden_path)).get("__sharded__", {})
        splans = json.loads(json.dumps(splans))
        drifted = []
        for arch in sorted(set(golden_sharded) | set(splans)):
            want = golden_sharded.get(arch, {})
            got = splans.get(arch, {})
            for mesh in sorted(set(want) | set(got)):
                if got.get(mesh) != want.get(mesh):
                    drifted.append(f"{arch}@{mesh}")
        check("sharded-plan-snapshot-stable", not drifted,
              f"{sum(len(v) for v in golden_sharded.values())} sharded "
              "plans match golden __sharded__"
              if not drifted else f"drifted: {'; '.join(drifted)}")
    else:
        print("  [--] sharded_plans section absent; sharded-snapshot "
              "gate skipped")

    shp = data.get("shard_proxy", {})
    if shp:
        cases = shp.get("cases", {})
        check("sharded-outputs-identical",
              bool(cases) and all(c.get("outputs_identical") is True
                                  for c in cases.values()),
              "sharded stream() vs single-device: " + ", ".join(
                  f"{name}: {c.get('outputs_identical')}"
                  for name, c in sorted(cases.items())))
        # per-device KV pool holds <= 1/tp of the single-device pool plus
        # one page frame of rounding slack (whole local frames only)
        pool_ok, detail = True, []
        for name, c in sorted(cases.items()):
            if not c.get("paged") or c.get("tp", 1) <= 1:
                continue
            bound = (c["kv_bytes_single_device"] / c["tp"]
                     + c["page_frame_bytes_per_device"])
            ok = c["kv_bytes_per_device"] <= bound
            pool_ok &= ok
            detail.append(f"{name}: {c['kv_bytes_per_device']:,} B <= "
                          f"{bound:,.0f} B ({'ok' if ok else 'OVER'})")
        check("sharded-pool-bytes-per-device", pool_ok,
              "; ".join(detail) if detail
              else "no paged tp case benchmarked")
    else:
        print("  [--] shard_proxy section absent; sharded gates skipped")

    spd = data.get("spec_proxy", {})
    if spd:
        s1 = spd.get("batches", {}).get("1", {})
        # speedup is tokens-per-dispatch on the deterministic clock (one
        # flattened verify per speculative round vs one step per baseline
        # token) — wall seconds are reported alongside but never gated
        check("spec-decode-speedup",
              s1.get("speedup_tokens_per_dispatch", 0) >= 1.5,
              f"batch-1 spec x{s1.get('speedup_tokens_per_dispatch', 0):.2f}"
              f" tokens/dispatch (wall x{s1.get('speedup_wall', 0):.2f}, "
              f"acceptance "
              f"{s1.get('spec', {}).get('acceptance_rate', 0):.0%})")
        check("spec-greedy-bit-exact",
              all(row.get("greedy_bit_exact") is True
                  for row in spd["batches"].values()),
              "greedy outputs vs sequential baseline: " + ", ".join(
                  f"batch {b}: {row.get('greedy_bit_exact')}"
                  for b, row in sorted(spd["batches"].items())))
    else:
        print("  [--] spec_proxy section absent; spec-decode gates skipped")

    dec = data.get("decode", {})
    if dec:
        b1 = dec.get("batches", {}).get("1", {})
        check("e2e-ratio-reported", "e2e_ratio" in b1,
              f"batch-1 e2e ratio = {b1.get('e2e_ratio')}")
        ph = b1.get("sparse", {}).get("phases", {})
        check("phase-breakdown-reported",
              ph.get("prefill_batches", 0) >= 1 and "decode_s" in ph,
              f"prefill_batches={ph.get('prefill_batches')} "
              f"prefill_s={ph.get('prefill_s')}")
    else:
        print("  [--] engine section absent (--no-engine run); "
              "proxy checks only")

    if failures:
        print(f"PERF GUARD FAILED: {', '.join(failures)}")
        return 1
    print("perf guard passed")
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:]))
