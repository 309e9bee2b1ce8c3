"""Streaming LM serving through the `repro.serve.LLM` facade (ISSUE 5).

The canonical serving entry point: resolve a ServePlan ONCE from the model
config and the serving budget (`core.plan.plan_serve` — every dispatch
decision with its Eyexam-style bound rationale), hand it to `LLM`, and
stream. Requests arrive on a Poisson process, share a page pool provisioned
*below* the dense worst case, and stream tokens through per-request
callbacks as they are generated.

The facade serves behind the robustness guard (ISSUE 6) by default: every
request ends in a structured outcome (ok/shed/expired/preempted_out/failed)
delivered via ``on_outcome``, overload degrades along the plan's ladder
(int8 KV -> clamp -> shed) instead of raising, and ``--ttl`` attaches a
deadline in decode steps to every request.

With ``--replicas N`` the same facade serves through the multi-replica
control plane (ISSUE 7): a router places requests by prefix affinity and
measured queue depth across N scheduler replicas on one shared virtual
clock, heartbeats are audited every sync window, and ``--kill-replica-at
STEP`` chaos-kills replica 0 mid-run — stranded requests migrate by
recompute and every request still ends in exactly one outcome.

``--trace out.json`` writes the run's step-clock trace (ISSUE 8) as Chrome
``trace_event`` JSON — open it at https://ui.perfetto.dev (or
chrome://tracing): replicas render as processes, requests as threads, one
virtual decode step as 1 ms. The trace structure is deterministic (wall
time rides along as annotations), and the end-of-run drift report diffs
measured occupancy/length/route proxies against the plan's decisions.

``--mesh tp=2,ep=4`` serves mesh-sharded (ISSUE 10): attention KV heads
shard over ``tp`` per-device page pools and MoE experts over ``ep``, the
plan's explain() gains the mesh/NoC-mode decisions, and the report prints
per-device pool bytes and collective traffic. Token streams stay
bit-identical to the single-device run.

    PYTHONPATH=src python examples/serve_lm.py --requests 12 --rows 4
    PYTHONPATH=src python examples/serve_lm.py --mean-gap 1 --ttl 40
    PYTHONPATH=src python examples/serve_lm.py --replicas 3 \\
        --kill-replica-at 8
    PYTHONPATH=src python examples/serve_lm.py --trace trace.json
    PYTHONPATH=src python examples/serve_lm.py --mesh tp=2
    PYTHONPATH=src python examples/serve_lm.py --arch mixtral-8x7b-reduced \\
        --mesh tp=2,ep=4

``--arch`` takes any registry name: a ``-reduced`` config runs on the CPU,
a published-width one needs a chip.
"""
import argparse
import json
import sys
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.core import dataflow, plan as plan_lib
from repro.models import transformer as tfm
from repro.serve import LLM
from repro.serve.scheduler import StreamRequest


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2-2b-reduced")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--rows", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--cache-len", type=int, default=96)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--mean-gap", type=float, default=4.0,
                    help="mean Poisson inter-arrival gap, in decode steps")
    ap.add_argument("--prefix-len", type=int, default=16,
                    help="shared system-prompt prefix length (0 disables); "
                         "CoW prefix sharing stores it once across requests")
    ap.add_argument("--kv-quant", choices=["fp", "int8"], default=None,
                    help="page payload format (default: plan rule)")
    ap.add_argument("--spec-k", type=int, default=None,
                    help="speculative draft depth per round (0 disables; "
                         "default: plan rule — on at batch 1 where the "
                         "weight stream dominates). Needs an all-global-"
                         "attention arch (e.g. --arch qwen2.5-3b-reduced) on fp "
                         "pages; greedy outputs stay bit-identical")
    ap.add_argument("--ttl", type=float, default=None,
                    help="per-request deadline in decode steps from arrival "
                         "(unfinished requests resolve `expired`)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="scheduler replicas behind the router (>1 serves "
                         "through the multi-replica control plane)")
    ap.add_argument("--kill-replica-at", type=float, default=None,
                    help="chaos-kill replica 0 at this virtual step "
                         "(requires --replicas > 1); stranded requests "
                         "migrate by recompute")
    ap.add_argument("--mesh", default=None, metavar="tp=2,ep=4",
                    help="serve mesh-sharded (ISSUE 10): tp shards "
                         "attention KV heads over per-device page pools, "
                         "ep shards the MoE expert axis (needs an MoE arch "
                         "e.g. --arch mixtral-8x7b-reduced). Token streams stay "
                         "bit-identical to single-device")
    ap.add_argument("--trace", metavar="OUT.json", default=None,
                    help="write the step-clock trace as Chrome trace_event "
                         "JSON (load at https://ui.perfetto.dev)")
    args = ap.parse_args()
    if args.kill_replica_at is not None and args.replicas < 2:
        ap.error("--kill-replica-at needs --replicas > 1 (killing the "
                 "only replica just respawns it)")

    cfg = get_config(args.arch)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)

    # resolve every dispatch decision once: pool provisioned for ~half-slot
    # expected occupancy (paging + preemption make under-provisioning safe)
    plan = plan_lib.plan_serve(
        cfg,
        hbm_budget_bytes=args.rows * 2 ** 30,     # demo-scale budget
        expected_batch=args.rows,
        expected_len_dist={"mean": args.cache_len // 2,
                           "max": args.cache_len},
        page_size=args.page_size,
        num_pages=max(args.rows * dataflow.pages_for(
            args.cache_len, args.page_size) // 2, 1),
        kv_quant=args.kv_quant,
        spec_k=args.spec_k,
        mesh=args.mesh)
    print(plan.explain())
    print()

    llm = LLM(cfg, params, plan, eos_id=1,   # guard on by default
              replicas=args.replicas)

    def finished(req, outcome):
        if not outcome.ok:
            why = f" ({outcome.reason})" if outcome.reason else ""
            print(f"  req {req.rid} -> {outcome.status}{why}")

    rng = np.random.default_rng(0)
    arrivals = np.cumsum(rng.exponential(args.mean_gap, args.requests))
    first_tokens = {}

    def stream(req, tok):
        if req.rid not in first_tokens:
            first_tokens[req.rid] = tok
            print(f"  req {req.rid} (arrived t={req.arrival:.0f}, admitted "
                  f"t={req.admitted_at:.0f}) first token: {tok}")

    # shared system-prompt prefix: CoW sharing stores its pages once,
    # refcounted across every live request
    prefix = list(rng.integers(2, cfg.vocab_size, args.prefix_len))
    reqs = [StreamRequest(rid=i,
                          prompt=prefix + list(
                              rng.integers(2, cfg.vocab_size,
                                           rng.integers(4, 12))),
                          max_new=int(rng.integers(4, args.max_new + 1)),
                          arrival=float(arrivals[i]),
                          ttl=args.ttl,
                          on_token=stream)
            for i in range(args.requests)]

    chaos = None
    if args.kill_replica_at is not None:
        from repro.serve.chaos import ReplicaChaosConfig
        chaos = ReplicaChaosConfig(
            kill_at_step={0: args.kill_replica_at})

    t0 = time.time()
    done = llm.stream(reqs, on_outcome=finished, chaos=chaos)
    dt = time.time() - t0
    new_toks = sum(len(r.out) for r in done)
    st = llm.phase_stats
    fleet = st.get("fleet", st)   # multi-replica aggregates live in "fleet"
    lat = [r.finished_at - r.arrival for r in done]
    print(f"{len(done)} requests, {new_toks} tokens in {dt:.1f}s "
          f"({new_toks / dt:.1f} tok/s wall; "
          f"{new_toks / max(st['clock_steps'], 1):.2f} tok/step)")
    print(f"latency p50 {np.percentile(lat, 50):.0f} / "
          f"p99 {np.percentile(lat, 99):.0f} steps; "
          f"preemptions {fleet['preemptions']}")
    if args.replicas > 1:
        ro = st["router"]
        print(f"fleet: {st['replicas_spawned']} replicas spawned, "
              f"{st['replicas_final']} live at end; "
              f"failovers {st['failovers']}"
              + (f" {st['failover_reasons']}" if st["failovers"] else "")
              + f", {st['migrated_requests']} requests migrated")
        print(f"router: {ro['affinity_hits']}/{ro['placements']} "
              f"placements hit prefix affinity "
              f"({fleet['shared_tokens_admitted']} prompt tokens adopted "
              f"from shared pages)")
    if st.get("spec_rounds"):
        print(f"speculation: k={st['spec_k']}, {st['spec_rounds']} verify "
              f"rounds retired {st['spec_accepted_tokens']}/"
              f"{st['spec_drafted_tokens']} drafted tokens "
              f"({st['spec_accepted_tokens'] / st['spec_rounds']:.2f} "
              f"tokens/dispatch)")
    print(f"outcomes: " + ", ".join(
        f"{k} {v}" for k, v in st["outcomes"].items() if v))
    pg = st.get("pages_peak")
    if pg:
        print(f"pages at peak: {pg['pages_used']}/{pg['pages_total']} in "
              f"use ({pg['used_tokens']} tokens), "
              f"fragmentation {pg['fragmentation']:.2f}, "
              f"{pg['shared_pages']} shared "
              f"(saved {pg['pages_saved_sharing']} pages)")
        print(f"sharing: {st['shared_tokens_admitted']} prompt tokens "
              f"admitted from adopted pages, {st['cow_copies']} CoW copies, "
              f"peak concurrency {st['peak_live_rows']} rows")

    if plan.sharded:
        rep = llm.sharding_report()
        snap = llm.telemetry().metrics.snapshot()
        print(f"mesh: {llm.mesh.describe()}")
        if rep.get("kv_bytes_per_device"):
            print(f"  pool/device {rep['kv_bytes_per_device']:,} B "
                  f"(single-device {rep['kv_bytes_single_device']:,} B, "
                  f"1/{plan.tp} KV heads each), lockstep divergence "
                  f"{rep.get('lockstep_divergence', 0)}")
        print(f"  collectives: {snap.counters['collective_ops']:.0f} "
              f"all-gathers, "
              f"{snap.counters['collective_allgather_bytes']:,.0f} B "
              f"({snap.counters['collective_allgather_bytes'] / max(new_toks, 1):,.0f} B/token)")

    tel = llm.telemetry()
    if tel.last_drift is not None:
        d = tel.last_drift
        print(f"plan drift: {len(d.confirmed)} CONFIRMED / "
              f"{len(d.findings)} compared over {d.windows} windows"
              + (" — " + "; ".join(f"{f.decision}.{f.metric}"
                                   for f in d.confirmed)
                 if d.confirmed else ""))
    if args.trace:
        with open(args.trace, "w") as f:
            json.dump(tel.tracer.to_chrome_trace(), f)
        print(f"wrote {len(tel.tracer.events)} spans to {args.trace} "
              f"(open at https://ui.perfetto.dev)")
    # the guard turns faults into outcomes; a run with a failed request
    # must not report success
    return 1 if st["outcomes"].get("failed") else 0


if __name__ == "__main__":
    sys.exit(main())
