"""Sparse/compressed decode analysis — what actually bounds the decode cells,
and which compression lever (paper §IV) moves each regime.

Measured finding (see decode_regimes()): at decode_32k's batch of 128 slots
the memory term is **KV-cache streaming** (the whole 32k-token cache is read
every step; weights amortize over the 128 slots — weight-stream share < 1%).
Weight sparsity (BCSC, the paper's Sparse PE) therefore pays at *small
batch*, while at large batch the paper-faithful compression move is applying
the same keep-it-compressed idea to the **cache** (int8 KV ≈ ×2 bytes).

ISSUE 1 additions:
* ``kernel_proxy`` — dense rs_matmul vs bcsc_gemv at decode shapes, grid-step
  counts (the interpret-mode proxy; on TPU the same harness wall-clocks).
* ``decode_benchmark`` — DecodeEngine tokens/sec, dense vs BCSC-packed params
  at batch {1, 4, 8}; written to BENCH_sparse_decode.json.

ISSUE 2 additions (the end-to-end gap PR 1 left):
* ``mlp_proxy`` — fused bcsc_mlp megakernel vs the two-call path: grid steps,
  payload block visits, and an HBM-bytes-moved model including the hidden-
  activation round-trip the megakernel eliminates. Wall-clock-free, so the
  CI perf guard (scripts/perf_guard.py) can enforce it in interpret mode.
* ``decode_benchmark`` now reports the sparse/dense end-to-end ratio as a
  first-class metric (vs the recorded PR 1 baseline 0.87 at batch 1), a
  per-phase prefill/decode breakdown from the engine's batched-prefill
  stats, and best-of-N timing (single-shot numbers on a shared CPU were
  ±30% noise).
* ``mlp_bound_analysis`` — the Eyexam-style analytic model (DESIGN.md §9)
  of *why* two-call lost, written to BENCH_sparse_decode.json["analytic"].

    PYTHONPATH=src python benchmarks/sparse_decode.py [--smoke] [--no-engine]
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import time
from typing import Dict, List

import numpy as np

from repro.configs import get_config
from repro.core import eyexam
from repro.core import plan as plan_lib
from repro.models import decoding

SPARSITIES = (0.5, 0.75, 0.9)
# analytic constants live with the ServePlan roofline (core.plan) so the
# plan's MLP rationale and this benchmark are the same numbers
BCSC_OVERHEAD = plan_lib.BCSC_OVERHEAD
KERNEL_LAUNCH_S = plan_lib.KERNEL_LAUNCH_S
BENCH_JSON = "BENCH_sparse_decode.json"
PR1_E2E_RATIO_B1 = 0.87  # PR 1's recorded batch-1 sparse/dense tokens/sec
ID_BYTES = 8             # row_id + col_id int32 per payload block


def decode_regimes(dryrun_dir: str = "results/dryrun_opt") -> Dict:
    out: Dict = {}
    for f in sorted(glob.glob(os.path.join(dryrun_dir,
                                           "*decode_32k__16x16*"))):
        r = json.load(open(f))
        if r["status"] != "ok":
            continue
        cfg = get_config(r["arch"])
        chips = r["chips"]
        # ANALYTIC decode stream model (the measured term stays conservative
        # on the CPU proxy — scan-carry cache rewrites that TPU aliasing
        # elides; see EXPERIMENTS.md D1). Per chip, per decode step:
        #   weights (active, bf16) + full KV/state-cache read.
        w_bytes = cfg.param_count(active_only=True) * 2 / chips
        cache = decoding.abstract_cache(cfg, 128, 32768)
        import jax
        c_bytes = sum(l.size * l.dtype.itemsize
                      for l in jax.tree.leaves(cache)) / chips
        t_w = w_bytes / eyexam.HBM_BW
        t_c = c_bytes / eyexam.HBM_BW
        t128 = t_w + t_c                      # batch-128 step
        rows: Dict = {
            "t_analytic_128_ms": t128 * 1e3,
            "cache_share": t_c / t128,
            "int8_cache_speedup": t128 / (t_w + t_c / 2),
        }
        # batch-1 regime (one slot): weights dominate; BCSC pays directly
        t1 = t_w + t_c / 128
        for sp in SPARSITIES:
            t1_sp = t_w * (1 - sp) * BCSC_OVERHEAD + t_c / 128
            rows[f"b1_bcsc_speedup_{sp:.2f}"] = t1 / t1_sp
        out[r["arch"]] = rows
    return out


# ------------------------------------------------------- ISSUE 1: fast path
def kernel_proxy(sparsities=SPARSITIES + (0.7,), K: int = 256, N: int = 512,
                 block: int = 16) -> Dict:
    """Batch-1 MLP projection: dense rs_matmul grid steps vs bcsc_gemv nnzb.

    Grid steps are the interpret-mode cost proxy (each step is one MXU-tile
    visit); both sides are normalized to the same (bk, bn) tiles so the ratio
    is exactly the structural-skip factor the paper's Sparse PE claims (§IV).
    """
    import jax.numpy as jnp
    from repro.core import sparsity as sp
    from repro.kernels import ops

    rng = np.random.default_rng(0)
    w = rng.standard_normal((K, N)).astype(np.float32)
    dense_blocks = (K // block) * (N // block)
    out: Dict = {"shape": [K, N], "block": block,
                 "dense_grid_steps": dense_blocks}
    for s in sorted(sparsities):
        ws = np.asarray(sp.block_magnitude_prune(jnp.asarray(w), s,
                                                 block, block))
        m = sp.bcsc_encode(ws, block, block)
        blocks, _, _, _ = ops.prepare_bcsc(m)
        steps = int(blocks.shape[0])
        out[f"sparsity_{s:.2f}"] = {
            "gemv_grid_steps": steps,
            "speedup_vs_dense": dense_blocks / max(steps, 1),
        }
    return out


# ------------------------------------------------- shared: pruned + packed
def _pruned_packed(arch: str, sparsity: float, block: int = 16):
    import jax
    import jax.numpy as jnp
    from repro.core import sparsity as sp
    from repro.models import transformer as tfm
    from repro.serve import sparse as sps

    cfg = get_config(arch)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    for slot in params.get("blocks", {}):
        mlp = params["blocks"][slot].get("mlp")
        if mlp:
            for nm in list(mlp):
                w = mlp[nm]
                mlp[nm] = jnp.stack([
                    sp.block_magnitude_prune(w[l], sparsity, block, block)
                    for l in range(w.shape[0])])
    packed, stats = sps.sparsify_mlp_params(params, cfg, sparsity=0.0)
    return cfg, params, packed, stats


# --------------------------------- ISSUE 2: fused megakernel vs two-call
def mlp_proxy(arch: str = "qwen2.5-3b-reduced", sparsity: float = 0.75,
              block: int = 16, bm: int = 8, stats: Dict = None) -> Dict:
    """Wall-clock-free cost model: fused bcsc_mlp vs the PR 1 two-call path.

    Counts, per decode token (M = bm activation rows) over every packed MLP
    layer of the model:

    * grid steps — sequential grid steps the kernel actually executes (the
      pipeline/prologue overhead proxy). Two-call visits one payload block
      per step and walks the full padded stack capacity. The megakernel's
      unrolled variant runs ONE step per m-tile; its gridded variant runs
      every capacity chunk step (a skipped chunk still spins its step — only
      its DMA and MACs are elided, which block visits/bytes capture).
    * work chunks — chunk-level units doing real DMA+MACs: capacity chunks
      for the unrolled variant (pads are masked, not skipped), ceil(real/C)
      for the gridded variant (whole pad chunks skipped).
    * block visits — payload blocks DMA'd from HBM. The megakernel's skip is
      chunk-granular, so its waste is < C blocks per segment vs the two-call
      path's full pad-to-densest-layer capacity.
    * hbm bytes — weight payload + index vectors + activations in/out
      **including the hidden-activation round-trip** (g/u written fp32, read
      for the gate product, h written bf16, re-read by the down projection)
      that exists only in the two-call path: the megakernel holds the hidden
      in VMEM scratch from first MAC to final drain.
    """
    from repro.kernels import bcsc_mlp as bmlp

    if stats is None:
        cfg, _, _, stats = _pruned_packed(arch, sparsity, block)
    else:
        cfg = get_config(arch)
    bb = block * block
    w_byte = 2                                   # bf16 payload (pack dtype)
    d = cfg.d_model
    ff = cfg.dense_d_ff if (cfg.moe and cfg.dense_d_ff) else cfg.d_ff
    gated = cfg.mlp_gated

    two = {"grid_steps": 0, "block_visits": 0, "hbm_bytes": 0,
           "kernel_launches": 0}
    fused = {"grid_steps": 0, "work_chunks": 0, "block_visits": 0,
             "hbm_bytes": 0, "kernel_launches": 0}
    weights = stats["weights"]
    names = list(weights)
    # mixed-density guard (ROADMAP latent bug): sparsify_mlp_params routes a
    # weight dense in one layer group and packed in another, so the per-name
    # "real"/"padded" lists can have UNEQUAL lengths — indexing them
    # uniformly was an IndexError. Layers where a projection is missing
    # count only the projections that were actually packed there.
    n_layers = max((len(weights[nm]["real"]) for nm in names), default=0)
    mixed_density = len({len(weights[nm]["real"]) for nm in names}) > 1
    for li in range(n_layers):
        seg = []                        # (real, padded, C) per projection
        for nm in names:
            w = weights[nm]
            if li >= len(w["real"]):
                continue                # dense in this layer group: no pack
            P = w["padded"][li]
            seg.append((w["real"][li], P, bmlp._pick_chunk(P)))
        if not seg:
            continue
        n_chunks = sum(p // c for _, p, c in seg)
        unrolled = n_chunks <= bmlp.UNROLL_CHUNKS_MAX

        # ---- two-call: one kernel per projection, 1 block per grid step
        two["kernel_launches"] += len(seg)
        for real, P, _ in seg:
            two["grid_steps"] += P
            two["block_visits"] += P
            two["hbm_bytes"] += P * (bb * w_byte + ID_BYTES)
        # activations: x read per up kernel, h read by the down kernel,
        # plus the hidden round-trip between the kernels
        ups = 2 if gated else 1
        two["hbm_bytes"] += ups * bm * d * 2          # x in (bf16) per up
        two["hbm_bytes"] += ups * bm * ff * 4         # g/u out (fp32)
        if gated:
            two["hbm_bytes"] += 2 * bm * ff * 4       # g,u re-read for gate
        two["hbm_bytes"] += bm * ff * 2               # h written bf16
        two["hbm_bytes"] += bm * ff * 2               # h read by down kernel
        two["hbm_bytes"] += bm * d * 4                # down out (fp32)

        # ---- fused megakernel: one launch, chunked walk, VMEM hidden
        fused["kernel_launches"] += 1
        fused["grid_steps"] += 1 if unrolled else n_chunks
        for real, P, C in seg:
            if unrolled:
                chunks = P // C          # whole (small) payload resident
            else:
                chunks = max(-(-real // C), 1)        # ceil: ragged skip
            fused["work_chunks"] += chunks
            fused["block_visits"] += chunks * C
            fused["hbm_bytes"] += chunks * C * (bb * w_byte + ID_BYTES)
        fused["hbm_bytes"] += bm * d * 2              # x in, VMEM-resident
        fused["hbm_bytes"] += bm * d * 4              # final out (fp32)

    return {
        "arch": arch, "sparsity": sparsity, "bm": bm,
        "mixed_density": mixed_density,
        "block_density": stats.get("block_density"),
        "packing_efficiency": stats.get("packing_efficiency"),
        "per_weight_packing": {
            nm: {"real": w["real"], "padded": w["padded"],
                 "packing_efficiency": w["packing_efficiency"]}
            for nm, w in weights.items()},
        "two_call": two,
        "fused": fused,
        "ratios": {
            "grid_steps": two["grid_steps"] / max(fused["grid_steps"], 1),
            "block_visits": (two["block_visits"] /
                             max(fused["block_visits"], 1)),
            "hbm_bytes": two["hbm_bytes"] / max(fused["hbm_bytes"], 1),
        },
    }


def mlp_bound_analysis(arch: str = "qwen2.5-3b", sparsity: float = 0.75,
                       packing_efficiency: float = 0.93) -> Dict:
    """Eyexam-style bound shift (paper Appendix A; DESIGN.md §9).

    Why PR 1's two-call sparse path lost end-to-end at batch 1 even though
    its kernels won the grid-step proxy: the decode-step MLP time is

        t = t_weight_stream + t_hidden_roundtrip + n_launch · t_launch

    Sparsity only shrinks the first term. The two-call path *adds* the second
    term (the (bm × d_ff) hidden crosses HBM four times: fp32 out ×2, gate
    re-read, bf16 write + re-read) and triples the third — at full scale the
    hidden round-trip is small next to weights, but the launch term is pure
    overhead, and on the CPU interpret proxy (where per-launch cost is ~100×
    a TPU launch) it dominated, which is exactly the 0.87 ratio recorded in
    PR 1. The megakernel removes both added terms, so the bound returns to
    the weight stream — the only term sparsity can shrink.

    The model itself lives in ``core.plan.mlp_roofline`` — it is the MLP
    decision's rationale inside every resolved ServePlan, and delegating
    keeps this benchmark and ``plan.explain()`` the same numbers by
    construction (tests/test_plan.py asserts it). This wrapper keeps the
    benchmark-JSON schema.
    """
    out = plan_lib.mlp_roofline(get_config(arch), sparsity=sparsity,
                                packing_efficiency=packing_efficiency)
    return {"arch": arch, **out}


# ---------------------------------- ISSUE 3: paged KV + continuous batching
def paged_proxy(arch: str = "qwen2.5-3b-reduced", rows: int = 8,
                cache_len: int = 4096, page_size: int = 64,
                mean_occupancy: float = 0.5, seed: int = 0) -> Dict:
    """Wall-clock-free paged-vs-dense cost model (perf_guard gates these).

    * **HBM bytes** — real cache allocations via jax.eval_shape: the dense
      (rows × cache_len) slot cache vs the paged layout provisioned for the
      expected occupancy (pages covering each row's page-rounded length at
      ``mean_occupancy``). Paged must be strictly smaller — that is the
      entire point of block-table indirection.
    * **blocks** — the paged decode kernel walks blocks of
      ``pages_per_block`` pages, exactly ceil(ceil(len/ps) / ppb) per row
      (the trip count of its block loop, kernels.paged_attention.row_blocks);
      the padded (rows × max_pages / ppb) grid is reported for the skip
      ratio. ``cache_len`` is the chat cell's 4096, where a row spans up to
      four blocks.
    """
    import importlib

    import jax.numpy as jnp
    from repro.core import dataflow
    from repro.serve import kvcache
    paged = importlib.import_module("repro.kernels.paged_attention")

    cfg = get_config(arch)
    rng = np.random.default_rng(seed)
    # ragged lengths with the target mean occupancy (clamped into range)
    lengths = np.clip(rng.normal(mean_occupancy * cache_len,
                                 0.5 * mean_occupancy * cache_len,
                                 rows).astype(int), 1, cache_len).tolist()
    # ceil(len/ps) per row from core.dataflow — the spec-side bound, computed
    # independently of the kernel module so the gate is cross-sourced
    ceil_pages = sum(dataflow.pages_for(n, page_size) for n in lengths)
    dense_bytes = kvcache.cache_bytes(cfg, rows, cache_len)
    paged_bytes = kvcache.paged_cache_bytes(cfg, rows, cache_len, ceil_pages,
                                            page_size)
    max_pages = dataflow.pages_for(cache_len, page_size)
    ppb = paged.pages_per_block(page_size, cfg.num_kv_heads, cfg.head_dim,
                                jnp.dtype(decoding.COMPUTE_DTYPE).itemsize,
                                max_pages)
    return {
        "arch": arch, "rows": rows, "cache_len": cache_len,
        "page_size": page_size, "mean_occupancy": mean_occupancy,
        "lengths": lengths,
        "dense_slot_bytes": dense_bytes,
        "paged_bytes": paged_bytes,
        "bytes_ratio": dense_bytes / max(paged_bytes, 1),
        "pages_per_block": ppb,
        # the kernel's own skip bound (kernels.paged_attention.row_blocks,
        # the trip count of its block loop) vs. the spec bound from
        # core.dataflow's pages
        "work_blocks": int(paged.row_blocks(np.asarray(lengths), 0, page_size,
                                            ppb, max_pages).sum()),
        "ceil_blocks": sum(-(-dataflow.pages_for(n, page_size) // ppb)
                           for n in lengths),
        "padded_grid_blocks": rows * -(-max_pages // ppb),
        "ceil_pages": ceil_pages,
        "tokens_resident_paged": dataflow.paged_kv_tokens(lengths, page_size),
        "tokens_resident_dense": dataflow.dense_kv_tokens(rows, cache_len),
    }


def _poisson_arrivals(n: int, mean_gap: float, rng) -> List[float]:
    gaps = rng.exponential(mean_gap, n)
    return np.cumsum(gaps).tolist()


def arrival_benchmark(arch: str = "qwen2.5-3b-reduced", rows: int = 3,
                      n_requests: int = 9, cache_len: int = 48,
                      page_size: int = 8, sync_every: int = 4,
                      mean_gap: float = 3.0, seed: int = 0) -> Dict:
    """Poisson-arrival sweep: continuous batching (paged scheduler) vs the
    drain-the-chunk baseline, at low and high request-length variance.

    The baseline is classic static batching: admit a cohort of ``rows``
    requests in arrival order, wait for the *last* cohort member to arrive,
    run the cohort to full completion (DecodeEngine), then admit the next —
    freed slots idle until the cohort drains. The scheduler admits/evicts at
    every sync boundary instead, and its page pool is provisioned at half
    the dense-slot footprint. Both sides are measured on the deterministic
    virtual clock (1 unit = 1 decode step; arrival gaps in the same unit) so
    the goodput/latency comparison is CI-stable; wall seconds are recorded
    alongside but never gated. Generation lengths are budget-bound
    (eos_id=-1), so token counts — and the whole comparison — are exact.
    """
    import jax
    from repro.core import dataflow
    from repro.models import transformer as tfm
    from repro.serve.engine import DecodeEngine, Request
    from repro.serve.kvcache import cache_bytes, paged_cache_bytes
    from repro.serve.scheduler import (ContinuousBatchingScheduler,
                                       StreamRequest)

    cfg = get_config(arch)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(seed)
    prompt = [5, 6, 7, 8]
    arrivals = _poisson_arrivals(n_requests, mean_gap, rng)
    # same mean generation length, ~9x the variance: the regime where
    # drain-the-chunk strands slots behind the longest cohort member
    cases = {
        "low_variance": [6 if i % 2 else 10 for i in range(n_requests)],
        "high_variance": [2 if i % 2 else 14 for i in range(n_requests)],
    }
    num_pages = (rows * dataflow.pages_for(cache_len, page_size)) // 2

    out: Dict = {
        "arch": arch, "rows": rows, "n_requests": n_requests,
        "cache_len": cache_len, "page_size": page_size,
        "sync_every": sync_every, "mean_gap": mean_gap,
        "arrivals": [round(a, 2) for a in arrivals],
        "memory": {
            # cache side only: this benchmark serves DENSE params (packing
            # would slow every interpret-mode step for no scheduling signal);
            # the weight-stream side (sparse.packed_bytes) is reported by
            # decode_benchmark, which actually serves packed params
            "dense_cache_bytes": cache_bytes(cfg, rows, cache_len),
            "paged_cache_bytes": paged_cache_bytes(
                cfg, rows, cache_len, num_pages, page_size),
        },
        "cases": {},
    }
    for name, max_news in cases.items():
        row: Dict = {"max_new": max_news,
                     "length_variance": float(np.var(max_news))}

        # ---- continuous batching: paged scheduler on the virtual clock ----
        # engines run plan-driven: dispatch is resolved once by core.plan
        sch = ContinuousBatchingScheduler(
            cfg, params, plan_lib.plan_for_scheduler(
                cfg, rows=rows, cache_len=cache_len, page_size=page_size,
                num_pages=num_pages, attn_path="paged",
                sync_every=sync_every),
            eos_id=-1)
        reqs = [StreamRequest(i, prompt, mn, arrival=arrivals[i])
                for i, mn in enumerate(max_news)]
        t0 = time.perf_counter()
        done = sch.run(reqs)
        wall = time.perf_counter() - t0
        toks = sum(len(r.out) for r in done)
        lat = [r.finished_at - r.arrival for r in done]
        makespan = sch.phase_stats["clock_steps"]
        row["scheduler"] = {
            "tokens": toks,
            "makespan_steps": makespan,
            "goodput_tokens_per_step": toks / max(makespan, 1e-9),
            "latency_p50_steps": float(np.percentile(lat, 50)),
            "latency_p99_steps": float(np.percentile(lat, 99)),
            "preemptions": sch.phase_stats["preemptions"],
            "wall_s": wall,
            "pages_peak": sch.phase_stats.get("pages_peak"),
        }

        # ---- drain-the-chunk baseline: static cohorts of `rows` ----------
        eng = DecodeEngine(cfg, params, plan_lib.plan_for_engine(
            cfg, slots=rows, cache_len=cache_len, sync_every=sync_every),
            eos_id=-1)
        clock, lat_d, toks_d, wall_d = 0.0, [], 0, 0.0
        order = sorted(range(n_requests), key=lambda i: arrivals[i])
        for c0 in range(0, n_requests, rows):
            cohort = order[c0:c0 + rows]
            start = max(clock, max(arrivals[i] for i in cohort))
            t0 = time.perf_counter()
            cdone = eng.run([Request(i, prompt, max_news[i]) for i in cohort])
            wall_d += time.perf_counter() - t0
            steps = eng.phase_stats["decode_chunks"] * sync_every
            clock = start + steps
            toks_d += sum(len(r.out) for r in cdone)
            lat_d += [clock - arrivals[r.rid] for r in cdone]
        row["drain"] = {
            "tokens": toks_d,
            "makespan_steps": clock,
            "goodput_tokens_per_step": toks_d / max(clock, 1e-9),
            "latency_p50_steps": float(np.percentile(lat_d, 50)),
            "latency_p99_steps": float(np.percentile(lat_d, 99)),
            "wall_s": wall_d,
        }
        row["goodput_ratio"] = (
            row["scheduler"]["goodput_tokens_per_step"] /
            max(row["drain"]["goodput_tokens_per_step"], 1e-9))
        out["cases"][name] = row
    lv = out["cases"]["low_variance"]["length_variance"]
    hv = out["cases"]["high_variance"]["length_variance"]
    out["variance_ratio"] = hv / max(lv, 1e-9)
    out["continuous_wins_at_high_variance"] = (
        out["cases"]["high_variance"]["goodput_ratio"] > 1.0)
    return out


# -------------------------------- ISSUE 4: shared-prefix arrival sweep
def shared_prefix_benchmark(arch: str = "qwen2.5-3b-reduced", rows: int = 3,
                            n_requests: int = 6, cache_len: int = 48,
                            page_size: int = 8, sync_every: int = 4,
                            prefix_len: int = 16, max_new: int = 6,
                            mean_gap: float = 2.0, seed: int = 0) -> Dict:
    """CoW prefix sharing under Poisson arrivals: the same request stream
    served with sharing ON vs OFF at a page pool deliberately too small for
    unshared admission to keep every row busy.

    Gated claims (scripts/perf_guard.py):
    * sharing admits strictly MORE concurrent requests at the same pool size
      (peak_live_rows) and peaks at strictly fewer distinct pages;
    * outputs are identical — sharing is a pure memory win;
    * the page-native prefill path allocates no dense (B, cache_len)
      KV transient: its per-layer buffer is the (B, tier) projection output
      itself (byte accounting below, tier << cache_len);
    * int8 KV pages record their quantized-vs-fp byte ratio.
    """
    import jax
    from repro.core import dataflow
    from repro.models import transformer as tfm
    from repro.serve import kvcache
    from repro.serve.engine import length_tier
    from repro.serve.scheduler import (ContinuousBatchingScheduler,
                                       StreamRequest)

    cfg = get_config(arch)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(seed)
    arrivals = _poisson_arrivals(n_requests, mean_gap, rng)
    prefix = [5 + (i % 90) for i in range(prefix_len)]
    prompts = [prefix + [2 + i, 3 + i] for i in range(n_requests)]
    plen = len(prompts[0])
    # pool sized so unshared admission cannot hold `rows` concurrent
    # requests at their final lengths, but shared admission can
    per_req = dataflow.pages_for(plen + max_new, page_size)
    shared_pages = dataflow.pages_for(prefix_len, page_size)
    num_pages = per_req + (rows - 1) * (per_req - shared_pages) \
        + shared_pages // 2

    def run(share: bool) -> Dict:
        sch = ContinuousBatchingScheduler(
            cfg, params, plan_lib.plan_for_scheduler(
                cfg, rows=rows, cache_len=cache_len, page_size=page_size,
                num_pages=num_pages, attn_path="paged", share_prefix=share,
                sync_every=sync_every),
            eos_id=-1)
        reqs = [StreamRequest(i, prompts[i], max_new, arrival=arrivals[i])
                for i in range(n_requests)]
        t0 = time.perf_counter()
        done = sch.run(reqs)
        wall = time.perf_counter() - t0
        toks = sum(len(r.out) for r in done)
        lat = [r.finished_at - r.arrival for r in done]
        makespan = sch.phase_stats["clock_steps"]
        return {
            "outputs": {r.rid: r.out for r in done},
            "tokens": toks,
            "makespan_steps": makespan,
            "goodput_tokens_per_step": toks / max(makespan, 1e-9),
            "latency_p50_steps": float(np.percentile(lat, 50)),
            "latency_p99_steps": float(np.percentile(lat, 99)),
            "peak_live_rows": sch.phase_stats["peak_live_rows"],
            "preemptions": sch.phase_stats["preemptions"],
            "cow_copies": sch.phase_stats["cow_copies"],
            "shared_tokens_admitted":
                sch.phase_stats["shared_tokens_admitted"],
            "pages_peak": sch.phase_stats["pages_peak"],
            "admission_wait_p99_steps": float(np.percentile(
                [r.admitted_at - r.arrival for r in done], 99)),
            "wall_s": wall,
        }

    shared = run(True)
    unshared = run(False)
    outputs_identical = shared.pop("outputs") == unshared.pop("outputs")

    # ---- prefill transient accounting: scatter path vs page-native ----
    tier = length_tier(plen, False, cache_len)
    n_glob = kvcache.num_global_layers(cfg)
    t_scatter = dataflow.prefill_kv_transient_bytes(
        rows, cache_len, cfg.num_kv_heads, cfg.head_dim, n_glob)
    t_paged = dataflow.prefill_kv_transient_bytes(
        rows, tier, cfg.num_kv_heads, cfg.head_dim, n_glob)

    return {
        "arch": arch, "rows": rows, "n_requests": n_requests,
        "cache_len": cache_len, "page_size": page_size,
        "prefix_len": prefix_len, "max_new": max_new,
        "num_pages": num_pages,
        "arrivals": [round(a, 2) for a in arrivals],
        "shared": shared,
        "unshared": unshared,
        "outputs_identical": outputs_identical,
        "concurrency_gain": (shared["peak_live_rows"]
                             - unshared["peak_live_rows"]),
        "goodput_ratio": (shared["goodput_tokens_per_step"] /
                          max(unshared["goodput_tokens_per_step"], 1e-9)),
        "prefill_transient": {
            "tier": tier,
            "scatter_path_bytes": t_scatter,       # PR 3: (B, cache_len) KV
            "paged_path_bytes": t_paged,           # now: the (B, tier) proj
            "bytes_saved": t_scatter - t_paged,
        },
        "kv_quant": _kv_quant_ratio(cfg, rows, cache_len, num_pages,
                                    page_size),
    }


# ------------------------------- ISSUE 6: overload + injected-fault sweep
def chaos_overload_benchmark(arch: str = "qwen2.5-3b-reduced", rows: int = 3,
                             n_requests: int = 12, cache_len: int = 48,
                             page_size: int = 4, sync_every: int = 4,
                             mean_gap: float = 0.5, seed: int = 0) -> Dict:
    """Overloaded Poisson stream through the serving guard, three ways:

    * ``shed_only`` — the degradation ladder restricted to its last rung:
      admission control sheds arrivals above the pressure threshold.
    * ``ladder``    — the full plan-authorized ladder (int8 pool
      requantization -> clamp max_new -> shed): graceful degradation should
      deliver at least the shed-only goodput while shedding no more.
    * ``faulted``   — shed_only again under a seeded ChaosConfig (spurious
      page-ensure failures, a transient step fault, one NaN poisoning):
      every request must still reach a terminal outcome, the pool must audit
      clean after every sync window (audit_every_sync raises otherwise), and
      every request that completes ``ok`` in both the faulted and the
      fault-free run must produce bit-identical tokens (greedy decode,
      pre-dispatch injection).

    Everything is measured on the deterministic virtual step clock, so
    perf_guard can gate shed rate and degraded goodput without wall-clock
    noise. Goodput counts only tokens of requests that resolved ``ok`` —
    shed/expired/failed work is not goodput by definition.
    """
    import jax
    from repro.core import dataflow
    from repro.models import transformer as tfm
    from repro.serve.chaos import ChaosConfig
    from repro.serve.guard import GuardConfig
    from repro.serve.scheduler import (ContinuousBatchingScheduler,
                                       StreamRequest)

    cfg = get_config(arch)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(seed)
    prompt = [5, 6, 7, 8]
    arrivals = _poisson_arrivals(n_requests, mean_gap, rng)
    max_news = [8 if i % 2 else 14 for i in range(n_requests)]
    # deliberately under-provisioned: 3 concurrent long requests need 15
    # pages, the pool holds 8 — pressure is the point of this sweep
    num_pages = 8
    assert num_pages < rows * dataflow.pages_for(
        len(prompt) + max(max_news), page_size)
    plan = plan_lib.plan_for_scheduler(
        cfg, rows=rows, cache_len=cache_len, page_size=page_size,
        num_pages=num_pages, attn_path="paged", sync_every=sync_every)
    guards = {
        "shed_only": GuardConfig(degrade_rungs=("shed",), shed_pressure=0.6,
                                 audit_every_sync=True),
        "ladder": GuardConfig(int8_pressure=0.45, clamp_pressure=0.6,
                              shed_pressure=0.8, clamp_max_new=4,
                              audit_every_sync=True),
    }
    # NaN targets rid 0: the longest early request, reliably still resident
    # at chunk 2 — its quarantine (outcome ``failed``) is part of the sweep
    chaos = ChaosConfig(seed=seed + 1, ensure_fail_rate=0.2,
                        ensure_fail_max=6, step_fail_chunks=(1,),
                        step_fail_attempts=2, nan_rids={2: (0,)})

    def run(guard, chaos_cfg=None) -> Dict:
        sch = ContinuousBatchingScheduler(cfg, params, plan, eos_id=-1,
                                          guard=guard)
        reqs = [StreamRequest(i, list(prompt), max_news[i],
                              arrival=arrivals[i])
                for i in range(n_requests)]
        t0 = time.perf_counter()
        done = sch.run(reqs, chaos=chaos_cfg)
        wall = time.perf_counter() - t0
        st = sch.phase_stats
        ok_toks = sum(len(r.out) for r in done if r.outcome.ok)
        makespan = st["clock_steps"]
        return {
            "outcomes": st["outcomes"],
            "all_terminal": len(done) == n_requests
            and all(r.outcome is not None for r in done),
            "shed_rate": st["outcomes"]["shed"] / n_requests,
            "ok_tokens": ok_toks,
            "makespan_steps": makespan,
            "goodput_tokens_per_step": ok_toks / max(makespan, 1e-9),
            "clamped_admissions": st["clamped_admissions"],
            "stalled_boundaries": st["stalled_boundaries"],
            "preemptions": st["preemptions"],
            "kv_quant_final": st["kv_quant"],
            "chaos_injected": st.get("chaos_injected"),
            "pool_audit_clean": True,    # audit_every_sync raises otherwise
            "wall_s": wall,
            "_tokens": {r.rid: list(r.out) for r in done if r.outcome.ok},
        }

    out: Dict = {
        "arch": arch, "rows": rows, "n_requests": n_requests,
        "cache_len": cache_len, "page_size": page_size,
        "num_pages": num_pages, "sync_every": sync_every,
        "mean_gap": mean_gap,
        "arrivals": [round(a, 2) for a in arrivals],
        "max_new": max_news,
    }
    shed_only = run(guards["shed_only"])
    ladder = run(guards["ladder"])
    faulted = run(guards["shed_only"], chaos)
    both_ok = set(shed_only["_tokens"]) & set(faulted["_tokens"])
    out["survivors_bit_identical"] = all(
        shed_only["_tokens"][rid] == faulted["_tokens"][rid]
        for rid in both_ok)
    out["survivors_compared"] = len(both_ok)
    for name, row in (("shed_only", shed_only), ("ladder", ladder),
                      ("faulted", faulted)):
        row.pop("_tokens")
        out[name] = row
    out["goodput_vs_shed_only"] = (
        ladder["goodput_tokens_per_step"] /
        max(shed_only["goodput_tokens_per_step"], 1e-9))
    return out


# -------------------------- ISSUE 7: multi-replica failover trace simulator
def replica_failover_benchmark(arch: str = "qwen2.5-3b-reduced",
                               rows: int = 2, n_requests: int = 12,
                               cache_len: int = 48, page_size: int = 4,
                               sync_every: int = 4, replicas: int = 3,
                               kill_step: float = 8.0, mean_gap: float = 1.0,
                               seed: int = 0) -> Dict:
    """Multi-replica trace simulator: the --arrivals Poisson sweep through
    the replica control plane (serve/replica.py), three ways:

    * ``fault_free``  — N replicas, prefix-affinity routing: the goodput
      baseline, plus the CoW page-sharing the router's placement achieves
      on shared-system-prompt traffic.
    * ``no_affinity`` — identical traffic with affinity off (pure
      least-depth placement, the round-robin-equivalent spread): the
      sharing comparison behind the ``router-prefix-affinity`` gate —
      affinity must win strictly, or the placement rule is dead weight.
    * ``killed``      — replica 0 killed mid-sweep at ``kill_step``:
      stranded requests migrate by recompute; every request must still end
      in exactly one terminal outcome, every request that completes ``ok``
      in both runs must produce bit-identical tokens (greedy decode on the
      shared virtual clock), and fleet goodput must hold the
      ``failover-goodput-floor`` (>= 0.9x fault-free with 1 of
      ``replicas`` lost — the recompute tax, not a collapse).

    Goodput counts ok-tokens per virtual step of fleet makespan; all three
    runs are seed-deterministic, so perf_guard gates them wall-clock-free.
    """
    import jax
    from repro.core import dataflow
    from repro.models import transformer as tfm
    from repro.serve.chaos import ReplicaChaosConfig
    from repro.serve.replica import ReplicaSet
    from repro.serve.router import RouterConfig
    from repro.serve.scheduler import StreamRequest

    cfg = get_config(arch)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(seed)
    arrivals = _poisson_arrivals(n_requests, mean_gap, rng)
    max_news = [6 if i % 2 else 10 for i in range(n_requests)]
    # two distinct system prompts (two full pages each) + a per-request
    # tail, interleaved across arrivals: affinity routing partitions each
    # prompt group onto its home replica for maximal CoW sharing, while
    # depth-based placement interleaves the groups so co-resident requests
    # hold mismatched prefixes — the traffic shape the placement rule
    # exists for
    sys_prompts = [[11, 12, 13, 14, 15, 16, 17, 18],
                   [21, 22, 23, 24, 25, 26, 27, 28]]
    num_pages = rows * dataflow.pages_for(cache_len, page_size)
    plan = plan_lib.plan_for_scheduler(
        cfg, rows=rows, cache_len=cache_len, page_size=page_size,
        num_pages=num_pages, attn_path="paged", sync_every=sync_every)

    def reqs():
        return [StreamRequest(i, sys_prompts[i % 2] + [30 + i], max_news[i],
                              arrival=arrivals[i], tenant="t%d" % (i % 3))
                for i in range(n_requests)]

    def run(affinity: bool = True, chaos=None) -> Dict:
        rs = ReplicaSet(cfg, params, plan, replicas=replicas, eos_id=-1,
                        router=RouterConfig(affinity=affinity))
        t0 = time.perf_counter()
        done = rs.run(reqs(), chaos=chaos)
        wall = time.perf_counter() - t0
        st = rs.phase_stats
        ok_toks = sum(len(r.out) for r in done if r.outcome.ok)
        makespan = st["clock_steps"]
        return {
            "outcomes": st["outcomes"],
            "all_terminal": len(done) == n_requests
            and all(r.outcome is not None for r in done),
            "ok_tokens": ok_toks,
            "makespan_steps": makespan,
            "goodput_tokens_per_step": ok_toks / max(makespan, 1e-9),
            "failovers": st["failovers"],
            "migrated_requests": st["migrated_requests"],
            "shared_tokens_admitted": st["fleet"]["shared_tokens_admitted"],
            "router": st["router"],
            # per-tenant goodput + admission-wait percentiles (ISSUE 8):
            # requests interleave tenants t0/t1/t2, so tail-wait skew
            # between tenants is the per-tenant fairness signal
            "tenants": st["tenants"],
            "wall_s": wall,
            "_tokens": {r.rid: list(r.out) for r in done if r.outcome.ok},
            "_migrated": {r.rid for r in done if r.migrations > 0},
        }

    out: Dict = {
        "arch": arch, "rows": rows, "replicas": replicas,
        "n_requests": n_requests, "cache_len": cache_len,
        "page_size": page_size, "num_pages": num_pages,
        "sync_every": sync_every, "kill_step": kill_step,
        "mean_gap": mean_gap,
        "arrivals": [round(a, 2) for a in arrivals],
        "max_new": max_news,
    }
    fault_free = run(affinity=True)
    no_affinity = run(affinity=False)
    killed = run(affinity=True,
                 chaos=ReplicaChaosConfig(kill_at_step={0: kill_step}))
    # survivors = requests that never migrated AND completed ok in both
    # runs; bit-identity there proves replica loss never perturbs work
    # that stayed on healthy replicas. Migrated requests are compared too
    # (greedy recompute is exact) but reported separately.
    survivors = [rid for rid in
                 set(fault_free["_tokens"]) & set(killed["_tokens"])
                 if rid not in killed["_migrated"]]
    out["survivors_bit_identical"] = all(
        fault_free["_tokens"][rid] == killed["_tokens"][rid]
        for rid in survivors)
    out["survivors_compared"] = len(survivors)
    out["migrated_bit_identical"] = all(
        fault_free["_tokens"][rid] == killed["_tokens"][rid]
        for rid in killed["_migrated"] if rid in fault_free["_tokens"])
    for name, row in (("fault_free", fault_free),
                      ("no_affinity", no_affinity), ("killed", killed)):
        row.pop("_tokens")
        row["migrated_rids"] = sorted(row.pop("_migrated"))
        out[name] = row
    out["failover_goodput_ratio"] = (
        killed["goodput_tokens_per_step"] /
        max(fault_free["goodput_tokens_per_step"], 1e-9))
    out["affinity_sharing_ratio"] = (
        fault_free["shared_tokens_admitted"] /
        max(no_affinity["shared_tokens_admitted"], 1))
    return out


def telemetry_benchmark(arch: str = "qwen2.5-3b-reduced",
                        n_requests: int = 6, cache_len: int = 64,
                        page_size: int = 4, sync_every: int = 4) -> Dict:
    """Observability sweep (ISSUE 8) behind two perf_guard gates:

    * ``trace-deterministic`` — two same-seed chaos runs (allocation
      failures + transient step faults + NaN poisoning) must produce
      identical trace signatures (wall-clock annotations stripped): the
      trace structure is a pure function of the seed, so a diverging trace
      is itself a determinism regression detector.
    * ``plan-drift-clean`` — Eyexam at runtime, both directions: a plan
      resolved from an *accurate* expected_len_dist must yield a clean
      DriftReport, and a plan provisioned for 40-token requests serving
      8-token traffic must emit a report naming the attention (paging)
      decision as CONFIRMED divergent. A detector that never fires is as
      dead as one that always fires.
    """
    import jax
    from repro.models import transformer as tfm
    from repro.serve import LLM
    from repro.serve.chaos import ChaosConfig
    from repro.serve.scheduler import StreamRequest

    cfg = get_config(arch)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)

    def plan(mean: float):
        return plan_lib.plan_serve(
            cfg, hbm_budget_bytes=1 << 30, expected_batch=3,
            expected_len_dist={"mean": mean, "max": cache_len},
            page_size=page_size, sync_every=sync_every)

    def reqs(max_new: int):
        return [StreamRequest(rid=i, prompt=[3 + i % 4, 5, 7],
                              max_new=max_new, arrival=float(i),
                              tenant="t%d" % (i % 2))
                for i in range(n_requests)]

    def chaos_run():
        llm = LLM(cfg, params, plan(16), eos_id=-1)
        llm.stream(reqs(13), chaos=ChaosConfig(
            seed=7, ensure_fail_rate=0.3, step_fail_chunks=(1,),
            nan_rids={2: (1,)}))
        return llm.telemetry()

    a, b = chaos_run(), chaos_run()
    sig = a.tracer.signature()

    # accurate plan (mean 16 vs measured 3 prompt + 13 generated = 16)
    llm = LLM(cfg, params, plan(16), eos_id=-1)
    llm.stream(reqs(13))
    clean = llm.telemetry().last_drift

    # mispredicted plan: provisioned for mean 40, serving 8-token requests
    llm = LLM(cfg, params, plan(40), eos_id=-1)
    llm.stream(reqs(5))
    drifted = llm.telemetry().last_drift

    return {
        "arch": arch, "n_requests": n_requests, "cache_len": cache_len,
        "page_size": page_size, "sync_every": sync_every,
        "trace_deterministic": sig == b.tracer.signature(),
        "span_count": len(a.tracer.events),
        "span_categories": sorted({e.cat for e in a.tracer.events}),
        "chaos_injected_kinds": sorted(
            {e.args["kind"] for e in a.tracer.events
             if e.name == "chaos_inject"}),
        "clean_drift": clean.summary(),
        "clean_report": clean.render(),
        "forced_drift": drifted.summary(),
        "forced_report": drifted.render(),
        "forced_names_attention": any(
            f.startswith("attention.") for f in
            drifted.summary()["confirmed"]),
    }


def _print_telemetry(tl: Dict) -> None:
    print(f"=== Telemetry sweep ({tl['arch']}, {tl['n_requests']} reqs) ===")
    print(f"  chaos trace deterministic: {tl['trace_deterministic']} "
          f"({tl['span_count']} spans, cats {tl['span_categories']}, "
          f"injected {tl['chaos_injected_kinds']})")
    print(f"  accurate plan drift: {tl['clean_drift']['confirmed'] or 'clean'}"
          f" over {tl['clean_drift']['windows']} windows")
    print(f"  mispredicted plan drift: {tl['forced_drift']['confirmed']} "
          f"(names attention: {tl['forced_names_attention']})")


def _print_replica_failover(rf: Dict) -> None:
    print(f"=== Replica failover sweep ({rf['replicas']} replicas x "
          f"{rf['rows']} rows, {rf['n_requests']} reqs, kill replica 0 @ "
          f"step {rf['kill_step']:g}) ===")
    for name in ("fault_free", "no_affinity", "killed"):
        c = rf[name]
        print(f"  {name:11s}: goodput {c['goodput_tokens_per_step']:.3f} "
              f"tok/step  makespan {c['makespan_steps']:.0f}  "
              f"ok {c['outcomes']['ok']}  failovers {c['failovers']}  "
              f"migrated {c['migrated_requests']}  "
              f"shared_toks {c['shared_tokens_admitted']}")
    print(f"  failover goodput x{rf['failover_goodput_ratio']:.2f} of "
          f"fault-free; survivors bit-identical: "
          f"{rf['survivors_bit_identical']} "
          f"({rf['survivors_compared']} compared, migrated identical: "
          f"{rf['migrated_bit_identical']}); affinity sharing "
          f"x{rf['affinity_sharing_ratio']:.1f} vs no-affinity")
    for tenant, t in rf["fault_free"]["tenants"].items():
        print(f"    tenant {tenant}: ok {t['ok_requests']:.0f}  goodput "
              f"{t['goodput_tokens']:.0f} tok  admission wait p50 "
              f"{t['admission_wait_p50_steps']:.0f} / p99 "
              f"{t['admission_wait_p99_steps']:.0f} steps")


def _print_chaos(ch: Dict) -> None:
    print(f"=== Overload + chaos sweep ({ch['rows']} rows, "
          f"{ch['n_requests']} reqs, {ch['num_pages']} pages) ===")
    for name in ("shed_only", "ladder", "faulted"):
        c = ch[name]
        oc = c["outcomes"]
        print(f"  {name:9s}: goodput {c['goodput_tokens_per_step']:.3f} "
              f"tok/step  shed {oc['shed']}  ok {oc['ok']}  "
              f"failed {oc['failed']}  clamped {c['clamped_admissions']}  "
              f"kv={c['kv_quant_final']}")
    print(f"  ladder/shed_only goodput x{ch['goodput_vs_shed_only']:.2f}; "
          f"faulted survivors bit-identical: "
          f"{ch['survivors_bit_identical']} "
          f"({ch['survivors_compared']} compared)")


def _kv_quant_ratio(cfg, rows, cache_len, num_pages, page_size) -> Dict:
    """Quantized-vs-fp byte accounting for the paged cache (int8 payload +
    per-page scale tables vs bf16) — the recorded ratio the guard checks."""
    from repro.serve import kvcache
    fp_b = kvcache.paged_cache_bytes(cfg, rows, cache_len, num_pages,
                                     page_size, "fp")
    i8_b = kvcache.paged_cache_bytes(cfg, rows, cache_len, num_pages,
                                     page_size, "int8")
    return {"fp_cache_bytes": fp_b, "int8_cache_bytes": i8_b,
            "int8_vs_fp_ratio": i8_b / max(fp_b, 1)}


# ------------------------------ ISSUE 9: speculative decode on CoW pages
def spec_decode_benchmark(arch: str = "qwen2.5-3b-reduced", spec_k: int = 4,
                          max_new: int = 16, cache_len: int = 64,
                          sync_every: int = 4, batches=(1, 4),
                          repeats: int = 3, seed: int = 7) -> Dict:
    """Draft/verify speculation (serve.scheduler spec chunks) vs the
    sequential greedy baseline, batch {1, 4}.

    The gated speedup is measured on the **deterministic dispatch clock**
    (the same convention as the arrivals and chaos sweeps): the baseline
    retires exactly one token per row per decode step, so its dispatch
    count IS its token count, while a speculative round retires the
    accepted-prefix length against one flattened k-position verify. With
    bit-identical outputs (asserted per batch) the ratio

        baseline decode steps / speculative verify rounds

    is the tokens-per-dispatch speedup — CI-stable, wall-clock-free.
    Wall seconds are recorded alongside (best-of-``repeats``) but never
    gated. ``verify_hbm_bytes`` models the price: one round streams the
    weights once but the resident cache ``spec_k`` times, which is why the
    plan only speculates where the weight stream dominates (batch 1).
    """
    import jax
    from repro.models import transformer as tfm
    from repro.serve import kvcache
    from repro.serve.scheduler import (ContinuousBatchingScheduler,
                                       StreamRequest)

    cfg = get_config(arch)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    # the batch-1 prompt is chosen for a non-degenerate draft (the bigram
    # self-draft on this seed accepts ~half its candidates, not all of them)
    base_prompts = [[9, 8, 7], [5, 6, 7, 8], [3, 1, 4, 1, 5], [2, 7, 1, 8]]
    w_bytes = cfg.param_count(active_only=True) * 2
    out: Dict = {"arch": arch, "spec_k": spec_k, "max_new": max_new,
                 "cache_len": cache_len, "sync_every": sync_every,
                 "repeats": repeats, "alpha_assumed": plan_lib.SPEC_ALPHA,
                 "batches": {}}
    for b in batches:
        prompts = [base_prompts[i % len(base_prompts)] for i in range(b)]
        plans = {k: plan_lib.plan_serve(
            cfg, hbm_budget_bytes=1 << 30, expected_batch=b,
            expected_len_dist={"mean": (max(map(len, prompts)) + max_new),
                               "max": cache_len},
            page_size=8, attn_path="paged", sync_every=sync_every,
            spec_k=k) for k in (0, spec_k)}
        row: Dict = {}
        for name, k in (("baseline", 0), ("spec", spec_k)):
            sch = ContinuousBatchingScheduler(cfg, params, plans[k],
                                              eos_id=-1)
            runs = []
            for rep in range(repeats + 1):       # first run = warmup/compile
                reqs = [StreamRequest(i, list(p), max_new)
                        for i, p in enumerate(prompts)]
                t0 = time.perf_counter()
                done = sch.run(reqs, rng=jax.random.PRNGKey(seed))
                runs.append((time.perf_counter() - t0,
                             dict(sch.phase_stats),
                             {r.rid: r.out for r in done}))
            wall, st, toks = min(runs[1:], key=lambda r: r[0])
            n_tok = sum(len(t) for t in toks.values())
            dispatches = (st["spec_rounds"] if k else st["decode_steps"])
            row[name] = {
                "tokens": n_tok,
                "wall_s": wall,
                "tokens_per_s_wall": n_tok / max(wall, 1e-9),
                "decode_dispatches": dispatches,
                "tokens_per_dispatch": n_tok / max(dispatches, 1),
                "outputs": toks,
            }
            if k:
                drafted = st["spec_drafted_tokens"]
                row[name]["acceptance_rate"] = (
                    st["spec_accepted_tokens"] / max(drafted, 1))
                row[name]["spec_rounds"] = st["spec_rounds"]
                row[name]["spec_drafted_tokens"] = drafted
                row[name]["spec_accepted_tokens"] = st["spec_accepted_tokens"]
        c_bytes = kvcache.cache_bytes(cfg, b, cache_len)
        row["hbm_model"] = {
            # per retired token: baseline streams weights+cache once/token;
            # one spec round streams weights once + cache spec_k times for
            # E[n] = acceptance-run tokens
            "baseline_step_bytes": w_bytes + c_bytes,
            "verify_round_bytes": w_bytes + spec_k * c_bytes,
            "verify_bytes_per_token": (w_bytes + spec_k * c_bytes) /
            max(row["spec"]["tokens_per_dispatch"], 1e-9),
        }
        row["greedy_bit_exact"] = (row["baseline"].pop("outputs")
                                   == row["spec"].pop("outputs"))
        row["speedup_tokens_per_dispatch"] = (
            row["spec"]["tokens_per_dispatch"] /
            max(row["baseline"]["tokens_per_dispatch"], 1e-9))
        row["speedup_wall"] = (row["spec"]["tokens_per_s_wall"] /
                               max(row["baseline"]["tokens_per_s_wall"],
                                   1e-9))
        out["batches"][str(b)] = row
    return out


def shard_proxy_benchmark(cases=(("gemma2-2b-reduced", "tp=2"),
                                 ("mixtral-8x7b-reduced", "ep=4")),
                          max_new: int = 10, seed: int = 7) -> Dict:
    """Mesh-sharded stream() vs single-device (ISSUE 10): per-token
    bit-identity (gated: sharded-outputs-identical), per-device KV pool
    bytes vs the 1/tp ideal (gated: sharded-pool-bytes-per-device), and the
    analytic collective traffic the scheduler counted. Logical mesh — the
    shard-explicit program is the same math on any host, which is exactly
    the property the gate pins."""
    import jax
    from repro.models import transformer as tfm
    from repro.serve.facade import LLM

    out: Dict = {"max_new": max_new, "cases": {}}
    kw = dict(hbm_budget_bytes=1 << 30, expected_batch=3,
              expected_len_dist={"mean": 10, "max": 64}, page_size=4,
              sync_every=4)
    for arch, mesh in cases:
        cfg = get_config(arch)
        params = tfm.init_params(jax.random.PRNGKey(0), cfg)
        reqs = [([5, 7, 11], max_new), ([3, 2, 9, 4], max_new - 2)]
        single = plan_lib.plan_serve(cfg, **kw)
        sharded = plan_lib.plan_serve(cfg, mesh=mesh, **kw)
        o1 = [r.out for r in LLM(cfg, params, single)
              .stream(reqs, rng=jax.random.PRNGKey(seed))]
        llm = LLM(cfg, params, sharded)
        o2 = [r.out for r in llm.stream(reqs, rng=jax.random.PRNGKey(seed))]
        rep = llm.sharding_report()
        snap = llm.telemetry().metrics.snapshot()
        out["cases"][f"{arch}@{mesh}"] = {
            "arch": arch, "mesh": mesh, "tp": sharded.tp, "ep": sharded.ep,
            "devices": sharded.mesh_devices, "paged": sharded.paged,
            "outputs_identical": o1 == o2,
            "tokens": sum(len(t) for t in o2),
            "kv_bytes_single_device": rep["kv_bytes_single_device"],
            "kv_bytes_per_device": rep["kv_bytes_per_device"],
            # page-rounding slack for the pool gate: one page frame's
            # local bytes (the per-device pool is whole frames)
            "page_frame_bytes_per_device": (
                rep["kv_bytes_per_device"] // max(sharded.num_pages, 1)
                if sharded.paged else 0),
            "lockstep_divergence": rep.get("lockstep_divergence", 0),
            "collective_ops": snap.counters["collective_ops"],
            "collective_allgather_bytes":
                snap.counters["collective_allgather_bytes"],
        }
    return out


def _print_shard(sp: Dict) -> None:
    print("=== Mesh-sharded serving vs single-device ===")
    for name, c in sp["cases"].items():
        print(f"  {name}: tp={c['tp']} ep={c['ep']} "
              f"bit-identical: {c['outputs_identical']} "
              f"({c['tokens']} tokens), lockstep divergence "
              f"{c['lockstep_divergence']}")
        if c["paged"]:
            print(f"           pool/device {c['kv_bytes_per_device']:,} B "
                  f"vs single-device {c['kv_bytes_single_device']:,} B "
                  f"(1/{c['tp']} heads)")
        print(f"           collectives: {c['collective_ops']} all-gathers, "
              f"{c['collective_allgather_bytes']:,} B")


def _print_spec(spd: Dict) -> None:
    print(f"=== Speculative decode on CoW pages ({spd['arch']}, "
          f"k={spd['spec_k']}, {spd['max_new']} new tokens) ===")
    for b, row in spd["batches"].items():
        sp = row["spec"]
        print(f"  batch {b}: {row['speedup_tokens_per_dispatch']:.2f}x "
              f"tokens/dispatch ({sp['tokens_per_dispatch']:.2f} vs "
              f"{row['baseline']['tokens_per_dispatch']:.2f}), "
              f"wall x{row['speedup_wall']:.2f}, acceptance "
              f"{sp['acceptance_rate']:.0%} "
              f"({sp['spec_accepted_tokens']}/{sp['spec_drafted_tokens']}), "
              f"bit-exact: {row['greedy_bit_exact']}")
        hm = row["hbm_model"]
        print(f"           verify round {hm['verify_round_bytes']:,} B vs "
              f"step {hm['baseline_step_bytes']:,} B "
              f"({hm['verify_bytes_per_token']:,.0f} B/token)")


# --------------------------------------------------------- engine benchmark
def decode_benchmark(batches=(1, 4, 8), max_new: int = 8,
                     arch: str = "qwen2.5-3b-reduced",
                     sparsity: float = 0.75, sync_every: int = 4,
                     repeats: int = 5, prepacked=None) -> Dict:
    """DecodeEngine tokens/sec, dense vs BCSC-packed MLP weights.

    On this CPU container kernels run interpret=True, so the sparse wall-clock
    is NOT the headline (Python-interpreted kernels); the grid-step/bytes
    proxies (mlp_proxy) carry the perf claim. On TPU the same harness times
    the compiled kernels. host_syncs per generated token is reported as the
    device-residency check (must be << 1). Timing is best-of-``repeats``
    (interleaved warm engines — the min is the standard noise-robust
    estimator on a shared CPU; single-shot runs here vary ±30%); ``phases``
    reports the best run's batched-prefill/decode wall-clock split and pad
    overhead.
    """
    import jax
    from repro.serve.engine import DecodeEngine, Request

    # ``prepacked``: reuse a (cfg, params, packed, stats) tuple from
    # _pruned_packed instead of re-pruning+encoding the whole model
    cfg, params, packed, stats = prepacked or _pruned_packed(arch, sparsity)

    from repro.serve import sparse as sps
    out: Dict = {"arch": arch, "sparsity": sparsity, "max_new": max_new,
                 "block_density": stats.get("block_density"),
                 "packing_efficiency": stats.get("packing_efficiency"),
                 "packed_weight_bytes": sps.packed_bytes(packed),
                 "interpret_mode": jax.default_backend() != "tpu",
                 "repeats": repeats, "batches": {}}
    for b in batches:
        row: Dict = {}
        engines = {}
        for name, p in (("dense", params), ("sparse", packed)):
            eng = DecodeEngine(cfg, p, plan_lib.plan_for_engine(
                cfg, slots=b, cache_len=32, sync_every=sync_every),
                eos_id=-1)
            eng.run([Request(rid=99, prompt=[5, 6, 7, 8], max_new=max_new)
                     for _ in range(b)])          # warmup / compile
            engines[name] = eng
        times: Dict[str, List] = {n: [] for n in engines}
        for _ in range(repeats):
            for name, eng in engines.items():     # interleaved A/B
                reqs = [Request(rid=i, prompt=[5, 6, 7, 8], max_new=max_new)
                        for i in range(b)]
                eng.host_syncs = 0
                t0 = time.perf_counter()
                done = eng.run(reqs)
                times[name].append((time.perf_counter() - t0,
                                    dict(eng.phase_stats), eng.host_syncs))
        for name, eng in engines.items():
            toks = b * max_new
            dt, st, syncs = min(times[name], key=lambda r: r[0])
            row[name] = {
                "tokens_per_s": toks / max(dt, 1e-9),
                "host_syncs_per_token": syncs / max(toks, 1),
                "phases": {
                    "prefill_s": st["prefill_s"],
                    "decode_s": st["decode_s"],
                    "prefill_batches": st["prefill_batches"],
                    "prefill_prompts": st["prefill_prompts"],
                    "prefill_real_tokens": st["prefill_real_tokens"],
                    "prefill_padded_tokens": st["prefill_padded_tokens"],
                },
            }
        row["e2e_ratio"] = (row["sparse"]["tokens_per_s"] /
                            max(row["dense"]["tokens_per_s"], 1e-9))
        out["batches"][str(b)] = row
    if "1" in out["batches"]:
        out["e2e_ratio_b1"] = out["batches"]["1"]["e2e_ratio"]
        out["pr1_baseline_e2e_ratio_b1"] = PR1_E2E_RATIO_B1
        out["improves_pr1_baseline"] = (
            out["e2e_ratio_b1"] > PR1_E2E_RATIO_B1)
    return out


def _print_shared_prefix(sp: Dict) -> None:
    s, u = sp["shared"], sp["unshared"]
    print(f"=== Shared-prefix arrivals: CoW sharing vs unshared "
          f"({sp['rows']} rows, {sp['n_requests']} reqs, "
          f"{sp['prefix_len']}-token prefix, {sp['num_pages']} pages) ===")
    print(f"  shared  : peak {s['peak_live_rows']} rows, "
          f"{s['pages_peak']['pages_used']} pages, "
          f"{s['goodput_tokens_per_step']:.3f} tok/step, "
          f"admit-wait p99 {s['admission_wait_p99_steps']:.0f}, "
          f"{s['cow_copies']} CoW copies")
    print(f"  unshared: peak {u['peak_live_rows']} rows, "
          f"{u['pages_peak']['pages_used']} pages, "
          f"{u['goodput_tokens_per_step']:.3f} tok/step, "
          f"admit-wait p99 {u['admission_wait_p99_steps']:.0f}")
    pt = sp["prefill_transient"]
    print(f"  prefill KV transient: paged {pt['paged_path_bytes']} B "
          f"(tier {pt['tier']}) vs scatter {pt['scatter_path_bytes']} B "
          f"(dense cache_len rows)")
    kq = sp["kv_quant"]
    print(f"  int8 KV pages: {kq['int8_cache_bytes']} B vs fp "
          f"{kq['fp_cache_bytes']} B ({kq['int8_vs_fp_ratio']:.2f}x), "
          f"outputs identical: {sp['outputs_identical']}")


def main(smoke: bool = False, engine: bool = True, repeats: int = None,
         arrivals: bool = True) -> Dict:
    sparsity = 0.75
    prepacked = _pruned_packed("qwen2.5-3b-reduced", sparsity)
    stats = prepacked[3]
    res: Dict = {
        "analytic": {
            "mlp_megakernel": mlp_bound_analysis(
                packing_efficiency=stats.get("packing_efficiency", 0.93)),
            "decode_regimes": decode_regimes(),
        },
        "kernel_proxy": kernel_proxy(),
        "mlp_proxy": mlp_proxy(sparsity=sparsity, stats=stats),
        "paged": paged_proxy(),
        # resolved ServePlans for the seed configs at the canonical snapshot
        # inputs — perf_guard's `plan-snapshot-stable` gate compares these
        # against scripts/golden_plans.json (silent dispatch drift fails CI)
        "plans": {arch: plan_lib.snapshot_plan(arch).as_dict()
                  for arch in plan_lib.SNAPSHOT_CONFIGS},
        # mesh-sharded plans (ISSUE 10) at the canonical 2 mesh shapes —
        # perf_guard's `sharded-plan-snapshot-stable` gate compares these
        # against golden_plans.json["__sharded__"]
        "sharded_plans": {
            arch: {mesh: plan_lib.snapshot_sharded_plan(arch, mesh)
                   .as_dict()
                   for mesh in plan_lib.SHARDED_SNAPSHOT_MESHES}
            for arch in plan_lib.SHARDED_SNAPSHOT_CONFIGS},
    }
    if engine:
        # seeded + dispatch-clock metrics: the spec-decode gates are
        # wall-clock-free like every other scheduler sweep
        res["spec_proxy"] = spec_decode_benchmark(
            repeats=2 if smoke else 3)
        # seeded, wall-clock-free: the sharded bit-identity and pool gates
        res["shard_proxy"] = shard_proxy_benchmark()
        res["decode"] = decode_benchmark(
            batches=(1,) if smoke else (1, 4, 8),
            max_new=8,
            sparsity=sparsity,
            repeats=repeats or (5 if smoke else 7),
            prepacked=prepacked)
    if engine and arrivals:
        res["arrivals"] = arrival_benchmark(
            n_requests=6 if smoke else 9)
        res["shared_prefix"] = shared_prefix_benchmark(
            n_requests=4 if smoke else 6)
        # not scaled down in smoke: the shed/goodput gates need the exact
        # overload profile the guard thresholds were tuned against
        res["chaos"] = chaos_overload_benchmark()
        # likewise exact: the failover/affinity gates compare seeded runs
        res["replica_failover"] = replica_failover_benchmark()
        # seeded, wall-clock-free: the trace-determinism and drift gates
        res["telemetry"] = telemetry_benchmark()

    kp = res["kernel_proxy"]
    print("=== Batch-1 BCSC GEMV vs dense RS grid steps "
          f"({kp['shape'][0]}x{kp['shape'][1]}, {kp['block']}-blocks) ===")
    print(f"dense grid steps: {kp['dense_grid_steps']}")
    for k in sorted(k for k in kp if k.startswith("sparsity_")):
        r = kp[k]
        print(f"  {k[9:]:>5s} block-sparse: {r['gemv_grid_steps']:5d} steps "
              f"-> {r['speedup_vs_dense']:.2f}x fewer")

    mp = res["mlp_proxy"]
    print(f"=== Fused bcsc_mlp vs two-call @ {mp['sparsity']:.0%} sparsity "
          f"({mp['arch']}) ===")
    for side in ("two_call", "fused"):
        r = mp[side]
        wc = f"  {r['work_chunks']:4d} work chunks" if "work_chunks" in r \
            else ""
        print(f"  {side:9s}: {r['grid_steps']:5d} grid steps  "
              f"{r['block_visits']:5d} block visits  "
              f"{r['hbm_bytes']:8d} HBM bytes  "
              f"{r['kernel_launches']:3d} launches{wc}")
    rr = mp["ratios"]
    print(f"  fused wins: {rr['grid_steps']:.2f}x steps, "
          f"{rr['hbm_bytes']:.2f}x bytes "
          f"(packing efficiency {mp['packing_efficiency']:.2f})")

    if engine:
        d = res["decode"]
        mode = "interpret (proxy only)" if d["interpret_mode"] else "compiled"
        print(f"=== DecodeEngine tokens/sec [{mode}] "
              f"{d['arch']} @ {d['sparsity']:.0%} sparsity ===")
        for b, row in d["batches"].items():
            ph = row["sparse"]["phases"]
            print(f"  batch {b}: dense {row['dense']['tokens_per_s']:8.2f} t/s"
                  f"  sparse {row['sparse']['tokens_per_s']:8.2f} t/s"
                  f"  ratio {row['e2e_ratio']:.3f}"
                  f"  (prefill {ph['prefill_s']*1e3:.1f}ms/"
                  f"{ph['prefill_batches']}b, decode {ph['decode_s']*1e3:.1f}ms,"
                  f" syncs/tok {row['sparse']['host_syncs_per_token']:.3f})")
        if "e2e_ratio_b1" in d:
            verdict = "improves" if d["improves_pr1_baseline"] else "REGRESSES"
            print(f"  batch-1 e2e sparse/dense ratio {d['e2e_ratio_b1']:.3f} "
                  f"{verdict} PR 1 baseline {PR1_E2E_RATIO_B1}")

    pg = res["paged"]
    print(f"=== Paged KV proxy ({pg['arch']}, {pg['rows']} rows x "
          f"{pg['cache_len']} ctx, {pg['page_size']}-token pages, "
          f"{pg['mean_occupancy']:.0%} occupancy) ===")
    print(f"  dense slot {pg['dense_slot_bytes']:9d} B  "
          f"paged {pg['paged_bytes']:9d} B  "
          f"({pg['bytes_ratio']:.2f}x smaller)")
    print(f"  kernel blocks {pg['work_blocks']} <= ceil-blocks "
          f"{pg['ceil_blocks']} (padded grid {pg['padded_grid_blocks']}, "
          f"{pg['pages_per_block']} pages a block)")

    if "arrivals" in res:
        ar = res["arrivals"]
        print(f"=== Poisson arrivals: continuous batching vs drain "
              f"({ar['rows']} rows, {ar['n_requests']} reqs, "
              f"variance x{ar['variance_ratio']:.0f}) ===")
        for name, c in ar["cases"].items():
            s, dr = c["scheduler"], c["drain"]
            print(f"  {name:14s}: sched {s['goodput_tokens_per_step']:.3f} "
                  f"tok/step p50 {s['latency_p50_steps']:.0f} "
                  f"p99 {s['latency_p99_steps']:.0f}"
                  f"  | drain {dr['goodput_tokens_per_step']:.3f} tok/step "
                  f"p50 {dr['latency_p50_steps']:.0f} "
                  f"p99 {dr['latency_p99_steps']:.0f}"
                  f"  -> goodput x{c['goodput_ratio']:.2f}")
        verdict = "beats" if ar["continuous_wins_at_high_variance"] \
            else "LOSES TO"
        print(f"  continuous batching {verdict} drain-the-chunk at high "
              f"length variance")

    if "spec_proxy" in res:
        _print_spec(res["spec_proxy"])

    if "shard_proxy" in res:
        _print_shard(res["shard_proxy"])

    if "shared_prefix" in res:
        _print_shared_prefix(res["shared_prefix"])

    if "chaos" in res:
        _print_chaos(res["chaos"])

    if "replica_failover" in res:
        _print_replica_failover(res["replica_failover"])

    if "telemetry" in res:
        _print_telemetry(res["telemetry"])

    with open(BENCH_JSON, "w") as f:
        json.dump(res, f, indent=2, default=float)
    print(f"wrote {BENCH_JSON}")
    return res


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="batch 1 only (CI)")
    ap.add_argument("--no-engine", action="store_true",
                    help="skip the DecodeEngine wall-clock section")
    ap.add_argument("--no-arrivals", action="store_true",
                    help="skip the Poisson-arrival scheduler-vs-drain sweep")
    ap.add_argument("--arrivals", action="store_true",
                    help="run ONLY the arrival sweep (+paged proxy), merging "
                         "into an existing BENCH json")
    ap.add_argument("--repeats", type=int, default=None,
                    help="timing repeats per engine config (best-of)")
    args = ap.parse_args()
    if args.arrivals:
        res = {}
        if os.path.exists(BENCH_JSON):
            res = json.load(open(BENCH_JSON))
        res["paged"] = paged_proxy()
        res["arrivals"] = arrival_benchmark()
        res["shared_prefix"] = shared_prefix_benchmark()
        res["chaos"] = chaos_overload_benchmark()
        res["replica_failover"] = replica_failover_benchmark()
        res["telemetry"] = telemetry_benchmark()
        with open(BENCH_JSON, "w") as f:
            json.dump(res, f, indent=2, default=float)
        ar = res["arrivals"]
        for name, c in ar["cases"].items():
            print(f"{name}: goodput ratio x{c['goodput_ratio']:.2f} "
                  f"(sched p99 {c['scheduler']['latency_p99_steps']:.0f} vs "
                  f"drain p99 {c['drain']['latency_p99_steps']:.0f} steps)")
        _print_shared_prefix(res["shared_prefix"])
        _print_chaos(res["chaos"])
        print(f"wrote {BENCH_JSON}")
    else:
        main(smoke=args.smoke, engine=not args.no_engine,
             repeats=args.repeats, arrivals=not args.no_arrivals)
