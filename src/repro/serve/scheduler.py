"""Continuous-batching scheduler over a paged KV cache (streaming serving).

``DecodeEngine`` (serve/engine.py) provisions a dense ``(slots, cache_len)``
cache — the worst-case allocation Eyeriss v2's flexible hierarchy exists to
avoid — and drains a fixed request list with no notion of arrival time. This
scheduler replaces that model end to end:

* **Paged KV** — global-attention layers store KV in fixed-size pages
  addressed through per-request block tables (serve/paging.py ↔
  models.decoding.init_paged_cache ↔ kernels/paged_attention.py): pages are
  allocated on demand as sequences grow, returned the moment a request
  finishes, and under page pressure the latest-admitted request is
  **preempted** (pages freed, request requeued for recompute) so the oldest
  work always completes. ``core.dataflow.attn_path`` decides paged vs. the
  contiguous-ring fallback from the expected occupancy. Prefill is
  **page-native**: ``decoding.prefill_batched``'s paged output mode writes
  each layer's K/V straight into pool pages during the layer scan — no
  dense (B, cache_len) transient, no post-prefill scatter.
* **Copy-on-write prefix sharing** — admission walks the allocator's prefix
  index and points a request's leading block-table entries at pages already
  holding the same prompt prefix (refcount++, prefill skips those tokens'
  writes); fresh pages start at the first divergent token. Shared pages are
  read-only: before each decode chunk the scheduler materializes a private
  copy of any shared page the chunk will append to (``PageAllocator.cow_page``
  + a device-side page copy). ``core.dataflow.kv_quant_path`` additionally
  picks the page payload format — int8 with per-page scales at cache-bound
  batch widths, bf16 otherwise.
* **Continuous batching** — admission runs every ``sync_every`` decode steps:
  arrived requests are bucketed into length tiers and batch-prefilled into
  freed rows (``decoding.prefill_batched``, the engine's amortized-admission
  path), EOS rows are evicted and their pages returned at the same boundary.
* **Streaming** — each request may carry an ``on_token`` callback, invoked
  per generated token at every sync (per-chunk host transfer, never
  per-token — the device-residency contract is unchanged from the engine).
* **Arrival accounting** — requests carry an ``arrival`` stamp on a virtual
  clock that advances ``sync_every`` per decode chunk (deterministic,
  CI-stable; wall-clock is recorded alongside). Admission never runs ahead
  of arrival, and per-request admitted/first-token/finished stamps feed the
  goodput/latency numbers in benchmarks/sparse_decode.py --arrivals.
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dataflow, plan as plan_lib
from repro.models import decoding
from repro.runtime.fault_tolerance import backoff_delay
from repro.serve import chaos as chaos_mod, kvcache, paging
from repro.serve import shard as shard_mod
from repro.serve import guard as guard_mod
from repro.serve import telemetry as telemetry_mod
from repro.serve.engine import (build_tier_batch, make_decode_step,
                                make_spec_decode_step)


@dataclasses.dataclass
class StreamRequest:
    """A request with arrival/latency accounting and optional streaming.

    ``arrival`` is in virtual decode steps (the scheduler's clock unit).
    ``on_token`` — if set — is called as ``on_token(request, token)`` for
    every generated token, in order, at each sync boundary. ``out`` always
    accumulates regardless. Latency stamps (``admitted_at``,
    ``first_token_at``, ``finished_at``) are on the same virtual clock;
    ``finished_wall_s`` is wall-clock seconds from run start.
    """
    rid: int
    prompt: List[int]
    max_new: int
    arrival: float = 0.0
    out: List = dataclasses.field(default_factory=list)
    done: bool = False
    on_token: Optional[Callable] = None
    admitted_at: Optional[float] = None
    first_token_at: Optional[float] = None
    finished_at: Optional[float] = None
    finished_wall_s: Optional[float] = None
    preemptions: int = 0
    shared_tokens: int = 0       # prompt tokens served from adopted pages
                                 # at the most recent admission (CoW sharing)
    # --- robustness layer (serve.guard, ISSUE 6) ---
    ttl: Optional[float] = None  # deadline = arrival + ttl (virtual steps);
                                 # None falls back to guard.default_ttl_steps
    on_outcome: Optional[Callable] = None   # on_outcome(request, outcome)
    outcome: Optional[guard_mod.RequestOutcome] = None
    degraded: List[str] = dataclasses.field(default_factory=list)
    # --- multi-replica control plane (serve.router/replica, ISSUE 7) ---
    tenant: Optional[str] = None  # fair-admission key (None: default tenant)
    replica: Optional[int] = None           # replica that resolved it
    migrations: int = 0          # failovers survived (recompute re-routes)


class ContinuousBatchingScheduler:
    """Streaming continuous-batching loop over paged (or contiguous) KV.

    Construction is plan-driven (ISSUE 5): pass a resolved
    ``core.plan.ServePlan`` (``plan_serve`` for budget-derived plans,
    ``plan_for_scheduler`` for explicit geometry) and every dispatch
    decision — rows, cache_len, page_size, pool size, paged vs contiguous,
    CoW sharing, KV quant, the prefill tier ladder — is read from it; the
    plan is activated around the jitted programs so ``layers.mlp`` and the
    kernels read the same resolved crossovers. The legacy kwarg pile
    (``rows=…, cache_len=…, page_size=…, num_pages=…, attn_path=…,
    kv_quant=…``) still works as a deprecated shim that builds the identical
    single-decision plan. Provisioning fewer pages than
    ``rows × ceil(cache_len/page_size)`` is the point of paging (short
    requests stop stranding worst-case HBM), with preemption as the safety
    valve; archs with no global-attention layers resolve to contiguous
    (ring/recurrent state is already bounded — nothing to page).
    """

    def __init__(self, cfg, params, plan: Optional[plan_lib.ServePlan] = None,
                 *, rows: Optional[int] = None,
                 cache_len: Optional[int] = None,
                 page_size: int = 0, num_pages: int = 0, eos_id: int = 1,
                 temperature: float = 0.0, sync_every: Optional[int] = None,
                 attn_path: Optional[str] = None,
                 share_prefix: Optional[bool] = None,
                 kv_quant: Optional[str] = None,
                 guard: Optional[guard_mod.GuardConfig] = None,
                 telemetry: Optional[telemetry_mod.Telemetry] = None,
                 slot: int = -1):
        legacy_kwargs = (rows is not None or cache_len is not None
                         or page_size or num_pages or attn_path is not None
                         or share_prefix is not None or kv_quant is not None)
        if plan is not None and legacy_kwargs:
            # a plan plus legacy dispatch kwargs would silently lose the
            # kwargs (the plan wins) — refuse instead of surprising the
            # caller mid-migration; sync_every alone stays an honored
            # per-engine override
            raise TypeError(
                "pass either plan= or the legacy rows=/cache_len=/"
                "page_size=/num_pages=/attn_path=/share_prefix=/kv_quant= "
                "kwargs, not both (the plan already fixes every decision)")
        if plan is None:
            # legacy kwarg pile: resolve it through the same shim the old
            # inline dispatch moved to (core.plan.plan_for_scheduler applies
            # the identical dataflow rules once) and deprecate the spelling
            if rows is None or cache_len is None:
                raise TypeError(
                    "ContinuousBatchingScheduler needs a ServePlan "
                    "(core.plan.plan_serve / plan_for_scheduler) or the "
                    "legacy rows=/cache_len= kwargs")
            warnings.warn(
                "constructing ContinuousBatchingScheduler from rows=/"
                "cache_len=/page_size=/... kwargs is deprecated — pass "
                "plan=core.plan.plan_for_scheduler(...) or serve through "
                "repro.serve.LLM",
                DeprecationWarning, stacklevel=2)
            if rows < 1:
                raise ValueError(
                    f"rows must be >= 1, got {rows}: a (1, {cache_len}) "
                    "cache row does not fit the HBM budget "
                    "(kvcache.max_slots == 0)")
            plan = plan_lib.plan_for_scheduler(
                cfg, rows=rows, cache_len=cache_len, page_size=page_size,
                num_pages=num_pages, attn_path=attn_path,
                share_prefix=share_prefix, kv_quant=kv_quant,
                sync_every=8 if sync_every is None else sync_every)
        if plan.rows < 1:
            raise ValueError(
                f"rows must be >= 1, got {plan.rows}: a "
                f"(1, {plan.cache_len}) cache row does not fit the HBM "
                "budget (kvcache.max_slots == 0)")
        self.cfg = cfg
        self.params = params
        self.plan = plan
        self.rows = plan.rows
        self.cache_len = plan.cache_len
        self.eos_id = eos_id
        self.temperature = temperature
        self.sync_every = max(1, plan.sync_every if sync_every is None
                              else sync_every)
        # every dispatch decision below reads the plan — the PAGE_SIZE /
        # occupancy / CoW / KV-quant rules were resolved exactly once
        self.page_size = plan.page_size
        self.paged = plan.paged
        self.max_pages = plan.max_pages
        if self.paged:
            self.num_pages = plan.num_pages
            # mesh-sharded plans (ISSUE 10) get one allocator per tp device
            # in lockstep over the same distributed address space
            self.pager = shard_mod.make_pool(plan)
        else:
            self.num_pages = 0
            self.pager = None
        self.share_prefix = plan.share_prefix
        self.kv_quant = plan.kv_quant
        # speculative decode (ISSUE 9): the plan's roofline `spec` Decision
        # picks k (0 disables); the runtime additionally requires greedy
        # sampling and the fp paged pool the flattened k-position verifier
        # is bit-exact on. A mid-run int8 degrade rung turns it back off.
        self.spec_k = int(getattr(plan, "spec_k", 0))
        self.spec_on = (self.spec_k >= 2 and self.paged
                        and temperature <= 0 and cfg.num_codebooks == 1
                        and self.kv_quant == "fp")
        # recompute-resume fast path (ISSUE 9 satellite): a re-admitted
        # preempted request whose leading pages are still resident refills
        # only the non-adopted suffix through the flattened verifier —
        # same gates as speculation minus the plan's k choice
        self._fast_resume = (self.paged and self.share_prefix
                             and cfg.num_codebooks == 1
                             and self.kv_quant == "fp"
                             and {kk for kk, _ in decoding.tfm.slot_kinds(cfg)}
                             == {"global"})
        # robustness policy (serve.guard): guard=None preserves the legacy
        # raise-on-exhaustion semantics exactly; with a GuardConfig every
        # request resolves to a structured RequestOutcome and overload walks
        # the plan's degradation ladder instead of raising
        self.guard = guard
        if guard is not None and guard.degrade_rungs is not None:
            self._ladder = tuple(r for r in plan.degrade
                                 if r in guard.degrade_rungs)
        else:
            self._ladder = plan.degrade if guard is not None else ()
        # observability (serve.telemetry, ISSUE 8): events are keyed by
        # (virtual clock, replica slot, rid). A shared Telemetry comes from
        # the facade or the multi-replica control plane (which also owns its
        # reset); a self-owned bundle is reset at each run start.
        self.telemetry = telemetry if telemetry is not None \
            else telemetry_mod.Telemetry()
        self._own_telemetry = telemetry is None
        self.slot = slot
        self.host_syncs = 0
        self.phase_stats: Dict = {}
        self._live = None             # run-in-progress state (see _run_gen)
        self.pool_devices: List[str] = []   # where the KV cache lives
        self._chunk = jax.jit(self._make_chunk_fn(), donate_argnums=(1,))
        self._refill = jax.jit(self._make_refill_fn(), donate_argnums=(1,))
        self._cow = jax.jit(self._make_cow_fn(), donate_argnums=(0,))
        self._resume = jax.jit(self._make_resume_fn(), donate_argnums=(1,))

    def _chunk_span(self) -> int:
        """Worst-case tokens one decode chunk appends per row: T baseline
        steps, T rounds of k candidate writes under speculation (rejected
        candidates occupy page slots until the next round overwrites them,
        so headroom and the CoW window must cover them)."""
        return self.sync_every * (self.spec_k if self.spec_on else 1)

    # ------------------------------------------------------ device programs
    def _init_state(self):
        cfg = self.cfg
        if self.paged:
            cache = decoding.init_paged_cache(cfg, self.rows, self.cache_len,
                                              self.num_pages, self.page_size,
                                              self.kv_quant)
        else:
            cache = decoding.init_cache(cfg, self.rows, self.cache_len)
        vshape = (self.rows, cfg.num_codebooks, cfg.vocab_padded) \
            if cfg.num_codebooks > 1 else (self.rows, cfg.vocab_padded)
        last = jnp.zeros(vshape, jnp.float32)
        pos = jnp.zeros((self.rows,), jnp.int32)
        live = jnp.zeros((self.rows,), jnp.bool_)
        budget = jnp.zeros((self.rows,), jnp.int32)
        # per-row committed token stream by absolute position (-1 empty):
        # feeds the bigram self-draft (engine.ngram_successor); threaded
        # unchanged through the baseline step so both chunk flavors share
        # one state pytree
        hist = jnp.full((self.rows, self.cache_len), -1, jnp.int32)
        return (cache, last, pos, live, budget, hist)

    def _make_refill_fn(self) -> Callable:
        """Batched prefill of one length tier into freed rows.

        Same contract as DecodeEngine's refill, except in paged mode the
        prefill itself is page-native (decoding.PagedPrefill): every
        global-attention layer's K/V is written into its block-table pages
        *during* the layer scan, per-row entries are merged at ``slots``
        inside the same program, and tokens before each row's shared-prefix
        boundary (``write_start``) are skipped — adopted pages stay
        read-only. The dense (B, cache_len) slot-shaped transient of the old
        scatter-after-prefill path never exists.
        """
        cfg, cache_len, paged = self.cfg, self.cache_len, self.paged

        def refill(params, state, toks, lengths, slots, max_new, block_table,
                   write_start):
            cache, last, pos, live, budget, hist = state
            if paged:
                pp = decoding.PagedPrefill(
                    cache=cache, block_table_rows=block_table[slots],
                    slots=slots, write_start=write_start)
                logits, new_cache = decoding.prefill_batched(
                    params, toks, lengths, cfg, cache_len, paged=pp)
            else:
                logits, row_cache = decoding.prefill_batched(
                    params, toks, lengths, cfg, cache_len)
                new_cache = {}
                for part in ("blocks", "rem"):
                    if part in cache:
                        ax = (lambda c, s: c.at[:, slots].set(
                            s.astype(c.dtype))) if part == "blocks" else \
                            (lambda c, s: c.at[slots].set(s.astype(c.dtype)))
                        new_cache[part] = {
                            k: jax.tree.map(ax, cache[part][k],
                                            row_cache[part][k])
                            for k in cache[part]}
            last = last.at[slots].set(logits[:, -1].astype(last.dtype))
            pos = pos.at[slots].set(lengths)
            live = live.at[slots].set(True)
            budget = budget.at[slots].set(max_new)
            if cfg.num_codebooks == 1:
                # seed the self-draft history with the (resume-extended)
                # prompt; pad positions stay -1 (never matched)
                S = toks.shape[1]
                row_hist = jnp.where(
                    jnp.arange(S, dtype=jnp.int32)[None, :]
                    < lengths[:, None], toks.astype(jnp.int32), -1)
                hist = hist.at[slots].set(-1)
                hist = hist.at[slots, :S].set(row_hist)
            return (new_cache, last, pos, live, budget, hist)

        return refill

    def _make_cow_fn(self) -> Callable:
        """Device-side page materialization for copy-on-write: content (and
        int8 scales) of physical pages ``src`` copied onto ``dst`` across
        every paged pool entry. Pairs are host-deduplicated; pad pairs
        repeat a real pair, so duplicate destinations carry identical
        values (order-independent scatter)."""
        def cow(state, src, dst):
            cache, last, pos, live, budget, hist = state
            new_cache = {}
            for part in ("blocks", "rem"):
                if part not in cache:
                    continue
                stacked = part == "blocks"
                out = {}
                for name, e in cache[part].items():
                    if decoding.is_paged_entry(e):
                        if stacked:   # (nper, P, ...) — page axis 1
                            out[name] = {k: v.at[:, dst].set(v[:, src])
                                         for k, v in e.items()}
                        else:
                            out[name] = {k: v.at[dst].set(v[src])
                                         for k, v in e.items()}
                    else:
                        out[name] = e
                new_cache[part] = out
            return (new_cache, last, pos, live, budget, hist)

        return cow

    def _make_chunk_fn(self) -> Callable:
        """sync_every fused decode steps — the engine's shared step
        (engine.make_decode_step), with serve_step routing paged entries
        through the block table. Under speculation each scan step is one
        draft-k/verify-once round (engine.make_spec_decode_step), so the
        chunk's outputs widen to (T, B, k) and a chunk retires up to
        ``T * k`` tokens per row at the same T dispatches."""
        T, paged = self.sync_every, self.paged
        if self.spec_on:
            step = make_spec_decode_step(self.cfg, self.eos_id, self.spec_k)
        else:
            base = make_decode_step(self.cfg, self.temperature, self.eos_id)

            def step(params, carry, rng_i, block_table=None):
                # thread the spec history through untouched — one state
                # pytree for both chunk flavors (degrade rungs retrace the
                # same donated buffers)
                core, out = base(params, carry[:5], rng_i,
                                 block_table=block_table)
                return core + (carry[5],), out

        def chunk(params, state, rng, block_table):
            bt = block_table if paged else None
            rngs = jax.random.split(rng, T)
            state, (toks, emits) = jax.lax.scan(
                lambda carry, rng_i: step(params, carry, rng_i,
                                          block_table=bt), state, rngs)
            return state, toks, emits

        return chunk

    def _make_resume_fn(self) -> Callable:
        """Suffix-only refill for a recompute resume (ISSUE 9 satellite):
        the adopted prefix pages already hold K/V for tokens [0, start), so
        only the ``toks`` suffix flows through the flattened k-position
        verifier — one dispatch over len(suffix) flattened rows instead of
        a full-prompt prefill tier. ``toks`` (1, Lp) is the pow2-padded
        suffix, ``n_real`` its unpadded length; pad positions write beyond
        the committed length (overwritten by decode before any masked read)
        and their logits are never selected."""
        cfg = self.cfg

        def resume(params, state, toks, start, n_real, row, n_tok, max_new,
                   block_table, hist_row):
            cache, last, pos, live, budget, hist = state
            logits, cache = decoding.verify_step(
                params, cache, toks, start[None], cfg,
                block_table=block_table[row][None])
            last = last.at[row].set(logits[0, n_real - 1].astype(last.dtype))
            pos = pos.at[row].set(n_tok)
            live = live.at[row].set(True)
            budget = budget.at[row].set(max_new)
            hist = hist.at[row].set(hist_row)
            return (cache, last, pos, live, budget, hist)

        return resume

    # -------------------------------------------------------------- host loop
    def _plen(self, r: StreamRequest) -> int:
        """Effective prompt length at (re-)admission: original prompt plus
        any tokens generated before a preemption (recompute resume)."""
        return len(r.prompt) + len(r.out)

    def _resume_prompt(self, r: StreamRequest) -> List[int]:
        if not r.out:
            return list(r.prompt)
        if self.cfg.num_codebooks > 1:
            raise RuntimeError(
                "recompute preemption requires num_codebooks == 1")
        return list(r.prompt) + [int(t) for t in r.out]

    def _final_len(self, r: StreamRequest) -> int:
        """Upper bound on tokens this request ever holds (page cap)."""
        return len(r.prompt) + r.max_new

    def _block_table(self, row_rids: List[int]):
        return jnp.asarray(self.pager.block_table_rows(row_rids,
                                                       self.max_pages))

    def _degrade_to_int8(self, state, clock: float):
        """int8 rung of the degradation ladder: requantize the resident fp
        pool to int8 pages in place and GROW it to the plan's
        ``num_pages_int8`` (same HBM footprint, ~2× pages — pressure relief
        without evicting anyone). Page ids 0..old-1 keep their contents, so
        every block table survives verbatim; the jitted programs retrace on
        the new pytree structure automatically. Sticky for the scheduler's
        lifetime (there is no un-degrade rung — re-widening would need a
        lossy fp reconstruction for no occupancy win)."""
        new_pages = self.plan.num_pages_int8

        def migrate(cache):
            out_cache = {}
            for part in ("blocks", "rem"):
                if part not in cache:
                    continue
                out = {}
                for name, e in cache[part].items():
                    if decoding.is_paged_entry(e) \
                            and not decoding.is_quantized_entry(e):
                        out[name] = decoding.quantize_paged_entry(e,
                                                                  new_pages)
                    else:
                        out[name] = e
                out_cache[part] = out
            return out_cache

        cache, last, pos, live, budget, hist = state
        with warnings.catch_warnings():
            # fp buffers can't be reused for the int8 pool (dtype + shape
            # change) — the donation-unused warning is expected here, once
            warnings.simplefilter("ignore", UserWarning)
            cache = jax.jit(migrate, donate_argnums=(0,))(cache)
        self.pager.grow(new_pages)
        self.num_pages = new_pages
        self.kv_quant = "int8"
        if self.spec_on:
            # int8 appends rewrite whole pages (per-page scale requant), so
            # rejected-draft garbage would poison committed tokens' scales:
            # speculation and the suffix-resume verifier end at this rung
            self.spec_on = False
            self._chunk = jax.jit(self._make_chunk_fn(), donate_argnums=(1,))
        self._fast_resume = False
        self.phase_stats["kv_quant"] = "int8"
        self.phase_stats["degraded_to_int8_at"] = clock
        self.telemetry.metrics.count("requant_events")
        self.telemetry.tracer.event("degrade_rung", clock, cat="degrade",
                                    slot=self.slot, rung="int8_kv",
                                    pages=new_pages)
        return (cache, last, pos, live, budget, hist)

    def run(self, requests: List[StreamRequest], rng=None, chaos=None
            ) -> List[StreamRequest]:
        # the plan is the dispatch source for everything traced below; the
        # run is self-paced: every boundary ticks with no external clock and
        # the loop idle-jumps across arrival gaps
        gen = self._run_gen(requests, rng, chaos, external=False)
        with plan_lib.activate(self.plan):
            try:
                gen.send(None)                       # prime: setup + validate
                while True:
                    gen.send(("tick", None))
            except StopIteration as e:
                return e.value
            finally:
                self._live = None

    def start_gen(self, requests: List[StreamRequest], rng=None, chaos=None):
        """Prime a boundary-stepped run for an external driver (the
        multi-replica control plane, serve/replica.py).

        The returned generator yields a status dict before every sync-window
        boundary: ``{"clock", "drained", "active", "waiting", "pending",
        "done", "decode_chunks"}``. Send ``("tick", global_clock)`` to
        process ONE boundary with the scheduler's virtual clock synced to
        the shared ``global_clock`` (the scheduler never idle-jumps ahead of
        it, so N replicas driven with the same ticks stay in lockstep), or
        ``("stop", None)`` to finalize — ``StopIteration.value`` is the done
        list, exactly as :meth:`run` returns it. Caller-bug validation runs
        here, before the first yield. The driver must wrap every ``send`` in
        ``plan_lib.activate(self.plan)`` (dispatch identity) and may
        :meth:`inject` requests between boundaries (failover re-routes).
        Abandoning the generator (``close()``) models replica death: no
        finalization, no outcome delivery, live state left harvestable in
        ``self._live``.
        """
        gen = self._run_gen(requests, rng, chaos, external=True)
        with plan_lib.activate(self.plan):
            gen.send(None)
        return gen

    def inject(self, requests: List[StreamRequest]) -> None:
        """Add requests to a run in progress (multi-replica failover and
        router dispatch land here). Same caller-bug validation as run start;
        a request whose ``arrival`` is already in the past is admissible at
        the next boundary."""
        live = self._live
        if live is None:
            raise RuntimeError(
                "inject() requires a run in progress (start_gen)")
        for r in requests:
            if r.rid in live["rids"]:
                raise ValueError(
                    f"request rid {r.rid} already known to this run — rids "
                    "must be unique across the run, including re-routes")
            total = len(r.prompt) + r.max_new
            if r.max_new > 0 and total > self.cache_len:
                raise ValueError(
                    f"request {r.rid}: prompt ({len(r.prompt)}) + max_new "
                    f"({r.max_new}) exceeds cache_len ({self.cache_len})")
            if self.paged and r.max_new > 0 and dataflow.pages_for(
                    total, self.page_size) > self.num_pages:
                raise ValueError(
                    f"request {r.rid} needs "
                    f"{dataflow.pages_for(total, self.page_size)} pages, "
                    f"pool has {self.num_pages}: it can never run")
        for r in sorted(requests, key=lambda r: (r.arrival, r.rid)):
            live["rids"].add(r.rid)
            live["requests"].append(r)
            if r.max_new <= 0:
                r.done = True
                r.finished_at = r.arrival
                r.outcome = guard_mod.RequestOutcome(
                    "ok", "empty generation budget", at_step=r.arrival)
                if r.on_outcome is not None:
                    r.on_outcome(r, r.outcome)
                live["done"].append(r)
            else:
                live["pending"].append(r)
        live["pending"].sort(key=lambda r: (r.arrival, r.rid))

    def _run_gen(self, requests: List[StreamRequest], rng=None, chaos=None,
                 external: bool = False):
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        g = self.guard
        inj = None
        if chaos is not None:
            inj = chaos if isinstance(chaos, chaos_mod.FaultInjector) \
                else chaos_mod.FaultInjector(chaos)
        self.last_injector = inj
        tel = self.telemetry
        if self._own_telemetry:
            tel.reset()
        tr, m = tel.tracer, tel.metrics
        slot = self.slot
        if inj is not None:
            # trace every delivered injection at the boundary it fired on
            # (the closure reads the loop's clock late-bound); the schedule
            # is seeded, so these events are same-seed deterministic too
            inj.on_inject = lambda kind, rid=-1: tr.event(
                "chaos_inject", clock, cat="chaos", slot=slot, rid=rid,
                kind=kind)
        rids = [r.rid for r in requests]
        if len(set(rids)) != len(rids):
            # block tables are keyed by rid — duplicates would silently share
            # pages and corrupt each other's KV history
            raise ValueError(f"request rids must be unique, got {rids}")
        # feasibility is arrival-independent (resume totals equal originals):
        # validate everything up front so a late infeasible request cannot
        # abort the run after other requests already finished — caller bugs
        # raise here, before any work; only runtime faults become outcomes
        for r in requests:
            total = len(r.prompt) + r.max_new
            if r.max_new > 0 and total > self.cache_len:
                raise ValueError(
                    f"request {r.rid}: prompt ({len(r.prompt)}) + max_new "
                    f"({r.max_new}) exceeds cache_len ({self.cache_len})")
            if self.paged and r.max_new > 0 and dataflow.pages_for(
                    total, self.page_size) > self.num_pages:
                raise ValueError(
                    f"request {r.rid} needs "
                    f"{dataflow.pages_for(total, self.page_size)} pages, "
                    f"pool has {self.num_pages}: it can never run")
        allreqs = list(requests)      # grows via inject() (failover re-routes)
        pending = sorted(requests, key=lambda r: (r.arrival, r.rid))
        waiting: List[StreamRequest] = []
        done: List[StreamRequest] = []
        if self.paged:
            # fresh pool per run (like the SlotAllocator below): an aborted
            # previous run must not leak its block tables into this one;
            # self.pager stays inspectable after the run (kvcache.report)
            self.pager = shard_mod.make_pool(self.plan)
        for r in [r for r in pending if r.max_new <= 0]:
            pending.remove(r)
            r.done = True
            r.finished_at = r.arrival
            r.outcome = guard_mod.RequestOutcome(
                "ok", "empty generation budget", at_step=r.arrival)
            if r.on_outcome is not None:
                r.on_outcome(r, r.outcome)
            done.append(r)
        alloc = kvcache.SlotAllocator(self.rows)
        active: Dict[int, StreamRequest] = {}        # row -> request
        # live run state, shared with inject() and harvestable by the
        # control plane after a replica death (the lists are the loop's own
        # objects, so external appends to pending are visible here)
        self._live = {"pending": pending, "waiting": waiting,
                      "active": active, "done": done, "requests": allreqs,
                      "rids": set(rids)}
        row_pos: Dict[int, int] = {}                 # row -> device pos mirror
        admit_order: List[int] = []                  # rows, oldest first
        row_rids = [-1] * self.rows
        state = self._init_state()
        self.pool_devices = shard_mod.devices_of(state[0])
        K = self.cfg.num_codebooks
        T = self.sync_every
        clock = 0.0
        stall_streak = 0
        run_clock = telemetry_mod.RunClock()
        st = self.phase_stats = {
            "prefill_s": 0.0, "decode_s": 0.0, "prefill_batches": 0,
            "prefill_prompts": 0, "prefill_real_tokens": 0,
            "prefill_padded_tokens": 0, "decode_chunks": 0,
            "decode_steps": 0, "idle_steps": 0.0, "preemptions": 0,
            "attn_path": "paged" if self.paged else "contiguous",
            "kv_quant": self.kv_quant,
            "share_prefix": self.share_prefix,
            "shared_tokens_admitted": 0,   # prompt tokens served from
                                           # adopted (refcounted) pages
            "cow_copies": 0,               # shared pages materialized for
                                           # a decode append
            "peak_live_rows": 0,           # max concurrent admitted requests
            "guard_enabled": g is not None,
            "stalled_boundaries": 0,       # boundaries skipped: pool stalled
            "step_retries": 0,             # transient step faults retried
            "clamped_admissions": 0,       # max_new clamps (degrade rung 2)
            # speculative decode (ISSUE 9)
            "spec_k": self.spec_k if self.spec_on else 0,
            "spec_rounds": 0,              # draft/verify rounds dispatched
            "spec_drafted_tokens": 0,      # candidates scored by the verifier
            "spec_accepted_tokens": 0,     # candidates emitted (greedy-exact)
            "resume_fast_prompts": 0,      # suffix-only recompute resumes
            "resume_fast_tokens": 0,       # prompt tokens NOT re-prefilled
        }

        preempted_rows: List[int] = []
        just_preempted: set = set()           # rids evicted this boundary
        peak_pages: Optional[Dict] = None     # busiest-boundary pool snapshot

        def clear_preempted_flags():
            """Drop the device live flags of rows preempted since the last
            clear: zombies would keep running full forward+sampling (and in
            paged mode DMA-ing clamped/freed pages) until the row is reused.
            Must run before any admission reuses a freed row AND before
            every decode chunk."""
            nonlocal state
            if not preempted_rows:
                return
            cache, last, pos, live, budget, hist = state
            live = live.at[jnp.asarray(preempted_rows)].set(False)
            state = (cache, last, pos, live, budget, hist)
            preempted_rows.clear()

        def resolve(r: StreamRequest, status: str, reason: str = ""):
            """Terminal state: exactly one structured RequestOutcome per
            request, delivered via its on_outcome callback — never an
            exception escaping mid-batch."""
            r.done = True
            if r.finished_at is None:
                r.finished_at = clock
            r.finished_wall_s = run_clock.elapsed_s()
            r.outcome = guard_mod.RequestOutcome(
                status=status, reason=reason, at_step=clock,
                degraded=tuple(r.degraded))
            done.append(r)
            m.count(status)
            m.observe("e2e_latency_steps", r.finished_at - r.arrival)
            if r.first_token_at is not None:
                m.observe("ttft_steps", r.first_token_at - r.arrival)
            if status == "ok":
                # length/goodput hists cover completions only — shed/expired
                # partials would skew the capacity-drift comparison
                m.observe("finished_len_tokens", len(r.prompt) + len(r.out))
                m.observe("generated_tokens", len(r.out))
                m.tenant_count(r.tenant, "ok_requests")
                m.tenant_count(r.tenant, "ok_tokens", len(r.out))
            tr.event("outcome", r.finished_at, cat="request", slot=slot,
                     rid=r.rid, status=status)
            if r.on_outcome is not None:
                r.on_outcome(r, r.outcome)

        def deadline_of(r: StreamRequest) -> Optional[float]:
            ttl = r.ttl if r.ttl is not None else (
                g.default_ttl_steps if g is not None else None)
            return None if ttl is None else r.arrival + ttl

        def evict_active(row: int, status: str, reason: str):
            """Terminal eviction of a live row (expired/failed): pages and
            slot returned, device live flag scheduled for clearing, partial
            output kept on the resolved request."""
            r = active.pop(row)
            if self.paged:
                self.pager.free(r.rid)
            alloc.free(row)
            admit_order.remove(row)
            row_rids[row] = -1
            row_pos.pop(row, None)
            preempted_rows.append(row)
            resolve(r, status, reason)

        def ensure_pages(rid: int, n_tokens: int) -> bool:
            """pager.ensure behind the chaos harness: an injected failure is
            indistinguishable from genuine pressure (and allocates nothing),
            so the same preempt/stall machinery absorbs both."""
            if inj is not None and inj.ensure_fails(rid, n_tokens):
                return False
            return self.pager.ensure(rid, n_tokens)

        def preempt_latest() -> bool:
            """Free the latest-admitted row and requeue its request for
            recompute — unless its retry budget is spent, in which case it
            resolves as ``preempted_out`` (starvation bound: under sustained
            pressure the same victim would otherwise recompute-thrash
            forever). Returns False when there is nothing to preempt.
            Re-admission order is deterministic: ``waiting`` is kept sorted
            by (arrival, rid), never by insertion order under churn."""
            if len(admit_order) <= 1:
                return False
            row = admit_order.pop()               # latest admitted
            r = active.pop(row)
            self._resume_prompt(r)                # raises early for K > 1
            self.pager.free(r.rid)
            alloc.free(row)
            row_rids[row] = -1
            row_pos.pop(row, None)
            r.preemptions += 1
            st["preemptions"] += 1
            m.count("preemptions")
            tr.event("preempt", clock, cat="pool", slot=slot, rid=r.rid)
            preempted_rows.append(row)
            if g is not None and r.preemptions > g.retry_budget:
                resolve(r, "preempted_out",
                        f"preempted {r.preemptions} times — retry budget "
                        f"({g.retry_budget}) spent; {len(r.out)} generated "
                        "tokens kept")
                return True
            just_preempted.add(r.rid)
            waiting.append(r)
            waiting.sort(key=lambda w: (w.arrival, w.rid))
            return True

        def note_stall(why: str):
            """A boundary that could not reserve chunk headroom even after
            preempting everything preemptible: skip the chunk (appending
            without reserved pages would drop writes and corrupt reads) and
            advance the clock so arrivals/deadlines keep progressing. A
            streak longer than stall_budget fails the oldest resident
            request — the pool demonstrably cannot serve it."""
            nonlocal stall_streak
            st["stalled_boundaries"] += 1
            m.count("stalled_boundaries")
            tr.event("stall", clock, cat="pool", slot=slot, why=why)
            stall_streak += 1
            just_preempted.clear()
            if g is not None and stall_streak > g.stall_budget and \
                    admit_order:
                evict_active(admit_order[0], "failed",
                             f"{why}: {stall_streak} consecutive stalled "
                             f"boundaries (stall_budget {g.stall_budget})")
                stall_streak = 0

        while True:
            # ---- boundary gate: yield status, receive the next command ----
            # self-paced runs tick with no clock (internal idle-jumps);
            # externally driven runs receive the shared global clock and
            # never run ahead of it — N replicas ticked together stay in
            # deterministic lockstep on one virtual clock
            cmd, tick = yield {
                "clock": clock,
                "drained": not (pending or waiting or active),
                "active": len(active), "waiting": len(waiting),
                "pending": len(pending), "done": len(done),
                "decode_chunks": st["decode_chunks"]}
            if cmd == "stop":
                break
            if tick is not None and tick > clock:
                st["idle_steps"] += tick - clock
                clock = tick
            if not (pending or waiting or active):
                if not external:
                    break             # self-paced: nothing can arrive later
                continue              # lockstep: stay alive for inject()

            # ---- int8 degrade rung (boundary start, measured pressure) ----
            # requantizing relieves pressure BEFORE this boundary's arrivals
            # are judged for clamping/shedding, so rung 1 shadows rungs 2-3
            if "int8_kv" in self._ladder and self.paged \
                    and self.kv_quant == "fp" \
                    and self.plan.num_pages_int8 > self.num_pages:
                if self.pager.in_use / self.num_pages >= g.int8_pressure:
                    state = self._degrade_to_int8(state, clock)

            # ---- arrivals (virtual clock; idle-jump when nothing to do) ----
            while pending and pending[0].arrival <= clock + 1e-9:
                r = pending.pop(0)
                tr.event("queued", clock, cat="request", slot=slot,
                         rid=r.rid)
                m.count("requests_queued")
                if g is not None and self.paged and self._ladder:
                    # admission control at the front door: rungs 2-3 judge
                    # each arrival against measured pool pressure
                    pressure = self.pager.in_use / self.num_pages
                    if "shed" in self._ladder and pressure >= g.shed_pressure:
                        resolve(r, "shed",
                                f"pool pressure {pressure:.2f} >= shed "
                                f"threshold {g.shed_pressure:.2f} at arrival")
                        continue
                    if "clamp_max_new" in self._ladder \
                            and pressure >= g.clamp_pressure \
                            and r.max_new > g.clamp_max_new:
                        r.max_new = g.clamp_max_new
                        r.degraded.append("clamp_max_new")
                        st["clamped_admissions"] += 1
                        m.count("clamped_admissions")
                        tr.event("degrade_rung", clock, cat="degrade",
                                 slot=slot, rid=r.rid,
                                 rung="clamp_max_new")
                waiting.append(r)

            # ---- deadlines: expire whatever outlived arrival + ttl --------
            if g is not None:
                for r in list(waiting):
                    dl = deadline_of(r)
                    if dl is not None and clock + 1e-9 >= dl:
                        waiting.remove(r)
                        resolve(r, "expired",
                                f"deadline (arrival {r.arrival:g} + ttl "
                                f"{dl - r.arrival:g} steps) passed before "
                                "admission")
                for row, r in list(active.items()):
                    dl = deadline_of(r)
                    if dl is not None and clock + 1e-9 >= dl:
                        evict_active(row, "expired",
                                     f"deadline (arrival {r.arrival:g} + "
                                     f"ttl {dl - r.arrival:g} steps) passed "
                                     f"mid-generation; {len(r.out)} tokens "
                                     "kept")

            if not active and not waiting:
                if external:
                    continue      # lockstep: never idle-jump past the tick
                if not pending:
                    break
                st["idle_steps"] += pending[0].arrival - clock
                clock = pending[0].arrival
                continue

            # ---- page headroom for the active rows' next chunk ------------
            # runs BEFORE admission: live rows reserve their chunk pages
            # first, so a new request is never admitted (and batch-prefilled)
            # only to be preempted at the same boundary — that would throw
            # the prefill away and thrash under sustained pressure
            stalled = False
            span = self._chunk_span()     # T, or T*k under speculation
            if self.paged:
                for row in list(admit_order):         # oldest first
                    if row not in active:
                        continue
                    r = active[row]
                    need = min(row_pos[row] + span, self._final_len(r))
                    while row in active and not ensure_pages(r.rid, need):
                        if not preempt_latest():
                            if g is None:
                                raise RuntimeError(
                                    "page pool exhausted with nothing left "
                                    "to preempt — num_pages is too small")
                            stalled = True
                            break
                    if stalled:
                        break
                    if row in active:
                        self.pager.set_length(r.rid, row_pos[row])
            clear_preempted_flags()
            if stalled:
                note_stall("no page headroom for the next chunk")
                clock += T
                continue

            # ---- admission: arrived requests into freed rows --------------
            to_admit: List[StreamRequest] = []
            while waiting and len(to_admit) < alloc.available():
                r = waiting[0]
                if r.rid in just_preempted:
                    # evicted THIS boundary to relieve pressure — re-admitting
                    # into the pages it just freed would re-run its (growing)
                    # prefill only to preempt it again: wait one boundary.
                    # break, not skip: it keeps queue priority
                    break
                plen = self._plen(r)
                if self.paged:
                    # CoW prefix sharing: point leading table entries at
                    # resident pages already holding this prompt's prefix
                    # (refcount++); prefill will skip writes before the
                    # boundary. Roll the adoption back if the fresh-page
                    # remainder doesn't fit — all-or-nothing, like ensure.
                    r.shared_tokens = self.pager.adopt_prefix(
                        r.rid, self._resume_prompt(r)) \
                        if self.share_prefix else 0
                    if not ensure_pages(
                            r.rid, min(plen + span, self._final_len(r))):
                        if self.pager.pages_of(r.rid):
                            self.pager.free(r.rid)   # roll back adoption
                        r.shared_tokens = 0
                        break                  # page pressure: wait for frees
                    if self.share_prefix:
                        # publish this prompt's pages immediately — their
                        # content lands in this same boundary's refill, so a
                        # same-boundary arrival can already adopt the chain
                        self.pager.register_prefix(r.rid,
                                                   self._resume_prompt(r))
                waiting.pop(0)
                to_admit.append(r)
            just_preempted.clear()
            admits: List[Tuple[int, StreamRequest]] = list(
                zip(alloc.alloc_many(len(to_admit)), to_admit))
            for row, r in admits:
                admit_order.append(row)
                row_rids[row] = r.rid
                row_pos[row] = self._plen(r)
                if self.paged:
                    self.pager.set_length(r.rid, row_pos[row])
                    st["shared_tokens_admitted"] += r.shared_tokens
                if r.admitted_at is None:
                    r.admitted_at = clock
                    m.count("requests_admitted")
                    wait = clock - r.arrival
                    m.observe("admission_wait_steps", wait)
                    m.tenant_observe(r.tenant, "admission_wait_steps", wait)
                    tr.event("admitted", clock, cat="request", slot=slot,
                             rid=r.rid, shared_tokens=r.shared_tokens)
                if self.paged and r.shared_tokens:
                    m.count("shared_tokens_admitted", r.shared_tokens)
            if admits:
                # recompute-resume fast path (ISSUE 9 satellite): a preempted
                # request re-admitted while its leading pages are still
                # resident (adopt_prefix above re-pointed the table at them)
                # refills only the non-adopted suffix through the flattened
                # verifier — one dispatch over len(suffix) rows instead of a
                # full-prompt prefill tier. Partial coverage is page-aligned
                # by construction (a partial-tail index key matches only the
                # entire remainder), so the suffix starts on a fresh
                # (unshared) page and its writes need no CoW.
                fast: List[Tuple[int, StreamRequest]] = []
                if self._fast_resume:
                    fast = [(row, r) for row, r in admits
                            if r.out and 0 < r.shared_tokens < self._plen(r)
                            and r.shared_tokens % self.page_size == 0]
                    fast_rows = {row for row, _ in fast}
                    admits = [a for a in admits if a[0] not in fast_rows]
                buckets: Dict[int, List[Tuple[int, StreamRequest]]] = {}
                for row, r in admits:
                    buckets.setdefault(self.plan.tier(self._plen(r)),
                                       []).append((row, r))
                bt = self._block_table(row_rids) if self.paged else \
                    jnp.zeros((self.rows, 1), jnp.int32)
                with telemetry_mod.phase_timer(
                        st, "prefill_s", tracer=tr, name="prefill",
                        start=clock, slot=slot) as ph:
                    for row, r in fast:
                        active[row] = r
                        prompt = self._resume_prompt(r)
                        cov = r.shared_tokens
                        suffix = prompt[cov:]
                        Lp = 1 << (len(suffix) - 1).bit_length()
                        hrow = np.full((self.cache_len,), -1, np.int32)
                        hrow[:len(prompt)] = prompt
                        state = self._resume(
                            self.params, state,
                            jnp.asarray([suffix + [0] * (Lp - len(suffix))],
                                        jnp.int32),
                            jnp.asarray(cov, jnp.int32),
                            jnp.asarray(len(suffix), jnp.int32),
                            jnp.asarray(row, jnp.int32),
                            jnp.asarray(len(prompt), jnp.int32),
                            jnp.asarray(r.max_new - len(r.out), jnp.int32),
                            bt, jnp.asarray(hrow))
                        st["resume_fast_prompts"] += 1
                        st["resume_fast_tokens"] += cov
                        st["prefill_real_tokens"] += len(suffix)
                        tr.event("resume_fast", clock, cat="request",
                                 slot=slot, rid=r.rid, adopted=cov,
                                 suffix=len(suffix))
                    for tier, group in sorted(buckets.items()):
                        B = len(group)
                        toks, lengths, row_ids, budgets, starts = \
                            build_tier_batch(
                                group, tier, self._resume_prompt,
                                lambda r: r.max_new - len(r.out),
                                lambda r: r.shared_tokens)
                        for row, r in group:
                            active[row] = r
                        state = self._refill(self.params, state,
                                             jnp.asarray(toks),
                                             jnp.asarray(lengths),
                                             jnp.asarray(row_ids),
                                             jnp.asarray(budgets), bt,
                                             jnp.asarray(starts))
                        real = int(lengths.sum())
                        st["prefill_batches"] += 1
                        st["prefill_prompts"] += B
                        st["prefill_real_tokens"] += real
                        st["prefill_padded_tokens"] += B * tier
                        m.count("prefill_batches")
                        m.count("prefill_prompts", B)
                        m.count("prefill_real_tokens", real)
                        m.count("prefill_padded_tokens", B * tier)
                    ph.ready(state[1])
                    ph.note(prompts=len(admits) + len(fast),
                            tiers=len(buckets))

            if not active:
                if g is not None or inj is not None:
                    # nothing running and nothing admitted (transient chaos
                    # ensure-failures can starve admission): advance the
                    # clock so arrivals/deadlines keep progressing
                    st["idle_steps"] += T
                    clock += T
                continue
            st["peak_live_rows"] = max(st["peak_live_rows"], len(active))

            # ---- CoW guard: materialize shared pages this chunk appends to
            # (runs after admission so freshly adopted whole-prompt tails are
            # covered too; shared pages are read-only by contract)
            if self.paged and self.share_prefix:
                pairs: List[Tuple[int, int]] = []
                for row in list(admit_order):         # oldest first
                    if row not in active:
                        continue
                    r = active[row]
                    lo = row_pos[row]
                    hi = min(lo + span, self._final_len(r))
                    # re-probe after every mutation: a preemption can drop a
                    # refcount to 1 mid-loop (page no longer needs a copy)
                    while row in active:
                        shared = self.pager.shared_pages_in(r.rid, lo, hi)
                        if not shared:
                            break
                        pair = self.pager.cow_page(r.rid, shared[0])
                        if pair is None:              # no free page: pressure
                            if not preempt_latest():
                                if g is None:
                                    raise RuntimeError(
                                        "page pool exhausted during CoW "
                                        "materialization with nothing left "
                                        "to preempt — num_pages is too "
                                        "small")
                                stalled = True
                                break
                            continue
                        pairs.append(pair)
                    if stalled:
                        break
                if pairs:
                    # apply collected copies even on a stalled boundary: the
                    # allocator already repointed those tables, so the device
                    # content copy must land before anything reads the pages
                    st["cow_copies"] += len(pairs)
                    m.count("cow_copies", len(pairs))
                    tr.event("cow_copy", clock, cat="pool", slot=slot,
                             pages=len(pairs))
                    # pad to a power of two (bounded retraces); pads repeat a
                    # real pair so duplicate dsts carry identical content
                    n = 1 << (len(pairs) - 1).bit_length()
                    pairs = pairs + [pairs[0]] * (n - len(pairs))
                    src = jnp.asarray([s for s, _ in pairs], jnp.int32)
                    dst = jnp.asarray([d for _, d in pairs], jnp.int32)
                    state = self._cow(state, src, dst)
            clear_preempted_flags()       # CoW-guard preemptions, pre-chunk
            if stalled:
                note_stall("no free page for CoW materialization")
                clock += T
                continue

            if self.paged:
                # sample occupancy at the busiest point of the boundary —
                # the end-of-run snapshot is always fully drained
                s = self.pager.stats()
                if peak_pages is None or \
                        s["pages_used"] > peak_pages["pages_used"]:
                    peak_pages = s

            # ---- transient step faults (chaos): retry with backoff --------
            # injected BEFORE the device dispatch (the chunk's state arg is
            # donated — a post-dispatch replay would reuse consumed buffers)
            # and BEFORE the rng split, so retried boundaries consume no
            # randomness and survivors stay bit-identical to a clean run
            if inj is not None:
                attempt, aborted = 0, False
                while True:
                    try:
                        inj.check_step(st["decode_chunks"])
                        break
                    except chaos_mod.InjectedFault as e:
                        attempt += 1
                        st["step_retries"] += 1
                        m.count("step_retries")
                        limit = g.max_step_retries if g is not None else 3
                        if attempt > limit:
                            reason = (f"decode step failing persistently "
                                      f"({e}) — {limit} retries spent")
                            for row in list(active):
                                evict_active(row, "failed", reason)
                            for r in list(waiting) + list(pending):
                                resolve(r, "failed", reason)
                            waiting.clear()
                            pending.clear()
                            aborted = True
                            break
                        time.sleep(backoff_delay(
                            attempt, g.backoff_s if g is not None else 0.0))
                if aborted:
                    clear_preempted_flags()
                    continue

            # ---- NaN quarantine (pre-chunk): state[1] holds the logits
            # the previous chunk (or prefill) produced for each row — a
            # non-finite value there means this row's next sampled token
            # would be garbage. Sweep at the boundary, evict poisoned rows
            # BEFORE dispatching the chunk, so they emit nothing. (Chaos
            # poisons the same buffer, so injection and genuine NaNs take
            # the identical detection path. In-scan NaNs are caught one
            # boundary late — tokens of the chunk that produced them may
            # include garbage; the terminal outcome says so.)
            if inj is not None:
                prids = set(inj.nan_rids_for(st["decode_chunks"]))
                prows = [row for row, r in active.items() if r.rid in prids]
                if prows:
                    cache_c, last_c = state[0], state[1]
                    last_c = last_c.at[jnp.asarray(prows)].set(jnp.nan)
                    state = (cache_c, last_c) + state[2:]
            if g is not None and (g.nan_check or inj is not None):
                bad = jax.device_get(jnp.isnan(
                    state[1]).reshape(self.rows, -1).any(axis=1))
                for row in [int(i) for i in np.nonzero(bad)[0]
                            if int(i) in active]:
                    r = active[row]
                    evict_active(row, "failed",
                                 "non-finite logits at the sync boundary; "
                                 f"{len(r.out)} tokens kept")
                clear_preempted_flags()
                if not active:
                    st["idle_steps"] += T
                    clock += T
                    continue

            # ---------------------- device-resident decode chunk ----------
            # under speculation the chunk runs against CoW forks of each
            # row's page chain (refcount++, zero copies): draft writes land
            # in the fork's tail headroom, commit adopts the fork table
            # after the device round-trip, and any abort between simply
            # drops the refcounts — no rollback scatter (ISSUE 9)
            # fork child ids live at -2 - rid: real rids are >= 0 and -1 is
            # the empty-device-row sentinel in row_rids, so ~0 == -1 would
            # hand a dead row the fork's page table and let its flattened
            # verify writes clobber the parent's KV
            fork_rids: List[int] = []
            if self.spec_on:
                for row in list(admit_order):
                    if row in active:
                        rid = active[row].rid
                        self.pager.fork_chain(rid, -2 - rid)
                        fork_rids.append(rid)
            with telemetry_mod.phase_timer(
                    st, "decode_s", tracer=tr, name="decode_chunk",
                    start=clock, end=clock + T, slot=slot) as ph:
                rng, k = jax.random.split(rng)
                bt = self._block_table(row_rids) if self.paged else \
                    jnp.zeros((self.rows, 1), jnp.int32)
                state, toks, emits = self._chunk(self.params, state, k, bt)
                toks_h, emits_h, live_h = jax.device_get(
                    (toks, emits, state[3]))
                ph.note(rows=len(active))
            for rid in fork_rids:
                self.pager.commit_fork(rid, -2 - rid)
            self.host_syncs += 1
            st["decode_chunks"] += 1
            st["decode_steps"] += T
            m.count("decode_chunks")
            m.count("decode_steps", T)
            stall_streak = 0
            clock += T
            # window-end gauges, sampled while this chunk's rows are still
            # resident (pre-eviction) — the per-window occupancy record the
            # plan-drift detector measures against
            m.gauge("queue_pending", len(pending))
            m.gauge("queue_waiting", len(waiting))
            m.gauge("active_rows", len(active))
            if self.paged:
                self.pager.observe(m)
            m.end_window(clock, slot)
            emitted = 0
            spec = emits_h.ndim == 3          # (T, B, k) speculative chunk
            if spec:
                for t in range(emits_h.shape[0]):
                    for row, r in active.items():
                        for i in range(emits_h.shape[2]):
                            if emits_h[t, row, i]:
                                tok = int(toks_h[t, row, i])
                                r.out.append(tok)
                                emitted += 1
                                if r.first_token_at is None:
                                    r.first_token_at = clock - T + t + 1
                                if r.on_token is not None:
                                    r.on_token(r, tok)
                drafted = emits_h.shape[0] * emits_h.shape[2] * len(active)
                st["spec_rounds"] += emits_h.shape[0]
                st["spec_drafted_tokens"] += drafted
                st["spec_accepted_tokens"] += emitted
                m.count("spec_rounds", emits_h.shape[0])
                m.count("spec_drafted_tokens", drafted)
                m.count("spec_accepted_tokens", emitted)
                tr.event("spec_chunk", clock, cat="spec", slot=slot,
                         drafted=drafted, accepted=emitted)
            else:
                for t in range(emits_h.shape[0]):
                    for row, r in active.items():
                        if emits_h[t, row]:
                            tok = [int(v) for v in toks_h[t, row]] if K > 1 \
                                else int(toks_h[t, row])
                            r.out.append(tok)
                            emitted += 1
                            if r.first_token_at is None:
                                r.first_token_at = clock - T + t + 1
                            if r.on_token is not None:
                                r.on_token(r, tok)
            m.count("tokens_emitted", emitted)
            if getattr(self.plan, "sharded", False):
                # analytic collective traffic for this chunk (ISSUE 10):
                # counted under the frozen collective_* keys so drift
                # detection can compare measured all-gather bytes per token
                # against the mesh decision's model
                cc = shard_mod.chunk_collectives(self.plan, steps=T,
                                                 tokens=emitted)
                for key, val in cc.items():
                    m.count(key, val)
                if cc:
                    tr.event("collective_chunk", clock, cat="collective",
                             slot=slot, **cc)
            freed_rows: List[int] = []
            for row in list(active):
                # mirror the device pos: baseline rows advance one per scan
                # step; speculative rows advance by their accepted count
                row_pos[row] += int(emits_h[:, row, :].sum()) if spec else T
                if not live_h[row]:
                    r = active.pop(row)
                    freed_rows.append(row)
                    admit_order.remove(row)
                    row_rids[row] = -1
                    row_pos.pop(row, None)
                    if self.paged:
                        self.pager.free(r.rid)   # pages return immediately
                    resolve(r, "ok")
            alloc.free_many(freed_rows)

            if g is not None and g.audit_every_sync and self.paged:
                # debug/CI mode: the full pool invariant audit after every
                # sync window — leaks surface at the boundary that caused
                # them, not as an end-of-run mystery
                guard_mod.assert_pool_clean(self.pager, tracer=tr,
                                            clock=clock, slot=slot)
        st["total_wall_s"] = run_clock.elapsed_s()
        st["clock_steps"] = clock
        m.gauge("clock", clock)
        if g is not None:
            for r in allreqs:
                if r.outcome is None:       # unreachable by construction —
                    if not r.done:          # belt and braces for the promise
                        r.done = True       # that every request terminates
                        done.append(r)
                    r.outcome = guard_mod.RequestOutcome(
                        "failed", "run ended without a terminal state",
                        at_step=clock)
            st["outcomes"] = {k: 0 for k in guard_mod.OUTCOMES}
            for r in done:
                if r.outcome is not None:
                    st["outcomes"][r.outcome.status] += 1
        if inj is not None:
            st["chaos_injected"] = dict(inj.injected)
        if self.paged:
            st["pages"] = self.pager.stats()       # drained end state
            st["pages_peak"] = peak_pages          # busiest boundary
            if g is not None:
                # every request terminal implies a fully drained pool — the
                # leak audit is the cheap proof
                guard_mod.assert_pool_clean(self.pager, drained=True,
                                            tracer=tr, clock=clock,
                                            slot=slot)
        if self._own_telemetry or self.slot < 0:
            # Eyexam-at-runtime: diff measured occupancy/length/route
            # proxies against the plan's Decision.numbers. Fleet members
            # (slot >= 0 on a shared bundle) skip this — the ReplicaSet
            # computes drift once at finalize, over the shared registry.
            st["drift"] = tel.detect_drift(self.plan).summary()
        return done
