"""Batched serving engine: prefill + decode steps, sampling, slot management.

``serve_step``/``prefill_step`` are the functions the dry-run lowers for the
``decode_*``/``prefill_*`` shapes. The ``DecodeEngine`` adds a continuous
batching loop (batched tier-bucketed refill on EOS, ISSUE 2) whose inner
decode loop is **device resident**: sampling, EOS detection and budget
accounting all run inside a ``lax.scan`` of ``sync_every`` fused steps, so
between refills there are zero per-token device→host transfers — the
utilization lever the Eyexam step model identifies for batch-1 decode (paper
Table VI; ISSUE 1). The decode state is donated to both jitted programs, so
the slot KV cache is updated in place rather than copied each chunk.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import plan as plan_lib
from repro.models import decoding
from repro.serve import kvcache
from repro.serve import telemetry as telemetry_mod
from repro.serve.guard import RequestOutcome


def make_serve_step(cfg) -> Callable:
    """(params, cache, tokens, pos[, cond]) -> (logits, new_cache)."""
    def serve_step(params, cache, tokens, pos, cond=None):
        return decoding.serve_step(params, cache, tokens, pos, cfg, cond=cond)
    return serve_step


def make_prefill_step(cfg, cache_len: int) -> Callable:
    def prefill_step(params, tokens, patch_embeds=None, cond=None):
        return decoding.prefill(params, tokens, cfg, cache_len,
                                patch_embeds=patch_embeds, cond=cond)
    return prefill_step


def sample_greedy(logits):
    return jnp.argmax(logits, axis=-1)


def sample_temperature(rng, logits, temperature: float = 1.0):
    if temperature <= 0:
        return sample_greedy(logits)
    return jax.random.categorical(rng, logits / temperature, axis=-1)


def make_generate_fn(cfg, num_steps: int, temperature: float = 0.0):
    """Fused prefill + N decode steps via lax.scan (one jit-able program)."""
    def generate(params, tokens, rng, patch_embeds=None, cond=None):
        B = tokens.shape[0]
        prompt_len = tokens.shape[-1] + (
            cfg.num_patches if cfg.frontend == "vision" else 0)
        cache_len = prompt_len + num_steps
        logits, cache = decoding.prefill(params, tokens, cfg, cache_len,
                                         patch_embeds=patch_embeds, cond=cond)

        def step(carry, rng_i):
            cache, last_logits, pos = carry
            nxt = sample_temperature(rng_i, last_logits[..., -1, :] if
                                     cfg.num_codebooks > 1 else
                                     last_logits[:, -1], temperature)
            if cfg.num_codebooks > 1:
                tok = nxt.reshape(B, cfg.num_codebooks, 1) if nxt.ndim > 1 \
                    else jnp.tile(nxt[:, None, None], (1, cfg.num_codebooks, 1))
            else:
                tok = nxt[:, None]
            logits, cache = decoding.serve_step(params, cache, tok, pos, cfg,
                                                cond=cond)
            return (cache, logits, pos + 1), nxt

        rngs = jax.random.split(rng, num_steps)
        (_, _, _), out_tokens = jax.lax.scan(
            step, (cache, logits, jnp.int32(prompt_len)), rngs)
        return jnp.moveaxis(out_tokens, 0, 1)  # (B, num_steps[, K])

    return generate


@dataclasses.dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new: int
    out: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    outcome: Optional[RequestOutcome] = None


def length_tier(plen: int, recurrent: bool, cache_len: int = 0) -> int:
    """Length bucket for batched prefill: next power of two (attention archs
    — causality makes right-padding exact); exact length for recurrent archs
    (pads would pollute ssm/rglru carried state). Clamped to ``cache_len``
    when given: the padded tier must fit the cache rows prefill builds
    (plen itself is validated ≤ cache_len by the callers, and right-padding
    stays exact at any tier ≥ plen). Shared by DecodeEngine and
    serve.scheduler."""
    if recurrent:
        return plen
    tier = 1 << max(plen - 1, 0).bit_length()
    return min(tier, cache_len) if cache_len else tier


def make_decode_step(cfg, temperature: float, eos_id: int) -> Callable:
    """One fused decode step: sample → EOS/budget masks → serve_step.

    The single source of the sampling/EOS/budget semantics, shared by
    DecodeEngine's chunk and the scheduler's paged chunk (which passes a
    ``block_table``) — the two loops cannot drift apart.
    """
    K = cfg.num_codebooks

    def step(params, carry, rng_i, block_table=None):
        cache, last, pos, live, budget = carry
        # ``last`` is (B,V) for LMs, (B,K,V) for multi-codebook (musicgen) —
        # sample_temperature reduces the trailing axis either way; the first
        # codebook carries EOS.
        with jax.named_scope("sample"):
            nxt = sample_temperature(rng_i, last, temperature)
            head = nxt[:, 0] if K > 1 else nxt
            emit = live                      # emitted this step
            budget = budget - emit.astype(jnp.int32)
            live = live & (head != eos_id) & (budget > 0)
        tok = nxt[..., None]                 # (B,1) or (B,K,1)
        logits, cache = decoding.serve_step(params, cache, tok, pos, cfg,
                                            block_table=block_table)
        last = logits[:, -1]                 # (B,V) or (B,K,V)
        return (cache, last, pos + 1, live, budget), (nxt, emit)

    return step


def ngram_successor(hist, pos, tok):
    """Self-drafting bigram lookup (ISSUE 9): for each row, the token that
    followed the most recent earlier occurrence of ``tok`` in that row's
    history, falling back to ``tok`` itself (repeat) when it never occurred.

    ``hist`` (B, H) holds the row's token stream by absolute position
    (positions >= ``pos`` are garbage from rejected drafts — masked here);
    ``pos`` (B,) is the valid history length. Only the successor position
    ``j + 1 < pos`` may be read, so the draft is a pure function of the
    committed stream — acceptance rate is a quality knob, never a
    correctness one.
    """
    H = hist.shape[1]
    idx = jnp.arange(H, dtype=jnp.int32)
    match = (hist == tok[:, None]) & (idx[None, :] + 1 < pos[:, None])
    j = jnp.where(match, idx[None, :], -1).max(axis=1)        # most recent
    nxt = jnp.take_along_axis(hist, jnp.clip(j + 1, 0, H - 1)[:, None],
                              axis=1)[:, 0]
    return jnp.where(j >= 0, nxt, tok)


def make_spec_decode_step(cfg, eos_id: int, k: int) -> Callable:
    """One fused speculative round: draft k candidates → ONE k-position
    verify dispatch → accept the matched prefix (ISSUE 9). Greedy only —
    the scheduler gates speculation on temperature <= 0 (and the plan on
    fp paged pools), which is what makes the accepted stream bit-identical
    to ``make_decode_step``'s: candidate 0 IS the baseline's argmax over
    ``last``, and candidate i+1 is emitted only when it equals the
    verifier's argmax after candidates 0..i — every emitted token is
    exactly the token sequential greedy decode would have produced.

    Carry adds a ``hist`` (B, H) token-history buffer (absolute-position
    indexed, seeded from the prompt at refill) that feeds the bigram
    self-draft; rejected candidates past the accepted prefix leave garbage
    beyond ``pos``, which both the drafter and the paged attention reads
    mask by length — no rollback scatter, host-side fork refcounts
    (paging.fork_chain/commit_fork/abort_fork) are the only cleanup.

    Per round a row emits n ∈ [1, k] tokens (0 when dead): the accepted
    prefix clamped by EOS and remaining budget; ``pos`` advances by n and
    ``last`` becomes the verifier logits after the last emitted token —
    the all-accepted case hands next round its bonus argmax for free.
    Emits (toks (B, k), emit_mask (B, k)) per scan step.
    """
    K = cfg.num_codebooks
    assert K == 1, "speculative decode is single-codebook only"
    assert k >= 2, f"spec k must be >= 2, got {k}"

    def step(params, carry, rng_i, block_table=None):
        del rng_i                              # greedy: sampling is argmax
        cache, last, pos, live, budget, hist = carry
        B = last.shape[0]
        t0 = jnp.argmax(last, axis=-1).astype(jnp.int32)      # (B,)
        cands = [t0]
        for _ in range(k - 1):
            cands.append(ngram_successor(hist, pos, cands[-1]))
        v = jnp.stack(cands, axis=1)                          # (B, k)
        logits, cache = decoding.verify_step(params, cache, v, pos, cfg,
                                             block_table=block_table)
        g = jnp.argmax(logits, axis=-1).astype(jnp.int32)     # (B, k)
        # accept-prefix: candidate 0 is the true argmax by construction;
        # candidate i (i >= 1) survives iff it equals the verifier's argmax
        # after candidates 0..i-1 AND everything before it survived
        ok = jnp.concatenate(
            [jnp.ones((B, 1), bool), v[:, 1:] == g[:, :-1]], axis=1)
        acc = jnp.cumprod(ok.astype(jnp.int32), axis=1).astype(bool)
        # emission clamps, identical semantics to make_decode_step unrolled:
        # stop at the first emitted EOS, never exceed the remaining budget
        not_eos = v != eos_id
        no_prior_eos = jnp.concatenate(
            [jnp.ones((B, 1), bool),
             jnp.cumprod(not_eos[:, :-1].astype(jnp.int32),
                         axis=1).astype(bool)], axis=1)
        steps_i = jnp.arange(k, dtype=jnp.int32)[None, :]
        emit = live[:, None] & acc & no_prior_eos & (budget[:, None] > steps_i)
        n = emit.sum(axis=1).astype(jnp.int32)                # (B,)
        budget = budget - n
        hit_eos = jnp.any(emit & ~not_eos, axis=1)
        new_live = live & ~hit_eos & (budget > 0)
        # next round's sampling distribution: verifier logits after the last
        # emitted token (dead rows keep their last unchanged, n == 0 there)
        sel = jnp.clip(n - 1, 0, k - 1)
        picked = jnp.take_along_axis(logits, sel[:, None, None],
                                     axis=1)[:, 0]
        last = jnp.where(live[:, None], picked, last)
        # history append: write all k candidates at pos..pos+k-1 — the
        # rejected tail beyond pos+n is overwritten by the next round and
        # masked by ngram_successor/verify reads until then
        H = hist.shape[1]
        posk = pos[:, None] + steps_i
        posk = jnp.where((posk < H) & live[:, None], posk, H)
        hist = hist.at[jnp.arange(B)[:, None], posk].set(v, mode="drop")
        return (cache, last, pos + n, new_live, budget, hist), (v, emit)

    return step


def build_tier_batch(group, tier: int, prompt_of: Callable,
                     budget_of: Callable, start_of: Callable = None):
    """Host-side arrays for one admission tier: (toks, lengths, slots,
    budgets, starts). ``group`` is [(slot, request), ...];
    ``prompt_of``/``budget_of`` extract the (possibly resume-extended)
    prompt and remaining budget; ``start_of`` the first prompt token the
    prefill actually writes (> 0 when a shared-prefix chain already holds
    the leading pages — the scheduler's CoW admission; default 0, write
    everything). Shared by DecodeEngine.run and the scheduler's admission."""
    B = len(group)
    toks = np.zeros((B, tier), np.int32)
    lengths = np.empty((B,), np.int32)
    slot_ids = np.empty((B,), np.int32)
    budgets = np.empty((B,), np.int32)
    starts = np.zeros((B,), np.int32)
    for i, (slot, r) in enumerate(group):
        p = prompt_of(r)
        toks[i, :len(p)] = p
        lengths[i] = len(p)
        slot_ids[i] = slot
        budgets[i] = budget_of(r)
        if start_of is not None:
            starts[i] = start_of(r)
    return toks, lengths, slot_ids, budgets, starts


class DecodeEngine:
    """Continuous batching over a fixed slot count, device-resident decode.

    Slots hold independent sequences with **per-slot positions** (the
    vector-pos path of decoding.serve_step). Admission is **chunked batched
    prefill** (ISSUE 2): pending prompts are bucketed into padded length
    tiers (next power of two; exact lengths for recurrent archs, where pad
    tokens would pollute the carried state), each tier is prefilled as ONE
    batch through ``decoding.prefill_batched``, and the resulting cache rows
    are scattered into their slots — admission cost amortizes over the
    cohort the same way decode already does, instead of one batch-1 prefill
    per slot. Between refills the loop never leaves the device: ``sync_every``
    decode steps — on-device sampling, EOS live-mask and max_new budget
    tracking — run as one ``lax.scan`` (same structure as make_generate_fn),
    and the generated token block is fetched with a single ``jax.device_get``
    per chunk. ``host_syncs`` counts those fetches; there are zero per-token
    transfers. The decode-state argument of both jitted programs is donated,
    so the KV cache updates in place instead of being copied every chunk.

    ``phase_stats`` (reset per run) reports the prefill/decode wall-clock
    split, batch counts, and real-vs-padded prefill token counts — the
    admission-amortization evidence benchmarks/sparse_decode.py records.

    Construction is plan-driven (ISSUE 5): pass a ``core.plan.ServePlan``
    (``plan_for_engine`` for explicit slots/cache_len) and the engine reads
    slots, cache_len, sync cadence, and the prefill tier ladder from it,
    activating the plan around its jitted programs so the MLP/matmul kernel
    routes come from the same resolved crossovers. The legacy
    ``slots=…, cache_len=…`` kwargs remain as a deprecated shim building
    the identical single-decision plan.
    """

    def __init__(self, cfg, params, plan: Optional[plan_lib.ServePlan] = None,
                 *, slots: Optional[int] = None,
                 cache_len: Optional[int] = None,
                 eos_id: int = 1, temperature: float = 0.0,
                 sync_every: Optional[int] = None,
                 telemetry: Optional["telemetry_mod.Telemetry"] = None):
        if plan is not None and not (slots is None and cache_len is None):
            # a plan plus legacy geometry kwargs would silently lose the
            # kwargs (the plan wins) — refuse instead of surprising the
            # caller mid-migration; sync_every alone stays an honored
            # per-engine override
            raise TypeError(
                "pass either plan= or the legacy slots=/cache_len= kwargs, "
                "not both (the plan already fixes the geometry)")
        if plan is None:
            # legacy kwarg construction: build the single-decision plan the
            # old inline dispatch amounted to (same core.dataflow rules, so
            # behavior is bit-identical — tests/test_plan.py asserts it)
            if slots is None or cache_len is None:
                raise TypeError(
                    "DecodeEngine needs a ServePlan (core.plan.plan_serve / "
                    "plan_for_engine) or the legacy slots=/cache_len= kwargs")
            warnings.warn(
                "constructing DecodeEngine from slots=/cache_len= kwargs is "
                "deprecated — pass plan=core.plan.plan_for_engine(...) or "
                "serve through repro.serve.LLM",
                DeprecationWarning, stacklevel=2)
            if slots < 1:
                # kvcache.max_slots returns 0 when one slot alone exceeds
                # the HBM budget — refuse here instead of letting the
                # zero-row cache OOM or produce empty batches downstream
                raise ValueError(
                    f"slots must be >= 1, got {slots}: a (1, {cache_len}) "
                    "cache slot does not fit the HBM budget "
                    "(kvcache.max_slots == 0) — shrink cache_len, shard "
                    "over more chips, or raise the budget fraction")
            plan = plan_lib.plan_for_engine(
                cfg, slots=slots, cache_len=cache_len,
                sync_every=8 if sync_every is None else sync_every)
        if plan.rows < 1:
            raise ValueError(
                f"slots must be >= 1, got {plan.rows}: a "
                f"(1, {plan.cache_len}) cache slot does not fit the HBM "
                "budget (kvcache.max_slots == 0) — shrink cache_len, shard "
                "over more chips, or raise the budget fraction")
        self.cfg = cfg
        self.params = params
        self.plan = plan
        self.slots = plan.rows
        self.cache_len = plan.cache_len
        self.eos_id = eos_id
        self.temperature = temperature
        self.sync_every = max(1, plan.sync_every if sync_every is None
                              else sync_every)
        self.host_syncs = 0                  # device->host fetches (per chunk)
        # mirror of plan.prefill_exact (tests introspect it; tier dispatch
        # itself goes through plan.tier)
        self._recurrent = plan.prefill_exact
        self.phase_stats: Dict = {}
        # observability (serve.telemetry): the drain engine has no arrival
        # clock, so its spans sit on a synthetic one (decode_chunks * T)
        self.telemetry = telemetry if telemetry is not None \
            else telemetry_mod.Telemetry()
        self._own_telemetry = telemetry is None
        # the decode state (arg 1: cache + sampling state) is donated — the
        # cache buffer is updated in place step over step, never copied
        self._chunk = jax.jit(self._make_chunk_fn(), donate_argnums=(1,))
        self._refill = jax.jit(self._make_refill_fn(), donate_argnums=(1,))

    # ------------------------------------------------------ device programs
    def _make_refill_fn(self) -> Callable:
        """Batched prefill of one length tier, scattered into its slots.

        toks (B, tier) right-padded; lengths/slots/max_new (B,). One jit per
        (tier, B) shape pair — tiers are powers of two, so the trace count
        stays logarithmic in prompt-length spread.
        """
        cfg, cache_len = self.cfg, self.cache_len

        def refill(params, state, toks, lengths, slots, max_new):
            cache, last, pos, live, budget = state
            logits, row_cache = decoding.prefill_batched(
                params, toks, lengths, cfg, cache_len)
            plen = lengths + (cfg.num_patches
                              if cfg.frontend == "vision" else 0)
            new_cache = {}
            if "blocks" in cache:    # stacked entries: (nper, B, ...) — axis 1
                new_cache["blocks"] = jax.tree.map(
                    lambda c, s: c.at[:, slots].set(s.astype(c.dtype)),
                    cache["blocks"], row_cache["blocks"])
            if "rem" in cache:       # unstacked entries: (B, ...) — axis 0
                new_cache["rem"] = jax.tree.map(
                    lambda c, s: c.at[slots].set(s.astype(c.dtype)),
                    cache["rem"], row_cache["rem"])
            last = last.at[slots].set(logits[:, -1].astype(last.dtype))
            pos = pos.at[slots].set(plen)
            live = live.at[slots].set(True)
            budget = budget.at[slots].set(max_new)
            return (new_cache, last, pos, live, budget)

        return refill

    def _tier(self, plen: int) -> int:
        # the plan's resolved tier ladder (== length_tier by construction)
        return self.plan.tier(plen)

    def _make_chunk_fn(self) -> Callable:
        """sync_every fused decode steps: sample → track EOS/budget → step."""
        T = self.sync_every
        step = make_decode_step(self.cfg, self.temperature, self.eos_id)

        def chunk(params, state, rng):
            rngs = jax.random.split(rng, T)
            state, (toks, emits) = jax.lax.scan(
                lambda carry, rng_i: step(params, carry, rng_i), state, rngs)
            return state, toks, emits

        return chunk

    # -------------------------------------------------------------- host loop
    def _init_state(self):
        cfg = self.cfg
        cache = decoding.init_cache(cfg, self.slots, self.cache_len)
        vshape = (self.slots, cfg.num_codebooks, cfg.vocab_padded) \
            if cfg.num_codebooks > 1 else (self.slots, cfg.vocab_padded)
        last = jnp.zeros(vshape, jnp.float32)
        pos = jnp.zeros((self.slots,), jnp.int32)
        live = jnp.zeros((self.slots,), jnp.bool_)
        budget = jnp.zeros((self.slots,), jnp.int32)
        return (cache, last, pos, live, budget)

    def run(self, requests: List[Request], rng=None) -> List[Request]:
        # the plan is the dispatch source for everything traced below
        # (layers.mlp / kernels.ops read it instead of re-deriving rules)
        with plan_lib.activate(self.plan):
            return self._run(requests, rng)

    def _run(self, requests: List[Request], rng=None) -> List[Request]:
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        queue = list(requests)
        done: List[Request] = []
        for r in [r for r in queue if r.max_new <= 0]:
            queue.remove(r)
            r.done = True
            r.outcome = RequestOutcome("ok", "empty generation budget")
            done.append(r)
        alloc = kvcache.SlotAllocator(self.slots)
        active: Dict[int, Request] = {}
        state = self._init_state()
        K = self.cfg.num_codebooks
        st = self.phase_stats = {
            "prefill_s": 0.0, "decode_s": 0.0, "prefill_batches": 0,
            "prefill_prompts": 0, "prefill_real_tokens": 0,
            "prefill_padded_tokens": 0, "decode_chunks": 0,
        }
        if self._own_telemetry:
            self.telemetry.reset()
        tr = self.telemetry.tracer
        T = self.sync_every

        while queue or active:
            # ---- admission: batched prefill, one call per length tier ----
            admits: List[Tuple[int, Request]] = []
            while queue and alloc.available():
                r = queue[0]
                plen = len(r.prompt) + (self.cfg.num_patches
                                        if self.cfg.frontend == "vision" else 0)
                if plen + r.max_new > self.cache_len:
                    # global-attention slots would silently wrap/clobber the
                    # last cache row past cache_len — refuse loudly instead
                    raise ValueError(
                        f"request {r.rid}: prompt ({plen}) + max_new "
                        f"({r.max_new}) exceeds cache_len ({self.cache_len})")
                queue.pop(0)
                admits.append((alloc.alloc(), r))
            if admits:
                buckets: Dict[int, List[Tuple[int, Request]]] = {}
                for slot, r in admits:
                    buckets.setdefault(self._tier(len(r.prompt)),
                                       []).append((slot, r))
                with telemetry_mod.phase_timer(
                        st, "prefill_s", tracer=tr, name="prefill",
                        start=st["decode_chunks"] * T) as ph:
                    for tier, group in sorted(buckets.items()):
                        B = len(group)
                        toks, lengths, slot_ids, max_news, _ = \
                            build_tier_batch(
                                group, tier, lambda r: r.prompt,
                                lambda r: r.max_new)
                        for slot, r in group:
                            active[slot] = r
                        state = self._refill(self.params, state,
                                             jnp.asarray(toks),
                                             jnp.asarray(lengths),
                                             jnp.asarray(slot_ids),
                                             jnp.asarray(max_news))
                        st["prefill_batches"] += 1
                        st["prefill_prompts"] += B
                        st["prefill_real_tokens"] += int(lengths.sum())
                        st["prefill_padded_tokens"] += B * tier
                    ph.ready(state[1])          # phase-accurate timing
                    ph.note(prompts=len(admits), tiers=len(buckets))

            # ---------------------- device-resident decode chunk ----------
            with telemetry_mod.phase_timer(
                    st, "decode_s", tracer=tr, name="decode_chunk",
                    start=st["decode_chunks"] * T,
                    end=(st["decode_chunks"] + 1) * T) as ph:
                rng, k = jax.random.split(rng)
                state, toks, emits = self._chunk(self.params, state, k)
                # the single device->host transfer for this chunk
                toks_h, emits_h, live_h = jax.device_get(
                    (toks, emits, state[3]))
                ph.note(rows=len(active))
            self.host_syncs += 1
            st["decode_chunks"] += 1
            for t in range(emits_h.shape[0]):
                for slot, r in active.items():
                    if emits_h[t, slot]:
                        r.out.append([int(v) for v in toks_h[t, slot]]
                                     if K > 1 else int(toks_h[t, slot]))
            for slot in list(active):
                if not live_h[slot]:
                    r = active.pop(slot)
                    r.done = True
                    r.outcome = RequestOutcome("ok")
                    done.append(r)
                    alloc.free(slot)
        return done
