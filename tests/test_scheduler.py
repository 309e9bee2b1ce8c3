"""Continuous-batching scheduler + paging subsystem (ISSUE 3): PageAllocator
accounting, SlotAllocator batch ops, the streaming scheduler vs the
DecodeEngine reference, arrivals, EOS page return, and recompute preemption."""
import importlib

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import dataflow
from repro.models import transformer as tfm
from repro.serve import kvcache
from repro.serve.engine import DecodeEngine, Request
from repro.serve.paging import PageAllocator
from repro.serve.scheduler import ContinuousBatchingScheduler, StreamRequest


# ------------------------------------------------------------ page allocator
def test_page_allocator_alloc_free_accounting():
    a = PageAllocator(4, page_size=8)
    assert a.available() == 4 and a.in_use == 0
    assert a.ensure(0, 9)                 # 2 pages
    assert a.pages_of(0) == 2 and a.available() == 2
    assert a.ensure(0, 10)                # still 2 pages — no growth
    assert a.pages_of(0) == 2
    assert a.ensure(1, 8)                 # 1 page
    assert a.table(0) == [0, 1] and a.table(1) == [2]
    assert a.free(0) == 2
    assert a.available() == 3
    with pytest.raises(ValueError):
        a.free(0)                         # double free


def test_page_allocator_exhaustion_is_all_or_nothing():
    a = PageAllocator(3, page_size=4)
    assert a.ensure(0, 8)                 # 2 pages
    assert not a.ensure(1, 12)            # needs 3, only 1 free — no change
    assert a.available() == 1 and a.pages_of(1) == 0
    assert 1 not in a.live_requests()
    assert a.ensure(1, 4)                 # 1 page fits
    assert not a.ensure(1, 8)             # growth refused, table unchanged
    assert a.pages_of(1) == 1


def test_page_allocator_pop_order_deterministic():
    a = PageAllocator(4, page_size=4)
    a.ensure(0, 4)
    a.ensure(1, 8)
    assert a.table(0) == [0] and a.table(1) == [1, 2]
    a.free(0)
    a.free(1)
    a.ensure(2, 12)                       # freed pages come back lowest-first
    assert a.table(2) == [0, 1, 2]


def test_page_allocator_stats_fragmentation():
    a = PageAllocator(8, page_size=8)
    a.ensure(0, 9)                        # 2 pages for 9 tokens
    a.set_length(0, 9)
    s = a.stats()
    assert s["pages_used"] == 2 and s["pages_free"] == 6
    assert s["used_tokens"] == 9
    assert s["fragmentation"] == pytest.approx(1 - 9 / 16)
    a.free(0)
    assert a.stats()["fragmentation"] == 0.0


def test_block_table_rows_device_view():
    a = PageAllocator(6, page_size=4)
    a.ensure(7, 10)                       # 3 pages
    bt = a.block_table_rows([7, -1], max_pages=4)
    assert bt.shape == (2, 4)
    assert bt[0].tolist() == [0, 1, 2, -1]
    assert bt[1].tolist() == [-1, -1, -1, -1]


# ------------------------------------------------------------ slot allocator
def test_slot_allocator_alloc_many_exhaustion_and_order():
    a = kvcache.SlotAllocator(4)
    got = a.alloc_many(3)
    assert got == [0, 1, 2]               # pop-order determinism
    with pytest.raises(RuntimeError):
        a.alloc_many(2)                   # only 1 free — all-or-nothing
    assert a.available() == 1             # nothing was partially taken
    a.free_many([1, 2])
    assert a.available() == 3
    with pytest.raises(ValueError):
        a.free_many([1])                  # double free via the batch API
    assert a.alloc_many(0) == []


# --------------------------------------------------------- kvcache satellites
def test_max_slots_zero_when_one_slot_oversized():
    cfg = get_config("gemma2-2b")
    # astronomically long context: one slot alone exceeds half-HBM
    assert kvcache.max_slots(cfg, cache_len=1 << 28, chips=1) == 0
    assert kvcache.max_slots(cfg, cache_len=8192, chips=256) >= 1


def test_engine_raises_on_zero_slots():
    cfg = get_config("qwen2.5-3b-reduced")
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="slots must be >= 1"):
        DecodeEngine(cfg, params, slots=0, cache_len=32)
    with pytest.raises(ValueError, match="rows must be >= 1"):
        ContinuousBatchingScheduler(cfg, params, rows=0, cache_len=32)


def test_report_includes_paged_occupancy():
    cfg = get_config("gemma2-2b")
    pager = PageAllocator(16, page_size=64)
    pager.ensure(0, 100)
    pager.set_length(0, 100)
    rep = kvcache.report(cfg, batch=4, cache_len=8192, chips=256, pager=pager)
    assert rep["paged"]["pages_total"] == 16
    assert rep["paged"]["pages_used"] == 2
    assert 0.0 < rep["paged"]["fragmentation"] < 1.0
    assert "paged" not in kvcache.report(cfg, 4, 8192, 256)


# ----------------------------------------------------------------- scheduler
PROMPTS = [[5, 6, 7], [9, 8, 7, 6, 5, 4], [1, 2], [3, 3, 3, 3, 3]]


def _engine_reference(cfg, params, prompts, max_new, cache_len=64):
    eng = DecodeEngine(cfg, params, slots=1, cache_len=cache_len, eos_id=-1,
                       sync_every=4)
    return [eng.run([Request(99, p, max_new)])[0].out for p in prompts]


@pytest.mark.parametrize("attn_path", ["paged", "contiguous"])
def test_scheduler_matches_engine_tokens(attn_path):
    """Both dispatch arms produce the engine's exact greedy tokens."""
    cfg = get_config("qwen2.5-3b-reduced")
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    ref = _engine_reference(cfg, params, PROMPTS, 5)
    sch = ContinuousBatchingScheduler(cfg, params, rows=2, cache_len=64,
                                      page_size=8, eos_id=-1, sync_every=4,
                                      attn_path=attn_path)
    assert sch.paged == (attn_path == "paged")
    done = sch.run([StreamRequest(i, p, 5) for i, p in enumerate(PROMPTS)])
    got = [r.out for r in sorted(done, key=lambda r: r.rid)]
    assert got == ref
    assert sch.phase_stats["attn_path"] == attn_path


@pytest.mark.parametrize("kv_quant", ["fp", "int8"])
def test_scheduler_attn_block_counters_match_kernel(monkeypatch, kv_quant):
    """attn_blocks_real / attn_blocks_grid equal what the paged kernel was
    handed: its own row_blocks bound over the lengths and tables of every
    call, and the padded (rows, blocks) grid of those calls."""
    kmod = importlib.import_module("repro.kernels.paged_attention")
    monkeypatch.setattr(kmod, "BLOCK_TOKENS", 8)      # 2 pages of 4 a block
    real_raw = kmod.paged_attention_raw
    seen = []

    def recording(q, k_pool, v_pool, block_table, lengths, **kw):
        P, ps, KV, D = k_pool.shape
        MP = block_table.shape[1]
        ppb = kmod.pages_per_block(ps, KV, D, k_pool.dtype.itemsize, MP)

        def record(lens, first):
            seen.append((int(kmod.row_blocks(lens, first, ps, ppb, MP).sum()),
                         len(lens) * -(-MP // ppb)))
        jax.debug.callback(record, lengths, block_table[:, 0])
        return real_raw(q, k_pool, v_pool, block_table, lengths, **kw)

    monkeypatch.setattr(kmod, "paged_attention_raw", recording)
    cfg = get_config("qwen2.5-3b-reduced")
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    sch = ContinuousBatchingScheduler(cfg, params, rows=2, cache_len=32,
                                      page_size=4, eos_id=-1, sync_every=4,
                                      attn_path="paged", kv_quant=kv_quant)
    prompts = [[5] * 3, [7] * 13, [2] * 6]
    sch.run([StreamRequest(i, p, 9) for i, p in enumerate(prompts)])
    jax.effects_barrier()
    st = sch.phase_stats
    assert st["attn_blocks_real"] == sum(r for r, _ in seen) > 0
    assert st["attn_blocks_grid"] == sum(g for _, g in seen)
    assert st["attn_blocks_real"] < st["attn_blocks_grid"]
    assert sch.telemetry.metrics.counters["attn_blocks_real"] == \
        st["attn_blocks_real"]


def test_scheduler_recurrent_arch_contiguous_fallback():
    """Archs without global attention dispatch contiguous automatically."""
    cfg = get_config("recurrentgemma-2b-reduced")
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    sch = ContinuousBatchingScheduler(cfg, params, rows=2, cache_len=64,
                                      eos_id=-1, sync_every=4)
    assert not sch.paged
    ref = _engine_reference(cfg, params, PROMPTS[:3], 4)
    done = sch.run([StreamRequest(i, p, 4) for i, p in enumerate(PROMPTS[:3])])
    assert [r.out for r in sorted(done, key=lambda r: r.rid)] == ref


def test_scheduler_streaming_callbacks_in_order():
    cfg = get_config("qwen2.5-3b-reduced")
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    sch = ContinuousBatchingScheduler(cfg, params, rows=2, cache_len=64,
                                      page_size=8, eos_id=-1, sync_every=4)
    seen = {}
    reqs = [StreamRequest(i, p, 5,
                          on_token=lambda r, t: seen.setdefault(r.rid, []
                                                                ).append(t))
            for i, p in enumerate(PROMPTS)]
    done = sch.run(reqs)
    for r in done:
        assert seen[r.rid] == r.out       # streamed == accumulated, in order


def test_scheduler_arrival_gating_and_latency_stamps():
    cfg = get_config("qwen2.5-3b-reduced")
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    sch = ContinuousBatchingScheduler(cfg, params, rows=2, cache_len=64,
                                      page_size=8, eos_id=-1, sync_every=4)
    reqs = [StreamRequest(0, [5, 6, 7], 4, arrival=0.0),
            StreamRequest(1, [1, 2], 4, arrival=10.0)]
    done = sch.run(reqs)
    by_rid = {r.rid: r for r in done}
    assert by_rid[0].admitted_at == 0.0
    assert by_rid[1].admitted_at >= 10.0          # never admitted early
    for r in done:
        assert r.first_token_at > r.admitted_at - 1e-9
        assert r.finished_at >= r.first_token_at
        assert r.queued_wall <= r.admitted_wall <= r.first_token_wall \
            <= r.finished_wall


def test_scheduler_idle_jump_to_next_arrival():
    """With nothing active, the virtual clock jumps to the next arrival
    instead of spinning empty chunks."""
    cfg = get_config("qwen2.5-3b-reduced")
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    sch = ContinuousBatchingScheduler(cfg, params, rows=2, cache_len=64,
                                      page_size=8, eos_id=-1, sync_every=4)
    done = sch.run([StreamRequest(0, [5, 6], 4, arrival=100.0)])
    assert done[0].admitted_at == 100.0
    assert sch.phase_stats["idle_steps"] == 100.0
    assert sch.phase_stats["decode_chunks"] == 1


def test_scheduler_eos_returns_pages():
    """Pages go back to the pool when a request finishes by EOS."""
    cfg = get_config("qwen2.5-3b-reduced")
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    probe = ContinuousBatchingScheduler(cfg, params, rows=1, cache_len=48,
                                        page_size=8, eos_id=-1, sync_every=2)
    first = probe.run([StreamRequest(0, [5, 6, 7], 1)])[0].out[0]
    sch = ContinuousBatchingScheduler(cfg, params, rows=1, cache_len=48,
                                      page_size=8, eos_id=first, sync_every=4)
    done = sch.run([StreamRequest(0, [5, 6, 7], 8),
                    StreamRequest(1, [5, 6, 7], 8)])
    assert all(r.out == [first] for r in done)    # EOS cut both short
    st = sch.phase_stats["pages"]
    assert st["pages_free"] == st["pages_total"]  # everything returned
    peak = sch.phase_stats["pages_peak"]
    assert peak["pages_used"] > 0                 # mid-run occupancy recorded
    assert peak["used_tokens"] > 0


def test_scheduler_preemption_recompute_exact():
    """Under page pressure the latest-admitted request is preempted and
    recomputed — final tokens still match the unpressured reference."""
    cfg = get_config("qwen2.5-3b-reduced")
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    ref = _engine_reference(cfg, params, PROMPTS, 12)
    sch = ContinuousBatchingScheduler(cfg, params, rows=3, cache_len=64,
                                      page_size=4, num_pages=6, eos_id=-1,
                                      sync_every=4)
    done = sch.run([StreamRequest(i, p, 12) for i, p in enumerate(PROMPTS)])
    got = [r.out for r in sorted(done, key=lambda r: r.rid)]
    assert got == ref
    assert sch.phase_stats["preemptions"] > 0
    assert max(r.preemptions for r in done) > 0
    st = sch.phase_stats["pages"]
    assert st["pages_free"] == st["pages_total"]


def test_scheduler_rejects_impossible_requests():
    cfg = get_config("qwen2.5-3b-reduced")
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    sch = ContinuousBatchingScheduler(cfg, params, rows=1, cache_len=32,
                                      page_size=8, eos_id=-1)
    with pytest.raises(ValueError, match="exceeds cache_len"):
        sch.run([StreamRequest(0, [1] * 30, 8)])
    with pytest.raises(ValueError, match="rids must be unique"):
        sch.run([StreamRequest(0, [1, 2], 2), StreamRequest(0, [3, 4], 2)])
    tiny = ContinuousBatchingScheduler(cfg, params, rows=1, cache_len=32,
                                       page_size=8, num_pages=2, eos_id=-1)
    with pytest.raises(ValueError, match="can never run"):
        tiny.run([StreamRequest(0, [1] * 20, 8)])


def test_tier_clamped_to_cache_len():
    """A prompt whose pow2 tier exceeds cache_len must still prefill: the
    tier clamps (right-padding stays exact at any tier >= plen)."""
    from repro.serve.engine import length_tier
    assert length_tier(17, False, 24) == 24
    assert length_tier(17, False) == 32           # unclamped helper
    cfg = get_config("qwen2.5-3b-reduced")
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    prompt = [3] * 17                             # pow2 tier 32 > cache_len 24
    eng = DecodeEngine(cfg, params, slots=1, cache_len=24, eos_id=-1,
                       sync_every=2)
    ref = eng.run([Request(0, prompt, 4)])[0].out
    assert len(ref) == 4
    sch = ContinuousBatchingScheduler(cfg, params, rows=1, cache_len=24,
                                      page_size=8, eos_id=-1, sync_every=2)
    done = sch.run([StreamRequest(0, prompt, 4)])
    assert done[0].out == ref


def test_scheduler_validates_feasibility_up_front():
    """A late-arriving infeasible request fails at run() entry, before any
    device work — finished requests' results are never lost mid-run."""
    cfg = get_config("qwen2.5-3b-reduced")
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    sch = ContinuousBatchingScheduler(cfg, params, rows=1, cache_len=32,
                                      page_size=8, eos_id=-1)
    ok = StreamRequest(0, [5, 6], 3, arrival=0.0)
    bad = StreamRequest(1, [1] * 30, 8, arrival=500.0)
    with pytest.raises(ValueError, match="exceeds cache_len"):
        sch.run([ok, bad])
    assert ok.out == []                       # raised before any decoding


def test_scheduler_paged_pool_smaller_than_dense():
    """The configuration the subsystem exists for: a page pool provisioned
    below rows × cache_len still serves everything correctly."""
    cfg = get_config("qwen2.5-3b-reduced")
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    rows, cache_len, ps = 4, 64, 8
    dense_pages = rows * (cache_len // ps)
    sch = ContinuousBatchingScheduler(cfg, params, rows=rows,
                                      cache_len=cache_len, page_size=ps,
                                      num_pages=dense_pages // 2, eos_id=-1,
                                      sync_every=4)
    ref = _engine_reference(cfg, params, PROMPTS, 6)
    done = sch.run([StreamRequest(i, p, 6) for i, p in enumerate(PROMPTS)])
    assert [r.out for r in sorted(done, key=lambda r: r.rid)] == ref
    assert dataflow.paged_kv_tokens(
        [len(p) + 6 for p in PROMPTS], ps) < dataflow.dense_kv_tokens(
        rows, cache_len)
