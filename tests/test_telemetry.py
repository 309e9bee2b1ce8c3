"""Observability suite (ISSUE 8): deterministic step-clock tracing, the
frozen metrics registry, phase timers, the shared heartbeat schema, and
Eyexam-at-runtime plan-drift detection.

The load-bearing invariants:

* trace *structure* is a pure function of the seed — two same-seed runs
  (including chaos runs, single-scheduler and multi-replica) produce
  byte-identical Chrome traces once wall-clock annotations are stripped;
* the metric key set is frozen — adding or removing a key silently fails
  the pinned-key test, and writing an undeclared name raises;
* a seeded mispredicted-occupancy scenario yields a DriftReport that names
  the divergent plan Decision, and an accurate plan yields a clean report.
"""
import json

import jax
import pytest

from repro.configs import get_config
from repro.core import plan as plan_lib
from repro.models import transformer as tfm
from repro.runtime.fault_tolerance import FaultToleranceConfig, Supervisor
from repro.serve import LLM, telemetry
from repro.serve.chaos import ChaosConfig, ReplicaChaosConfig
from repro.serve.scheduler import ContinuousBatchingScheduler, StreamRequest


@pytest.fixture(scope="module")
def model():
    cfg = get_config("qwen2.5-3b-reduced")
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _reqs(n=6, max_new=6, tenants=2):
    return [StreamRequest(rid=i, prompt=[3 + i % 4, 5, 7], max_new=max_new,
                          arrival=float(i), tenant="t%d" % (i % tenants))
            for i in range(n)]


def _plan(cfg, mean=10, cache_len=64):
    # page_size=4 keeps expected occupancy below PAGED_OCCUPANCY_MAX so the
    # plan resolves the paged path (the drift comparisons' richest case)
    return plan_lib.plan_serve(
        cfg, hbm_budget_bytes=1 << 30, expected_batch=3,
        expected_len_dist={"mean": mean, "max": cache_len}, page_size=4,
        sync_every=4)


# ------------------------------------------------------------------- tracer
def test_tracer_records_events_and_spans():
    tr = telemetry.Tracer()
    tr.event("queued", 0.0, cat="request", rid=3, tenant="t0")
    tr.span("decode_chunk", 4.0, 8.0, cat="phase", slot=1, wall_s=0.01,
            rows=2)
    assert len(tr.events) == 2
    e0, e1 = tr.events
    assert e0.dur == 0.0 and e0.rid == 3 and e0.args == {"tenant": "t0"}
    assert e1.dur == 4.0 and e1.slot == 1 and e1.wall_s == 0.01
    tr.reset()
    assert tr.events == []


def test_tracer_disabled_is_noop():
    tr = telemetry.Tracer(enabled=False)
    tr.event("queued", 0.0)
    tr.span("x", 0.0, 4.0)
    assert tr.events == [] and tr.signature() == "[]"


def test_signature_strips_wall_time_only():
    a, b = telemetry.Tracer(), telemetry.Tracer()
    a.span("prefill", 0.0, 4.0, cat="phase", wall_s=0.123)
    b.span("prefill", 0.0, 4.0, cat="phase", wall_s=9.876)
    assert a.signature() == b.signature()
    b.span("extra", 4.0, 4.0)
    assert a.signature() != b.signature()


def test_chrome_trace_mapping_and_strip():
    tr = telemetry.Tracer()
    tr.span("decode_chunk", 4.0, 8.0, cat="phase", slot=0, wall_s=0.5)
    tr.event("outcome", 8.0, cat="request", slot=0, rid=2, status="ok")
    doc = tr.to_chrome_trace()
    assert doc["otherData"]["schema"] == telemetry.SCHEMA
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert meta[0]["args"]["name"] == "replica 0"       # pid = slot + 1
    span = next(e for e in doc["traceEvents"] if e["ph"] == "X")
    assert span["ts"] == 4000.0 and span["dur"] == 4000.0   # 1 step = 1 ms
    assert span["args"]["wall_s"] == 0.5
    inst = next(e for e in doc["traceEvents"] if e["ph"] == "i")
    assert inst["tid"] == 3 and inst["s"] == "t"            # tid = rid + 1
    stripped = tr.to_chrome_trace(strip_wall=True)
    assert all("wall_s" not in e["args"]
               for e in stripped["traceEvents"] if e["ph"] == "X")


# -------------------------------------------------------------- phase timer
def test_phase_timer_accumulates_and_traces():
    st = {}
    tr = telemetry.Tracer()
    with telemetry.phase_timer(st, "prefill_s", tracer=tr, name="prefill",
                               start=8.0, slot=2) as ph:
        ph.note(prompts=3)
    with telemetry.phase_timer(st, "prefill_s"):
        pass
    assert st["prefill_s"] > 0.0
    assert len(tr.events) == 1
    e = tr.events[0]
    assert e.name == "prefill" and e.clock == 8.0 and e.slot == 2
    assert e.args == {"prompts": 3} and e.wall_s is not None


def test_phase_timer_ready_blocks_device_values():
    class FakeDeviceArray:
        def __init__(self):
            self.blocked = False

        def block_until_ready(self):
            self.blocked = True

    x = FakeDeviceArray()
    with telemetry.phase_timer(None, None) as ph:
        assert ph.ready(x) is x
    assert x.blocked


# ---------------------------------------------------------------- heartbeat
def test_heartbeat_record_schema_and_injection():
    rec = telemetry.heartbeat_record(7, wall_time=100.0, mono_s=42.0,
                                     restarts=2, extra_key="v")
    assert rec == {"schema": telemetry.HEARTBEAT_SCHEMA, "step": 7,
                   "wall_time": 100.0, "mono_s": 42.0, "restarts": 2,
                   "extra_key": "v"}
    # clocks default to real readings when not injected
    live = telemetry.heartbeat_record(0)
    assert live["wall_time"] > 0 and live["mono_s"] > 0


def test_supervisor_heartbeat_uses_shared_schema(tmp_path):
    hb = tmp_path / "hb.json"
    sup = Supervisor(
        FaultToleranceConfig(checkpoint_dir=str(tmp_path / "ckpt"),
                             checkpoint_every=100,
                             heartbeat_path=str(hb)),
        step_fn=lambda state, batch: (state + 1, {"loss": 0.0}),
        data_fn=lambda step: step,
        init_state_fn=lambda: 0)
    sup.wall_clock = lambda: 1234.5          # injectable — deterministic
    sup.mono_clock = lambda: 11.25
    sup.run(num_steps=3)
    rec = json.loads(hb.read_text())
    assert rec == {"schema": telemetry.HEARTBEAT_SCHEMA, "step": 2,
                   "wall_time": 1234.5, "mono_s": 11.25, "restarts": 0}


# ----------------------------------------------------------------- registry
def test_metric_key_set_is_frozen():
    """THE pinned key set: this test fails when a metric is added or removed
    without updating telemetry.*_KEYS (and DESIGN.md §15) deliberately."""
    m = telemetry.MetricsRegistry()
    snap = m.snapshot()
    assert snap.key_set() == telemetry.METRIC_KEYS
    assert len(telemetry.COUNTER_KEYS) == 33
    assert len(telemetry.GAUGE_KEYS) == 12
    assert len(telemetry.HISTOGRAM_KEYS) == 5
    # mesh-sharded serving (ISSUE 10): the shard/collective keys are part
    # of the frozen schema — an undeclared shard metric must fail loudly
    # (test_registry_rejects_undeclared_names), not silently appear
    assert "collective_ops" in telemetry.COUNTER_KEYS
    assert "collective_allgather_bytes" in telemetry.COUNTER_KEYS
    assert "attn_blocks_real" in telemetry.COUNTER_KEYS
    assert "attn_blocks_grid" in telemetry.COUNTER_KEYS
    assert "shard_pages_used_max" in telemetry.GAUGE_KEYS
    assert "shard_pages_used_min" in telemetry.GAUGE_KEYS
    assert "shard_lockstep_divergence" in telemetry.GAUGE_KEYS
    assert "collective" in telemetry.CATEGORIES
    assert telemetry.TENANT_COUNTER_KEYS == ("ok_requests", "ok_tokens")
    assert telemetry.TENANT_HISTOGRAM_KEYS == ("admission_wait_steps",)


def test_registry_rejects_undeclared_names():
    m = telemetry.MetricsRegistry()
    with pytest.raises(KeyError, match="undeclared counter"):
        m.count("made_up_counter")
    with pytest.raises(KeyError, match="undeclared gauge"):
        m.gauge("made_up_gauge", 1.0)
    with pytest.raises(KeyError, match="undeclared histogram"):
        m.observe("made_up_hist", 1.0)
    with pytest.raises(KeyError, match="undeclared tenant counter"):
        m.tenant_count("t0", "made_up")
    with pytest.raises(KeyError, match="undeclared tenant histogram"):
        m.tenant_observe("t0", "made_up", 1.0)
    # shard metrics are declared-or-die like everything else (ISSUE 10)
    with pytest.raises(KeyError, match="undeclared counter"):
        m.count("collective_psum_bytes")
    with pytest.raises(KeyError, match="undeclared gauge"):
        m.gauge("shard_pages_used_mean", 1.0)


def test_registry_windows_and_snapshot():
    m = telemetry.MetricsRegistry()
    m.count("decode_chunks")
    m.count("tokens_emitted", 5)
    m.gauge("active_rows", 3)
    m.gauge("resident_tokens", 24)
    m.end_window(4.0, slot=0)
    m.observe("admission_wait_steps", 2.0)
    snap = m.snapshot()
    assert snap.counters["tokens_emitted"] == 5
    assert snap.gauges["clock"] == 4.0
    assert snap.histograms["admission_wait_steps"]["count"] == 1
    assert m.windows == [{"clock": 4.0, "slot": 0,
                          **{k: m.gauges[k] for k in telemetry.GAUGE_KEYS}}]
    assert json.dumps(snap.as_dict())        # JSON-serializable
    m.reset()
    assert m.windows == [] and m.snapshot().counters["tokens_emitted"] == 0


def test_tenant_summary_percentiles():
    m = telemetry.MetricsRegistry()
    for i in range(100):
        m.tenant_observe("t0", "admission_wait_steps", float(i + 1))
    m.tenant_count("t0", "ok_requests", 3)
    m.tenant_count("t0", "ok_tokens", 90)
    m.tenant_count("t1", "ok_requests")
    s = m.tenant_summary()
    assert sorted(s) == ["t0", "t1"]
    assert s["t0"]["admission_wait_p50_steps"] == 50.0     # nearest rank
    assert s["t0"]["admission_wait_p99_steps"] == 99.0
    assert s["t0"]["goodput_tokens"] == 90
    assert s["t1"]["ok_requests"] == 1
    assert s["t1"]["admission_wait_p50_steps"] == 0.0


# ---------------------------------------------------------- drift detection
def _fill_windows(m, plan, resident_per_row, active_rows, n=4):
    for i in range(n):
        m.gauge("active_rows", active_rows)
        m.gauge("resident_tokens", resident_per_row * active_rows)
        m.end_window(float((i + 1) * plan.sync_every))


def test_drift_clean_when_measurements_match_plan(model):
    cfg, _ = model
    plan = _plan(cfg, mean=10)
    attn = next(d for d in plan.decisions if d.name == "attention")
    expected = attn.numbers["expected_resident_tokens"]
    m = telemetry.MetricsRegistry()
    _fill_windows(m, plan, resident_per_row=expected, active_rows=plan.rows)
    for _ in range(4):
        m.observe("finished_len_tokens", 10.0)
    m.count("prefill_real_tokens", 64)
    m.count("prefill_padded_tokens", 80)     # pad ratio 1.25 < pow2 bound 2
    rep = telemetry.detect_drift(plan, m)
    assert rep.windows == 4 and len(rep.findings) >= 4
    assert rep.clean, rep.render()
    assert {f.decision for f in rep.findings} >= {
        "attention", "capacity", "kv_quant", "mlp", "prefill"}


def test_drift_confirms_mispredicted_occupancy(model):
    """The tentpole acceptance scenario: the plan provisioned for mean
    length 40 but requests finish at ~10 tokens — the report must name the
    attention (paging) decision as divergent."""
    cfg, _ = model
    plan = _plan(cfg, mean=40)
    assert plan.paged                        # drift's richest comparison set
    m = telemetry.MetricsRegistry()
    _fill_windows(m, plan, resident_per_row=12, active_rows=plan.rows)
    for _ in range(4):
        m.observe("finished_len_tokens", 10.0)
    rep = telemetry.detect_drift(plan, m)
    confirmed = {f"{f.decision}.{f.metric}" for f in rep.confirmed}
    assert "attention.resident_tokens_per_row" in confirmed, rep.render()
    assert "capacity.mean_finished_len" in confirmed
    f = rep.for_decision("attention")[0]
    assert f.confirmed and f.ratio < 1.0 / (1.0 + f.threshold)
    assert "CONFIRMED" in f.render()
    assert rep.summary()["confirmed"]


def test_drift_confirms_forced_requant_under_fp_plan(model):
    cfg, _ = model
    plan = _plan(cfg, mean=10)
    kv = next(d for d in plan.decisions if d.name == "kv_quant")
    assert kv.choice == "fp"                 # small pool resolves fp pages
    m = telemetry.MetricsRegistry()
    m.count("requant_events")                # measured forced degrade rung
    rep = telemetry.detect_drift(plan, m)
    assert any(f.decision == "kv_quant" and f.metric == "requant_events"
               and f.confirmed for f in rep.findings)


def test_explain_renders_drift_lines(model):
    cfg, _ = model
    plan = _plan(cfg, mean=40)
    m = telemetry.MetricsRegistry()
    _fill_windows(m, plan, resident_per_row=12, active_rows=plan.rows)
    rep = telemetry.detect_drift(plan, m)
    text = plan.explain(drift=rep)
    assert "drift: [CONFIRMED] attention.resident_tokens_per_row" in text \
        or "[CONFIRMED] attention.resident_tokens_per_row" in text
    assert "CONFIRMED" in text.rsplit("drift:", 1)[-1]
    assert "drift:" not in plan.explain()    # no report, no drift lines


# --------------------------------------------------- end-to-end determinism
def _run_llm(model, chaos=None, **llm_kw):
    cfg, params = model
    llm = LLM(cfg, params, _plan(cfg), eos_id=-1, **llm_kw)
    llm.stream(_reqs(), chaos=chaos)
    return llm


def test_scheduler_trace_deterministic_same_seed(model):
    sigs, traces = [], []
    for _ in range(2):
        llm = _run_llm(model, chaos=ChaosConfig(
            seed=7, ensure_fail_rate=0.3, step_fail_chunks=(1,),
            nan_rids={2: (1,)}))
        tr = llm.telemetry().tracer
        assert tr.events, "run recorded no spans"
        sigs.append(tr.signature())
        traces.append(json.dumps(tr.to_chrome_trace(strip_wall=True),
                                 sort_keys=True))
    assert sigs[0] == sigs[1]
    assert traces[0] == traces[1]            # byte-identical once stripped


@pytest.mark.chaos
def test_replica_chaos_trace_deterministic_same_seed(model):
    traces = []
    for _ in range(2):
        llm = _run_llm(model, replicas=3,
                       chaos=ReplicaChaosConfig(kill_at_step={1: 4.0}))
        tr = llm.telemetry().tracer
        cats = {e.cat for e in tr.events}
        assert {"request", "phase", "window"} <= cats
        traces.append(json.dumps(tr.to_chrome_trace(strip_wall=True),
                                 sort_keys=True))
    assert traces[0] == traces[1]


def test_scheduler_run_populates_metrics_and_drift(model):
    llm = _run_llm(model)
    tel = llm.telemetry()
    snap = tel.metrics.snapshot()
    assert snap.key_set() == telemetry.METRIC_KEYS
    assert snap.counters["requests_queued"] == 6
    assert snap.counters["requests_admitted"] == 6
    assert snap.counters["ok"] == 6
    assert snap.counters["tokens_emitted"] >= 6
    assert snap.counters["decode_chunks"] >= 1
    assert snap.histograms["admission_wait_steps"]["count"] == 6
    assert tel.metrics.windows, "no per-window gauge history"
    # per-tenant goodput/wait percentiles (requests alternate t0/t1)
    tenants = tel.metrics.tenant_summary()
    assert sorted(tenants) == ["t0", "t1"]
    assert all(t["goodput_tokens"] > 0 for t in tenants.values())
    # end-of-run drift report reached phase_stats and the bundle
    assert tel.last_drift is not None
    assert llm.phase_stats["drift"] == tel.last_drift.summary()


def test_engine_generate_records_phase_spans(model):
    cfg, params = model
    llm = LLM(cfg, params, _plan(cfg), eos_id=-1)
    llm.generate([([3, 5, 7], 4), ([4, 5], 4)])
    names = [e.name for e in llm.telemetry().tracer.events]
    assert "prefill" in names and "decode_chunk" in names
    st = llm.phase_stats
    assert st["prefill_s"] > 0 and st["decode_s"] > 0


def test_trace_false_keeps_metrics_drops_spans(model):
    llm = _run_llm(model, trace=False)
    tel = llm.telemetry()
    assert tel.tracer.events == []
    assert tel.metrics.snapshot().counters["ok"] == 6


def test_shared_telemetry_bundle_resets_per_call(model):
    cfg, params = model
    tel = telemetry.Telemetry()
    llm = LLM(cfg, params, _plan(cfg), eos_id=-1, trace=tel)
    llm.stream(_reqs(n=3))
    first = tel.tracer.signature()
    llm.stream(_reqs(n=3))
    assert tel.tracer.signature() == first   # reset, not appended


def test_scheduler_shared_bundle_not_reset_by_scheduler(model):
    """A scheduler handed a shared bundle (replica mode) must not clear the
    fleet's events at its own run start — only an owned bundle resets."""
    cfg, params = model
    tel = telemetry.Telemetry()
    tel.tracer.event("dispatch", 0.0, cat="window")
    sched = ContinuousBatchingScheduler(
        cfg, params, _plan(cfg), eos_id=-1, telemetry=tel, slot=0)
    sched.run(_reqs(n=2))
    assert tel.tracer.events[0].name == "dispatch"
    assert "drift" not in sched.phase_stats  # fleet computes drift once
