"""The serving path as the profiler sees it: the scheduler's ``serve.*``
host spans nest and cover its boundary, each request carries ordered
wall-clock stamps, and the device programs keep the names the benchmark's
trace reduction looks for (``jit_chunk``, ``jit_refill``)."""
import glob
import os
import re
import time

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.core import plan as plan_lib
from repro.models import transformer as tfm
from repro.serve import LLM
from repro.serve.guard import GuardConfig
from repro.serve.scheduler import ContinuousBatchingScheduler, StreamRequest

MS = 1_000_000                        # nanoseconds
CHILDREN = ("serve.admit", "serve.prefill", "serve.cow", "serve.decode_chunk",
            "serve.harvest", "serve.audit")


@pytest.fixture(scope="module")
def model():
    cfg = get_config("qwen2.5-3b-reduced")
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _plan(cfg, rows=3, num_pages=24):
    return plan_lib.plan_for_scheduler(cfg, rows=rows, cache_len=64,
                                       page_size=4, num_pages=num_pages,
                                       share_prefix=True, sync_every=4)


def _host_spans(log_dir, prefix="serve."):
    """(name, start_ns, end_ns) of the host spans under ``prefix`` in the
    xplane file ``jax.profiler`` wrote into ``log_dir``."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(prefix):
                        s = int(ev.start_ns)
                        out.append((ev.name, s, s + int(ev.duration_ns)))
    return out


def _within(inner, outers):
    return any(a <= inner[1] and inner[2] <= b for _, a, b in outers)


def test_boundary_spans_nest_and_cover_the_boundary(model, tmp_path):
    """Driven as the benchmark drives it (``start_gen``, ``inject``, one
    tick a boundary) under a profiler trace: every child span lies in a
    ``serve.boundary``, ``serve.decode_wait`` in ``serve.decode_chunk``,
    ``serve.inject`` outside the boundaries; and no stretch of a boundary
    that its children leave uncovered is longer than the loop control. A
    slow ``on_token`` makes the harvest long enough that an unspanned
    harvest would show."""
    cfg, params = model
    plan = _plan(cfg)
    sched = ContinuousBatchingScheduler(
        cfg, params, plan, eos_id=-1,
        guard=GuardConfig(audit_every_sync=True))
    slow = lambda r, tok: time.sleep(0.003)            # noqa: E731
    reqs = [StreamRequest(rid=i, prompt=[3, 5, 7, 9, 11, i + 2], max_new=6,
                          on_token=slow) for i in range(4)]
    with jax.profiler.trace(str(tmp_path)):
        gen = sched.start_gen([])
        sched.inject(reqs)
        with plan_lib.activate(plan):
            status = gen.send(("tick", None))
            while not status["drained"]:
                status = gen.send(("tick", None))
        gen.close()
    spans = _host_spans(str(tmp_path))
    names = {n for n, _, _ in spans}
    assert set(CHILDREN) | {"serve.boundary", "serve.inject",
                            "serve.decode_wait"} <= names, names
    bounds = [s for s in spans if s[0] == "serve.boundary"]
    for s in spans:
        if s[0] in CHILDREN:
            assert _within(s, bounds), s
        elif s[0] == "serve.decode_wait":
            assert _within(s, [c for c in spans
                               if c[0] == "serve.decode_chunk"]), s
        elif s[0] == "serve.inject":
            assert not any(a < s[2] and s[1] < b for _, a, b in bounds), s
    assert max(e - s for n, s, e in spans if n == "serve.harvest") > 10 * MS
    for _, a, b in bounds:
        kids = sorted((s, e) for n, s, e in spans
                      if n in CHILDREN and a <= s and e <= b)
        t, free = a, 0
        for s, e in kids:
            free = max(free, s - t)
            t = max(t, e)
        free = max(free, b - t)
        assert free < 5 * MS, (free, kids)


def _pressure(cfg, params):
    """A pool too small for all four rows at once: requests are preempted
    and resumed, every one finishes ``ok``."""
    llm = LLM(cfg, params, _plan(cfg, num_pages=6), eos_id=-1,
              guard=GuardConfig(degrade_rungs=("shed",), shed_pressure=2.0))
    done = llm.stream([StreamRequest(rid=i, prompt=[3 + i, 5, 7],
                                     max_new=12) for i in range(4)])
    assert any(r.preemptions for r in done)
    assert all(r.outcome.ok for r in done)
    return done


def _refused(cfg, params):
    """One request shed at arrival, one expired before admission."""
    llm = LLM(cfg, params, _plan(cfg), eos_id=-1,
              guard=GuardConfig(degrade_rungs=("shed",), shed_pressure=0.01))
    reqs = [StreamRequest(rid=i, prompt=[3 + i, 5, 7], max_new=16)
            for i in range(3)]
    reqs.append(StreamRequest(rid=8, prompt=[2, 3], max_new=4, ttl=4.0))
    reqs.append(StreamRequest(rid=9, prompt=[2, 3], max_new=4, arrival=8.0))
    done = llm.stream(reqs)
    refused = {r.rid: r.outcome.status for r in done if not r.outcome.ok}
    assert refused == {8: "expired", 9: "shed"}, refused
    return done


@pytest.mark.parametrize("case", [_pressure, _refused],
                         ids=["ok_and_resumed", "refused"])
def test_request_wall_stamps_are_ordered(model, case):
    """``queued <= admitted <= first_token <= finished`` on the host's
    monotonic clock; a request never admitted has neither of the middle
    two; a served one has all four."""
    done = case(*model)
    for r in done:
        chain = [r.queued_wall, r.admitted_wall, r.first_token_wall,
                 r.finished_wall]
        assert r.queued_wall is not None and r.finished_wall is not None
        if r.admitted_at is None:
            assert r.admitted_wall is None and r.first_token_wall is None
        if r.out:
            assert None not in chain
        stamps = [t for t in chain if t is not None]
        assert stamps == sorted(stamps), (r.rid, chain)


def test_device_programs_keep_their_names(model):
    """The benchmark finds the decode chunk and the prefill on the device
    trace as ``jit_chunk`` and ``jit_refill``: a rename would silently turn
    its decode-step, prefill, MFU and roofline metrics to null."""
    cfg, params = model
    plan = _plan(cfg)
    sched = ContinuousBatchingScheduler(cfg, params, plan, eos_id=-1)
    state = sched._init_state()
    bt = jnp.zeros((plan.rows, plan.max_pages), jnp.int32)
    one = jnp.zeros((1,), jnp.int32)
    with plan_lib.activate(plan):
        chunk = sched._chunk.lower(params, state, jax.random.PRNGKey(0), bt)
        refill = sched._refill.lower(params, state,
                                     jnp.zeros((1, 8), jnp.int32), one + 3,
                                     one, one + 4, bt, one)
    assert re.match(r"module @jit_chunk\b", chunk.as_text())
    assert re.match(r"module @jit_refill\b", refill.as_text())
