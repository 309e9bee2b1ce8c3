"""The comparison that decides ``correct``, on a cell the CPU can hold: a
sound run passes; a run with a fault planted underneath the timed path
fails; the lower-precision control reads above the limit on the same
served tokens. Also: without a TPU, ``run.py`` exits non-zero with no
result line."""
import json
import os
import subprocess
import sys

import pytest

import bench_tiny

pytest.importorskip("jax")


def test_sound_run_is_correct():
    res = bench_tiny.run(2**40 + 17)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert res["checks"]["unchecked"]["value"] == 0
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "ttft_p90_ms", "tpot_p90_ms"}


def _alter_token(sched):
    """A served token altered where the decode chunk produces it (the
    chunk's own state carries on unaltered)."""
    chunk = sched._chunk

    def bad(params, state, rng, bt):
        state, toks, emits = chunk(params, state, rng, bt)
        return state, (toks + 1) % sched.cfg.vocab_size, emits
    sched._chunk = bad


def _skip_prefill(sched):
    """Admission's prefill returns the state it was given."""
    sched._refill = lambda params, state, *a: state


def _refuse_some(sched):
    """Every fourth request is refused as it is sent: it gets its outcome
    and never a token."""
    import types
    inject = sched.inject

    def bad(streams):
        keep = []
        for r in streams:
            if r.rid % 4 == 3:
                r.on_outcome(r, types.SimpleNamespace(status="refused",
                                                      degraded=()))
            else:
                keep.append(r)
        return inject(keep)
    sched.inject = bad


@pytest.mark.parametrize("fault", [_alter_token, _skip_prefill, _refuse_some],
                         ids=["token_altered", "prefill_skipped",
                              "request_refused"])
def test_fault_is_not_correct(fault):
    res = bench_tiny.run(2**33 + 5, patch=fault)
    assert not res["correct"], res["checks"]


def test_unanswered_requests_wait_to_the_end():
    """A request with no token counts in the TTFT with its whole wait, to
    the run's end, not with the time it was refused."""
    from benchlib import readers, serve
    reqs = [serve.RunReq(0, None, 4, due=1.0, tag="t", first=1.5),
            serve.RunReq(1, None, 4, due=2.0, tag="t", finish=2.1,
                         status="refused"),
            serve.RunReq(2, None, 4, due=3.0, tag="t")]
    rec = readers.Record(window=(0.0, 10.0), end=20.0, reqs=reqs,
                         boundaries=[], counted=reqs, dims=None, rows=1,
                         page_size=16, kv_bytes=1, kind="TPU v5 lite")
    assert readers.ttft_s(rec) == pytest.approx([0.5, 18.0, 17.0])


def test_control_is_not_correct():
    """The reference one precision step down (int8 matrix products, int4
    keys and values), read at the positions of the program's own served
    tokens, lies further from the reference than the limit allows."""
    import run as bench_run
    seen = {}

    def inspect(sample, params, block, dims, cfile):
        seen["program"] = bench_run.check_outputs(
            block, sample, params, dims, cfile["eos_token_id"],
            cfile["check"]["logit_gap"])
        seen["control"] = bench_run.check_outputs(
            block, sample, params, dims, cfile["eos_token_id"],
            cfile["check"]["logit_gap"], quant="control")
    res = bench_tiny.run(2**35 + 3, inspect=inspect, seconds=2.0)
    assert res["correct"], res["checks"]
    gap, limit = seen["control"]["logit_gap"]
    assert gap > limit >= seen["program"]["logit_gap"][0]
    assert bench_run.verdict(seen["program"])
    assert not bench_run.verdict(seen["control"])


def test_no_tpu_no_result(tmp_path):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(root, "run.py"), "--workload",
         "qwen2.5-3b.chat-paced", "--seed", str(2**40), "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=300, cwd=tmp_path)
    assert p.returncode != 0
    for line in p.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)
