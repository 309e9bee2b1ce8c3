"""Serving benchmark: run one cell of ``BENCHMARK.json`` once on the chip.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A cell is a configuration (``bench/configs``) under a traffic mix
(``bench/traffic``). The run makes the weights from the seed on the
device, resolves the serving plan, warms every prefill shape the traffic
can reach, starts the traffic, opens the window once the batch is at its
steady occupancy, measures for ``--seconds``, serves what came due in the
window to its end, and then checks a sample of the served tokens against
the plain float32 reference of the configuration's block
(``bench/blocks/<block>.py``, named by the file's ``"block"``).

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics read from a profiler trace of the
window), ``device``, with ``--trace 1`` a ``breakdown``, and last
``checks``: each number compared with its limit. The checks are also the
last lines of stderr. Without a TPU, or with fewer chips than the cell
asks for, the run exits non-zero and prints no result.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH_DIR))

from benchlib import REPO_ROOT, spec, traffic as traffic_lib  # noqa: E402

SPANS = ("bench.boundary", "bench.inject", "bench.sleep_until_due",
         "bench.harness")
TRACE_DIR = BENCH_DIR / ".runs" / "trace"


def log(*a) -> None:
    print(*a, flush=True)


class CompileClock:
    """Seconds and count of XLA compilations (persistent-cache lookups
    included) and persistent-cache hits, from JAX's monitoring events."""

    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == self.COMPILE:
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        self.cache_hits += event == "/jax/compilation_cache/cache_hits"


def require_devices(chips: int):
    """JAX's first device, when it is a TPU and there are enough of them;
    otherwise exit non-zero with no result."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: JAX's first device is {devs[0].platform!r}"
                         f" ({devs[0].device_kind}), not a TPU: no result")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}: no result")
    return devs[0]


def seed_key(seed: int):
    """A JAX key from any whole seed, however large."""
    import jax
    words = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(words[0])),
                              int(words[1] >> 1))


def resolve_plan(arch, cfile):
    """``plan_serve`` with the configuration's inputs: ``kv_budget_bytes``
    caps the rows, ``num_pages``, where the configuration states it, sizes
    the pool."""
    from repro.core import plan as plan_lib
    p = cfile["plan"]
    plan = plan_lib.plan_serve(
        arch, hbm_budget_bytes=p["kv_budget_bytes"],
        expected_batch=p["expected_batch"],
        expected_len_dist={"mean": p["mean_len"], "max": p["cache_len"]},
        page_size=p["page_size"], kv_quant=p["kv_quant"],
        num_pages=p.get("num_pages"))
    if not (plan.paged and plan.kv_quant == p["kv_quant"]):
        raise RuntimeError(f"the plan resolved attn={plan.attn_path} "
                           f"kv={plan.kv_quant}, not paged {p['kv_quant']}")
    return plan


def warm_groups(plan, tfile, max_batch: int):
    """Boundaries of warm-up requests: every (batch, tier) prefill shape the
    traffic can reach, packed so that one boundary admits whole groups."""
    lo, hi = traffic_lib.prompt_range(tfile)
    _, out_hi = traffic_lib.output_range(tfile)
    top = min(hi + out_hi, plan.cache_len - 1)      # a resumed request
    tiers = sorted({plan.tier(n) for n in range(lo, top + 1)
                    if n in (lo, top) or n == plan.tier(n)})
    pages = int(0.8 * plan.num_pages)
    groups = []
    for b in range(1, max_batch + 1):
        cur, used, rows = [], 0, 0
        for t in tiers:
            plen = min(t, plan.cache_len - 1)
            need = b * -(-(plen + 1) // plan.page_size)
            if cur and (used + need > pages or rows + b > plan.rows):
                groups.append(cur)
                cur, used, rows = [], 0, 0
            cur.append((b, plen))
            used, rows = used + need, rows + b
        groups.append(cur)
    return groups


def pick_sample(done, seed: int, n: int):
    """The ``n`` requests the reference checks: the one with the most served
    tokens, and others drawn from the seed."""
    if not done:
        return []
    done = sorted(done, key=lambda r: r.rid)
    first = max(done, key=lambda r: (len(r.out), -r.rid))
    rest = [r for r in done if r is not first]
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 7]))
    pick = rng.permutation(len(rest))[:max(n - 1, 0)]
    return [first] + [rest[int(i)] for i in sorted(pick)]


def verdict(checks) -> bool:
    """``correct``: every number compared lies within its limit."""
    return all(v <= lim for v, lim in checks.values())


def check_outputs(block, sample, params, dims, eos: int, limit: float,
                  quant=None):
    """Numbers compared, each (value, limit): the widest logit gap of a
    served token below the block's reference's best, answers that stop
    short of their budget without an end token, and tokens outside the
    vocabulary."""
    wts = block.plain_weights(params)
    widest, short, oov, n = 0.0, 0, 0, 0
    for r in sample:
        out = np.asarray(r.out, np.int64)
        oov += int(((out < 0) | (out >= dims.vocab)).sum())
        if len(out) != r.max_new and (len(out) == 0 or out[-1] != eos):
            short += 1
        if len(out) == 0 or oov:
            continue
        g = block.request_gaps(wts, dims, r.prompt, out, quant)
        widest = max(widest, float(g.max()))
        n += len(out)
    return {"logit_gap": (widest, limit), "short_answers": (short, 0),
            "out_of_vocab": (oov, 0), "unchecked": (int(n == 0), 0)}


def run_cell(bench, cell, seed: int, seconds: float, trace: bool, dev, *,
             cfile=None, tfile=None, patch=None, inspect=None,
             bench_dir=BENCH_DIR):
    """One run of ``cell``; returns the result object. ``cfile``/``tfile``
    replace the cell's configuration and traffic files, ``bench_dir`` the
    directory that holds its traffic, blocks and metric readers;
    ``patch(sched)`` may alter the scheduler and
    ``inspect(sample, params, block, dims, cfile)`` sees the checked
    requests (tests and the control's calibration)."""
    import jax
    from benchlib import model, readers, serve, trace as trace_lib
    from repro.serve.guard import GuardConfig
    from repro.serve.scheduler import ContinuousBatchingScheduler

    cfile = cfile or spec.load_config(bench, cell["config"])
    tfile = tfile or spec.load_traffic(cell["traffic"], bench_dir)
    block = spec.block_of(cfile, bench_dir)
    dims = block.Dims.from_file(cfile)
    arch = block.program_config(cfile)
    clock = CompileClock()
    params = jax.block_until_ready(model.make_params(
        arch, seed_key(seed), cfile["initializer_range"]))
    wbytes = sum(int(x.nbytes) for x in jax.tree.leaves(params))
    plan = resolve_plan(arch, cfile)
    log(f"weights: {wbytes} B on {dev.device_kind}; KV budget "
        f"{cfile['plan']['kv_budget_bytes']} B")
    log(f"plan: rows={plan.rows} cache_len={plan.cache_len} "
        f"page_size={plan.page_size} num_pages={plan.num_pages} "
        f"kv_quant={plan.kv_quant} sync_every={plan.sync_every}")
    sched = ContinuousBatchingScheduler(
        arch, params, plan, eos_id=cfile["eos_token_id"], temperature=0.0,
        guard=GuardConfig())
    if patch is not None:
        patch(sched)
    span = (lambda n: jax.profiler.TraceAnnotation(n)) if trace else None
    sess = serve.Session(sched, plan, span=span)
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 3]))
    vocab = dims.vocab

    # ---- warm-up: every prefill shape the traffic can reach -------------
    for group in warm_groups(plan, tfile, tfile["warm_max_batch"]):
        now = time.monotonic()
        sess.inject([(traffic_lib.tokens(rng, plen, vocab), 1, now, "warm")
                     for b, plen in group for _ in range(b)])
        while sess.busy():
            sess.boundary()
    log(f"warm-up: {clock.compiles} compiles, {clock.seconds:.3f} s, "
        f"{clock.cache_hits} persistent-cache hits")

    # ---- traffic: ramp, window, tail -------------------------------------
    closed = tfile["kind"] == "closed"
    gen = traffic_lib.make(tfile, seed, vocab, ramp_s=tfile.get("ramp_s", 0),
                           window_s=seconds)
    t0 = time.monotonic()
    open_t = close_t = drain_t = None
    window_span = None
    compiles_open = compiles_close = None
    clients = gen.clients(plan.rows) if closed else 0
    current = {}                          # client -> its RunReq, or None
    due_c = {}                            # client -> when it sends next
    while True:
        now = time.monotonic()
        with sess.span("bench.harness"):
            if closed:
                for c, rr in current.items():
                    if rr is not None and rr.finish is not None:
                        current[c] = None
                        due_c[c] = rr.finish
                if len(current) < clients:        # start one client a boundary
                    current[len(current)] = None
                    due_c[len(current) - 1] = now
                # clients past the program's queue bound hold their request
                room = tfile.get("max_queued", clients) - (
                    sess.status["waiting"] + sess.status["pending"])
                send = sorted((t, c) for c, t in due_c.items()
                              if t <= now)[:max(room, 0)]
                send = [c for _, c in send]
                items = []
                for c in send:
                    q = gen.next_request(due_c[c])
                    items.append((q.prompt, q.max_new, due_c.pop(c), "c"))
                for c, rr in zip(send, sess.inject(items)):
                    rr.client = c
                    current[c] = rr
            else:
                sess.inject([(q.prompt, q.max_new, t0 + q.due, "t")
                             for q in gen.pop_due(now - t0)])
        if sess.busy():
            sess.boundary()
        else:
            nxt = min(due_c.values(), default=now) if closed \
                else t0 + gen.next_due()
            wait = nxt - time.monotonic()
            if wait > 0:
                with sess.span("bench.sleep_until_due"):
                    time.sleep(wait)
        now = time.monotonic()
        if open_t is None:
            ready = (len(current) == clients and
                     sess.status["active"] >= min(plan.rows, clients)) \
                if closed else now >= t0 + tfile["ramp_s"]
            if ready or now >= t0 + tfile["ramp_limit_s"]:
                if trace:
                    shutil.rmtree(TRACE_DIR, ignore_errors=True)
                    opts = jax.profiler.ProfileOptions()
                    opts.python_tracer_level = 0
                    jax.profiler.start_trace(str(TRACE_DIR),
                                             profiler_options=opts)
                    window_span = jax.profiler.TraceAnnotation("bench.window")
                    window_span.__enter__()
                compiles_open = clock.compiles
                open_t = time.monotonic()
        elif close_t is None and now >= open_t + seconds:
            close_t = time.monotonic()
            compiles_close = clock.compiles
            if trace:
                window_span.__exit__(None, None, None)
                jax.profiler.stop_trace()
                if not closed:
                    # writing the trace stalls the loop for many seconds:
                    # the arrivals it held back would land at once and
                    # crowd the window's last answers, so they are not sent
                    held = gen.pop_due(time.monotonic() - t0)
                    log(f"trace written in {time.monotonic() - close_t:.3f}"
                        f" s; {len(held)} arrivals due meanwhile not sent")
            # the wait for the window's answers starts once the loop runs
            # again, so that writing a trace does not eat into it
            drain_t = time.monotonic()
        if close_t is not None:
            if closed:
                break
            due = [r for r in sess.reqs.values() if r.tag == "t"
                   and open_t <= r.due < close_t]
            if all(r.finish is not None for r in due) or \
                    now > drain_t + tfile["drain_limit_s"]:
                break
    end_t = time.monotonic()
    sess.close()
    mem = dev.memory_stats() or {}
    peak_bytes = mem.get("peak_bytes_in_use")

    # ---- what the window judges ------------------------------------------
    reqs = list(sess.reqs.values())
    if closed:
        counted = [r for r in reqs if r.tag == "c" and r.inject < close_t
                   and (r.finish is None or r.finish >= open_t)]
        finished = [r for r in counted if r.ok
                    and open_t <= r.finish <= close_t]
        failed = sum(r.finish is not None and not r.ok for r in counted)
    else:
        counted = [r for r in reqs if r.tag == "t"
                   and open_t <= r.due < close_t]
        finished = [r for r in counted if r.ok]
        failed = sum(not r.ok for r in counted)
    lag = sorted(r.inject - r.due for r in counted)
    if lag:
        log(f"generator lateness (inject - due), ms over {len(lag)} "
            f"requests: p50 {1e3 * lag[len(lag) // 2]:.3f} "
            f"max {1e3 * lag[-1]:.3f}")
    log(f"compiles in the window: {compiles_close - compiles_open}")
    at_open = [b for b in sess.boundaries if b.t1 <= open_t][-1:]
    at_close = [b for b in sess.boundaries if b.t1 <= close_t][-1:]
    third = [r.admit - r.due for r in counted if r.admit is not None]
    k = max(1, len(third) // 3)
    if at_open and at_close and third:
        log(f"backlog (waiting + pending): {at_open[0].status['waiting'] + at_open[0].status['pending']} at open, "
            f"{at_close[0].status['waiting'] + at_close[0].status['pending']} at close; queue wait, ms, mean of "
            f"first third {1e3 * np.mean(third[:k]):.1f}, last third {1e3 * np.mean(third[-k:]):.1f}")
    log(f"window {close_t - open_t:.3f} s; set-up {open_t - T_START:.3f} s;"
        f" {len(counted)} requests counted, {failed} failed")

    kind = dev.device_kind
    rec = readers.Record(
        window=(open_t, close_t), end=end_t, reqs=reqs,
        boundaries=sess.boundaries,
        counted=counted, dims=dims, rows=plan.rows,
        page_size=plan.page_size, kv_bytes=1 if plan.kv_quant == "int8"
        else 2, kind=kind)
    device = {"platform": dev.platform, "kind": kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak_bytes}
    result = {"correct": False, "attempted": len(counted), "failed": failed}
    if trace:
        tr = trace_lib.load(trace_lib.find_xplane(str(TRACE_DIR)))
        rec.trace = tr
        rec.trace_window = trace_lib.span_window(tr, "bench.window")
        device["busy_s"] = trace_lib.busy_s(tr, rec.trace_window)
        device["window_s"] = (rec.trace_window[1] - rec.trace_window[0]) / 1e9
        result["breakdown"] = {
            "device_ops": trace_lib.top_ops(
                trace_lib.in_window(tr.ops, rec.trace_window)),
            "idle_gaps": trace_lib.gaps(tr, rec.trace_window, SPANS)}
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        names = [m["name"] for m in spec.per_layer_for(bench, cell["name"])]
    else:
        names = [m["name"] for m in spec.end_to_end_for(bench, cell["name"])]
    units = {m["name"]: m["unit"]
             for m in bench["end_to_end"] + bench["per_layer"]}
    metrics = {}
    e2e = {
        "setup_s": lambda: open_t - T_START,
        "ttft_p90_ms": lambda: _ms(readers.p90(readers.ttft_s(rec))),
        "tpot_p90_ms": lambda: _ms(readers.p90(readers.tpot_s(rec))),
        "output_tokens_per_s": lambda: readers.window_tokens(rec)
        / (close_t - open_t),
    }
    for name in names:
        value = e2e[name]() if name in e2e \
            else spec.load_reader(name, bench_dir)(rec)
        if value is not None:
            metrics[name] = {"value": float(value), "unit": units[name]}
    for note in rec.notes:
        log(note)

    # ---- correctness: the served tokens against the reference ------------
    del sess, sched
    sample = pick_sample(finished, seed, tfile["check_requests"])
    log(f"reference: {len(sample)} requests, "
        f"{sum(len(r.out) for r in sample)} served tokens")
    if inspect is not None:
        inspect(sample, params, block, dims, cfile)
    t_ref = time.monotonic()
    checks = check_outputs(block, sample, params, dims,
                           cfile["eos_token_id"], cfile["check"]["logit_gap"])
    # a request refused, cut by the guard or never answered is a wrong answer
    checks["failed"] = (failed, 0)
    log(f"reference seconds: {time.monotonic() - t_ref:.3f}")
    result["correct"] = verdict(checks)
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        print(f"check {k}: {v!r} limit {lim!r}", file=sys.stderr, flush=True)
    # the last key of the result is the checks
    result = {k: result[k] for k in ("correct", "attempted", "failed",
                                     "metrics", "device", "breakdown",
                                     "checks") if k in result}
    return result


def _ms(v):
    return None if v is None else 1e3 * v


def enable_cache() -> None:
    """JAX's persistent compile cache at the checkout's fixed path (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), for every program however quick
    to compile, so that only a checkout's first run compiles."""
    import jax
    from repro.launch import compile_cache
    cache = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    log(f"compile cache: {cache}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = spec.load_benchmark(REPO_ROOT)
    cell = spec.workload(bench, args.workload)
    dev = require_devices(cell["chips"])
    enable_cache()
    import jax
    log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}")
    result = run_cell(bench, cell, args.seed, args.seconds,
                      bool(args.trace), dev)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
