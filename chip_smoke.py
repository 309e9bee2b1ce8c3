"""Bring-up check: serve qwen2.5-3b at its published width on one TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # tp=2 against tp=1 on a four-chip host

The default run drives the serving path a user calls, once, at full width
(36 layers, d_model 2048, GQA 16/2, head_dim 128, d_ff 11008, vocab 151936)
with random bf16 weights made from ``--seed``:

1. ``plan_serve`` resolves a plan against the device's own memory: 16 rows,
   cache_len 4096 and 64-token pages, which selects the int8 page pool.
2. ``repro.serve.LLM.stream`` serves 24 generated requests twice: prompts of
   257-512 tokens (one prefill tier), half of them behind one shared
   256-token prefix (copy-on-write page sharing), max_new 16-64. The first
   pass compiles; the second is timed as serving and must repeat the first
   pass token for token.
3. The compiled decode chunk must hold Mosaic kernels (``tpu_custom_call``),
   and ``paged_attention`` at these widths, fp and int8, must match
   ``kernels.ref.paged_attention_ref``.

``--four-chips`` runs only ``LLM.stream`` with ``mesh="tp=2"`` against
``tp=1`` (qwen's two KV heads cap tp at 2), token for token, and prints each
device's ``bytes_in_use``.

Any failed check exits non-zero without a result line. Otherwise the last
line of stdout is ``{"ok": true, "device": {"platform": "tpu", "kind": ...,
"count": ...}}``. JAX's persistent compile cache is the directory
``JAX_COMPILATION_CACHE_DIR`` names, else ``.jax_cache/`` in the checkout.
"""
from __future__ import annotations

import argparse
import collections
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen2.5-3b"
ROWS = 16
CACHE_LEN = 4096
PAGE_SIZE = 64
N_REQUESTS = 24
SHARED_PREFIX = 256
MAX_PROMPT = 512
# the second cohort arrives (virtual decode steps) after the first has
# drained, so each cohort is admitted as one prefill batch: two compiles
LATE_ARRIVAL = 96.0
# paged_attention against the oracle, both under highest matmul precision:
# the kernel accumulates in fp32 but TPU matmuls of fp32 operands may still
# round them to bf16 (relative 2^-9), which moves unit-scale logits by about
# 4e-3 and the softmax-weighted unit-scale values by well under 1e-2
KERNEL_ATOL = 1e-2
KERNEL_RTOL = 1e-2


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAIL: {msg}")


def require_tpu():
    import jax
    dev = jax.devices()[0]
    check(dev.platform == "tpu",
          f"JAX's first device is {dev.platform!r} ({dev.device_kind}), not "
          "a TPU: this check runs only on the chip, and nothing falls back "
          "to the CPU")
    return dev


class CompileClock:
    """Seconds of XLA compilation (persistent-cache lookups included, which
    is the time that cache saves) and of tracing and lowering to MLIR, read
    from JAX's monitoring events."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    TRACE = ("/jax/core/compile/jaxpr_trace_duration",
             "/jax/core/compile/jaxpr_to_mlir_module_duration")

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.trace_seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == self.COMPILE:
            self.seconds += duration
            self.compiles += 1
        elif event in self.TRACE:
            self.trace_seconds += duration

    def _event(self, event, **_):
        self.cache_hits += event == "/jax/compilation_cache/cache_hits"


def make_requests(seed: int, vocab: int):
    """``N_REQUESTS`` requests from ``seed``; the first ``ROWS`` arrive at
    once, the rest at ``LATE_ARRIVAL``. Even rids share one
    ``SHARED_PREFIX``-token prefix, and rid 2 repeats rid 0 exactly, so its
    partly filled tail page is adopted and copied on the first decode
    append. Prompt lengths stay in (SHARED_PREFIX, MAX_PROMPT], one
    power-of-two prefill tier."""
    rng = np.random.default_rng(seed)
    prefix = rng.integers(2, vocab, SHARED_PREFIX).tolist()
    reqs = []
    for i in range(N_REQUESTS):
        if i % 2 == 0:
            tail = rng.integers(2, vocab, rng.integers(
                1, MAX_PROMPT - SHARED_PREFIX + 1)).tolist()
            prompt = prefix + tail
        else:
            prompt = rng.integers(2, vocab, rng.integers(
                SHARED_PREFIX + 1, MAX_PROMPT + 1)).tolist()
        reqs.append({"prompt": prompt,
                     "max_new": int(rng.integers(16, 65)),
                     "arrival": 0.0 if i < ROWS else LATE_ARRIVAL})
    reqs[2]["prompt"] = list(reqs[0]["prompt"])
    return reqs


def resolve_plan(cfg, dev, param_bytes: int, mesh=None):
    """The serving plan against the device's own memory: half of what the
    weights leave is the KV budget, the other half stays for activations
    and compiled programs. The pool holds rows of a quarter cache_len,
    more than the longest request, so the guard's pressure rungs (clamp,
    shed) stay out of the way."""
    from repro.core import plan as plan_lib
    limit = dev.memory_stats()["bytes_limit"]
    return plan_lib.plan_serve(
        cfg, hbm_budget_bytes=(limit - param_bytes) // 2,
        expected_batch=ROWS,
        expected_len_dist={"mean": CACHE_LEN // 4, "max": CACHE_LEN},
        page_size=PAGE_SIZE, mesh=mesh)


def serve(llm, reqs, seed: int):
    """One ``LLM.stream`` pass: wall seconds and the finished requests."""
    import jax
    t0 = time.perf_counter()
    done = llm.stream([dict(r) for r in reqs], rng=jax.random.PRNGKey(seed))
    return time.perf_counter() - t0, done


def check_outputs(done, vocab: int, label: str) -> int:
    """Every outcome ok and every token in vocab; returns tokens served."""
    counts = collections.Counter(r.outcome.status if r.outcome else "none"
                                 for r in done)
    print(f"{label}: outcomes {dict(counts)}")
    bad = [(r.rid, r.outcome) for r in done
           if r.outcome is None or not r.outcome.ok]
    check(not bad, f"{label}: requests not ok: {bad}")
    tokens = [t for r in done for t in r.out]
    check(all(0 <= int(t) < vocab for t in tokens),
          f"{label}: token outside the vocabulary [0, {vocab})")
    return len(tokens)


def decode_chunk_hlo(llm, params, plan) -> str:
    """Compiled HLO of the scheduler's decode chunk at the served shapes."""
    import jax
    import jax.numpy as jnp
    from repro.core import plan as plan_lib
    sch = llm._scheduler
    state = jax.eval_shape(sch._init_state)
    bt = jax.ShapeDtypeStruct((plan.rows, plan.max_pages), jnp.int32)
    with plan_lib.activate(plan):
        return sch._chunk.lower(params, state, jax.random.PRNGKey(0),
                                bt).compile().as_text()


def kernel_vs_oracle(cfg, seed: int):
    """Compiled paged_attention (fp and int8 pools) against the
    gather-then-softmax oracle at the config's widths; returns the largest
    absolute error of each."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref
    rng = np.random.default_rng(seed + 1)
    rows, cache_len, page_size = ROWS, CACHE_LEN, PAGE_SIZE
    KV, H, D = cfg.num_kv_heads, cfg.num_heads, cfg.head_dim
    MP = cache_len // page_size
    lengths = rng.integers(1, cache_len + 1, rows).astype(np.int32)
    lengths[0], lengths[-1] = 1, cache_len
    pages = -(-lengths // page_size)
    P = int(pages.sum()) + 1
    perm = rng.permutation(P)
    bt = np.full((rows, MP), -1, np.int32)
    i = 0
    for b, n in enumerate(pages):
        bt[b, :n] = perm[i:i + n]
        i += n
    q = jnp.asarray(rng.standard_normal((rows, KV, H // KV, D)), jnp.float32)
    bt, lens = jnp.asarray(bt), jnp.asarray(lengths)
    shape = (P, page_size, KV, D)
    cases = {
        "fp": dict(k=jnp.asarray(rng.standard_normal(shape), jnp.bfloat16),
                   v=jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)),
        "int8": dict(k=jnp.asarray(rng.integers(-127, 128, shape), jnp.int8),
                     v=jnp.asarray(rng.integers(-127, 128, shape), jnp.int8),
                     k_scale=jnp.asarray(rng.uniform(0.5, 2.0, (P, KV)),
                                         jnp.float32),
                     v_scale=jnp.asarray(rng.uniform(0.5, 2.0, (P, KV)),
                                         jnp.float32)),
    }
    errs = {}
    for name, c in cases.items():
        scales = {k: c[k] for k in ("k_scale", "v_scale") if k in c}
        with jax.default_matmul_precision("highest"):
            out = jax.jit(lambda q, k, v, bt, n, s: ops.paged_attention(
                q.reshape(rows, 1, H, D), k, v, bt, n, **s))(
                    q, c["k"], c["v"], bt, lens, scales)
            want = jax.jit(lambda q, k, v, bt, n, s: ref.paged_attention_ref(
                q, k, v, bt, n, **s))(q, c["k"], c["v"], bt, lens, scales)
        out = np.asarray(out).reshape(np.shape(want))
        want = np.asarray(want)
        check(np.isfinite(out).all(), f"paged_attention {name}: non-finite")
        errs[name] = float(np.abs(out - want).max())
        check(np.allclose(out, want, atol=KERNEL_ATOL, rtol=KERNEL_RTOL),
              f"paged_attention {name} differs from the oracle by "
              f"{errs[name]:.3g} (atol {KERNEL_ATOL}, rtol {KERNEL_RTOL})")
    return errs


def run_one_chip(cfg, params, dev, seed: int, clock: CompileClock) -> None:
    import jax
    from repro.kernels import ops
    from repro.serve import LLM
    from repro.serve.guard import GuardConfig

    param_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    plan = resolve_plan(cfg, dev, param_bytes)
    weight_bytes = {d.name: d for d in plan.decisions}["kv_quant"] \
        .numbers["weight_stream_bytes"]
    print(f"parameter bytes on device: {param_bytes} "
          f"(plan counts {weight_bytes})")
    check(param_bytes == weight_bytes,
          "the plan counts other weight bytes than the params hold")
    print(f"plan: rows={plan.rows} cache_len={plan.cache_len} "
          f"page_size={plan.page_size} num_pages={plan.num_pages} "
          f"attn={plan.attn_path} kv_quant={plan.kv_quant}")
    check(plan.rows == ROWS and plan.paged and plan.kv_quant == "int8",
          "the plan did not resolve 16 paged rows with int8 pages")
    print(f"kernels: {'interpreted' if ops.interpret_mode() else 'Mosaic'}")

    reqs = make_requests(seed, cfg.vocab_size)
    llm = LLM(cfg, params, plan, guard=GuardConfig(nan_check=True))
    first_wall, done = serve(llm, reqs, seed)
    tokens = check_outputs(done, cfg.vocab_size, "first pass")
    st = llm.phase_stats
    print(f"kv pool: {st['kv_quant']}, shared prompt tokens "
          f"{st['shared_tokens_admitted']}, CoW copies {st['cow_copies']}, "
          f"prefill batches {st['prefill_batches']}, "
          f"preemptions {st['preemptions']}")
    check(st["kv_quant"] == "int8", "the int8 page pool did not serve")
    check(st["shared_tokens_admitted"] > 0 and st["cow_copies"] > 0,
          "copy-on-write prefix sharing did not run")
    compile_s, trace_s, compiles = (clock.seconds, clock.trace_seconds,
                                    clock.compiles)

    serve_wall, again = serve(llm, reqs, seed)
    check_outputs(again, cfg.vocab_size, "second pass")
    check([r.out for r in again] == [r.out for r in done],
          "the second pass did not repeat the first token for token")
    check(clock.compiles == compiles, "the second pass compiled again")
    print(f"tokens served per pass: {tokens}")
    print(f"compile seconds: {compile_s:.3f} over {compiles} compiles "
          f"({clock.cache_hits} persistent-cache hits); trace and lowering "
          f"seconds: {trace_s:.3f}")
    print(f"first pass wall seconds (compiles included): {first_wall:.3f}")
    print(f"serving wall seconds (second pass): {serve_wall:.3f}")

    hlo = decode_chunk_hlo(llm, params, plan)
    n_custom = hlo.count("tpu_custom_call")
    print(f"decode chunk HLO: {n_custom} tpu_custom_call site(s)")
    check(n_custom > 0, "the decode chunk holds no Mosaic kernel")

    errs = kernel_vs_oracle(cfg, seed)
    print("paged_attention vs oracle, max abs error: "
          + ", ".join(f"{k} {v:.3g}" for k, v in errs.items()))
    print(f"peak_bytes_in_use: {dev.memory_stats()['peak_bytes_in_use']}")


def run_four_chips(cfg, params, dev, seed: int) -> None:
    import jax
    from repro.serve import LLM
    from repro.serve.guard import GuardConfig

    check(len(jax.devices()) == 4,
          f"--four-chips needs 4 devices, JAX sees {len(jax.devices())}")
    param_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    reqs = make_requests(seed, cfg.vocab_size)
    outs = {}
    for mesh in ("tp=1", "tp=2"):
        plan = resolve_plan(cfg, dev, param_bytes, mesh=mesh)
        llm = LLM(cfg, params, plan, guard=GuardConfig(nan_check=True))
        wall, done = serve(llm, reqs, seed)
        tokens = check_outputs(done, cfg.vocab_size, mesh)
        outs[mesh] = [r.out for r in done]
        rep = llm.sharding_report()
        print(f"{mesh}: {llm.mesh.describe()}; {tokens} tokens in "
              f"{wall:.3f} s (compiles included); weights on "
              f"{rep['weights_devices']}, KV pool on {rep['pool_devices']}")
        for d in jax.devices():
            mem = d.memory_stats()
            print(f"  {d}: bytes_in_use {mem['bytes_in_use']}, "
                  f"peak_bytes_in_use {mem['peak_bytes_in_use']}")
    check(outs["tp=2"] == outs["tp=1"],
          "tp=2 streams differ from tp=1 token for token")
    print("tp=2 matches tp=1 token for token")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="only the tp=2 against tp=1 comparison (4 chips)")
    args = ap.parse_args(argv)

    dev = require_tpu()
    import jax
    from repro.configs import get_config
    from repro.launch import compile_cache
    from repro.models import transformer as tfm

    print(f"compile cache: {compile_cache.enable()}")
    clock = CompileClock()
    print(f"device: {dev.platform} {dev.device_kind} "
          f"x{len(jax.devices())}")
    cfg = get_config(ARCH)
    params = jax.block_until_ready(
        tfm.init_params(jax.random.PRNGKey(args.seed), cfg))
    if args.four_chips:
        run_four_chips(cfg, params, dev, args.seed)
    else:
        run_one_chip(cfg, params, dev, args.seed, clock)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
