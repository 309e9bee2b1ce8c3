"""Serving driver: --arch <id> [--reduced] batched continuous decoding.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --reduced \
        --requests 16 --max-new 24
"""
from __future__ import annotations

import argparse
import time

import jax

from repro.configs import ARCH_NAMES, get_config
from repro.core import plan as plan_lib
from repro.launch import compile_cache
from repro.models import transformer as tfm
from repro.serve.engine import DecodeEngine, Request


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES, required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    compile_cache.enable()

    cfg = get_config(args.arch + ("-reduced" if args.reduced else ""))
    rng = jax.random.PRNGKey(args.seed)
    params = tfm.init_params(rng, cfg)

    import numpy as np
    nprng = np.random.default_rng(args.seed)
    reqs = [Request(rid=i,
                    prompt=list(nprng.integers(
                        2, cfg.vocab_size, size=args.prompt_len)),
                    max_new=args.max_new)
            for i in range(args.requests)]

    engine = DecodeEngine(cfg, params,
                          plan_lib.plan_for_engine(cfg, slots=args.slots,
                                                   cache_len=args.cache_len),
                          temperature=args.temperature)
    t0 = time.time()
    done = engine.run(reqs, rng=jax.random.PRNGKey(args.seed + 1))
    dt = time.time() - t0
    total_new = sum(len(r.out) for r in done)
    print(f"served {len(done)} requests, {total_new} tokens "
          f"in {dt:.1f}s ({total_new / dt:.1f} tok/s)")
    for r in done[:4]:
        print(f"  req {r.rid}: {len(r.out)} new tokens, "
              f"first 8 = {r.out[:8]}")
    return done


if __name__ == "__main__":
    main()
