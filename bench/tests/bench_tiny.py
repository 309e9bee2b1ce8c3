"""A cell small enough for the CPU: the qwen2.5 block at toy widths, and a
short open-loop mix. The tests drive ``run.run_cell`` with it.

Weights are drawn at a wider scale (0.25) than the real configurations,
so that toy-width logits spread as real ones do. The gap limit 0.3 lies
between the program's widest gap on CPU (0 to 0.063 over three seeds) and
the control's (1.71 to 2.55)."""
import copy
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

CONFIG = {
    "registry": "qwen2.5-3b", "block": "dense_gqa",
    "source": "toy widths of the qwen2.5 block",
    "num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
    "vocab_size": 503, "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": True, "hidden_act": "silu",
    "initializer_range": 0.25, "eos_token_id": 1,
    "assumed": {"qkv_bias": True},
    "plan": {"expected_batch": 4, "cache_len": 256, "mean_len": 100,
             "page_size": 16, "kv_quant": "int8",
             "kv_budget_bytes": 1 << 29},
    "check": {"logit_gap": 0.3},
}

TRAFFIC = {
    "kind": "paced", "rate_per_s": 6.0, "block_s": 5,
    "prompt": {"dist": "lognormal", "median": 40, "sigma": 0.5,
               "min": 8, "max": 120},
    "output": {"dist": "uniform", "min": 4, "max": 24},
    "ramp_s": 0.5, "ramp_limit_s": 30, "drain_limit_s": 60,
    "warm_max_batch": 1, "check_requests": 3,
}

BENCH_SPEC = {
    "end_to_end": [{"name": "setup_s", "unit": "s"},
                   {"name": "ttft_p90_ms", "unit": "ms"},
                   {"name": "tpot_p90_ms", "unit": "ms"}],
    "per_layer": [],
}
CELL = {"name": "tiny", "config": "tiny", "traffic": "tiny", "chips": 1}


def run(seed, patch=None, seconds=1.5, cfile=None, inspect=None,
        bench_dir=BENCH):
    import jax
    import run as bench_run
    return bench_run.run_cell(
        BENCH_SPEC, CELL, seed, seconds, False, jax.devices()[0],
        cfile=copy.deepcopy(cfile or CONFIG), tfile=copy.deepcopy(TRAFFIC),
        patch=patch, inspect=inspect, bench_dir=bench_dir)
