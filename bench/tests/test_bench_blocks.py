"""The block seam: a configuration names its block (``"block"``), the
harness finds ``bench/blocks/<block>.py`` by that name alone, and the dense
block, moved there, counts and computes what it did before the move.

The pinned integers and checksums were made on the commit before the move
(``model.Dims`` and ``reference.logits_at`` in ``bench/benchlib``): the
same lengths through ``roofline``, and the same weights, tokens and
positions through the reference, in a process held to one CPU core."""
import copy
import json
import os
import subprocess
import sys

import pytest

import bench_tiny
from benchlib import REPO_ROOT, roofline, spec

BENCH = spec.load_benchmark(REPO_ROOT)
DECODE = [[1], [100], [64, 600, 4096], [4096] * 9 + [13312]]
PREFILL = [[3], [600, 3072], [12288]]
# name: (vocab rows of the served tables, token_flops, decode_step_flops
# at DECODE, prefill_flops at PREFILL, weight_bytes)
PINNED = {
    "qwen2.5-3b": (
        152064, 5549064192,
        [6171688960, 6200885248, 19917963264, 76511444992],
        [17271291904, 21822603395072, 90454445522944], 6172401664),
    "mistral-nemo-12b-d10": (
        131072, 5452595200,
        [6794936320, 6811156480, 21164195840, 76168560640],
        [17700945920, 20827500052480, 79373344440320], 8137164800),
}
# sha256 of the float32 logits (99 positions x 503), bench_tiny's sizes
CHECKSUMS = {
    (True, None):
        "b4b72f4ba8125cfbe6f6841f311cce518b8fdd2c29186922f61a6427edb23531",
    (True, "control"):
        "c843760123072cf7f899e475787638e425f3647f586d870bc0ed4c89137dd48f",
    (False, None):
        "6052de9c6e8f83eb9d5104133715fa96e80dfbd256e0ac16056106ad0bc8cb9a",
    (False, "control"):
        "0a3b1c8aa232fa469db091a56a89c33673b1e201e8326705910dc479763bc94f",
}


def _config(name):
    entry = spec.config_entry(BENCH, name)
    with open(REPO_ROOT / entry["file"]) as f:
        return json.load(f)


@pytest.mark.parametrize("name", sorted(PINNED))
def test_yardstick_counts_match_the_dense_block_before_the_move(name):
    rows, token, decode, prefill, wbytes = PINNED[name]
    c = _config(name)
    dims = spec.block_of(c).Dims.from_file(c)
    assert roofline.token_flops(dims) == token
    assert [roofline.decode_step_flops(dims, n) for n in DECODE] == decode
    assert [roofline.prefill_flops(dims, n) for n in PREFILL] == prefill
    assert dims.weight_bytes(rows) == wbytes
    assert dims.paged_layers == dims.layers


# XLA's CPU backend orders float32 sums differently on one core than on
# several, so the child is held to one core and the checksums do not
# depend on the machine's core count
_CHILD = r'''
import dataclasses, hashlib, json, os
os.sched_setaffinity(0, {min(os.sched_getaffinity(0))})
import numpy as np
import jax.numpy as jnp
import bench_tiny
from benchlib import reference, spec

block = spec.block_of(bench_tiny.CONFIG)


def weights(dims, seed):
    rng = np.random.default_rng(seed)
    L, d, H, KV = dims.layers, dims.d, dims.heads, dims.kv_heads
    D, F = dims.head_dim, dims.d_ff

    def w(*shape, scale=0.25):
        x = (scale * rng.standard_normal(shape)).astype(np.float32)
        return jnp.asarray(x, jnp.bfloat16)
    layers = {"ln1": w(L, d, scale=0.05), "ln2": w(L, d, scale=0.05),
              "wq": w(L, d, H * D), "wk": w(L, d, KV * D),
              "wv": w(L, d, KV * D), "wo": w(L, H * D, d), "wg": w(L, d, F),
              "wu": w(L, d, F), "wd": w(L, F, d), "bq": w(L, H * D),
              "bk": w(L, KV * D), "bv": w(L, KV * D)}
    out = {"embed": w(dims.vocab + 9, d), "final_norm": w(d, scale=0.05),
           "layers": layers}
    if not dims.tied:
        out["lm_head"] = w(d, dims.vocab + 9)
    return out


tokens = (np.arange(700) * 7919 + 11) % 503
pos = np.arange(600, 699)
out = {}
for tied in (True, False):
    dims = dataclasses.replace(block.Dims.from_file(bench_tiny.CONFIG),
                               tied=tied)
    wts = weights(dims, 20261019)
    for quant in (None, "control"):
        x = np.asarray(reference.logits_at(block._layer, wts, dims, tokens,
                                           pos, quant), np.float32)
        out[f"{tied} {quant}"] = hashlib.sha256(x.tobytes()).hexdigest()
print(json.dumps(out))
'''


def test_dense_reference_logits_are_bit_identical():
    pytest.importorskip("jax")
    bench = bench_tiny.BENCH
    path = os.pathsep.join([str(REPO_ROOT / "src"), str(bench),
                            str(bench / "tests")])
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=path)
    p = subprocess.run([sys.executable, "-c", _CHILD], capture_output=True,
                       text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    got = json.loads(p.stdout.splitlines()[-1])
    assert got == {f"{t} {q}": h for (t, q), h in CHECKSUMS.items()}


_COUNTING = '''

def _counted(fn):
    def call(*a, **k):
        with open(__file__ + ".calls", "a") as f:
            f.write(fn.__name__ + "\\n")
        return fn(*a, **k)
    return call


program_config = _counted(program_config)
plain_weights = _counted(plain_weights)
request_gaps = _counted(request_gaps)
'''


def test_run_cell_dispatches_to_the_configurations_block(tmp_path):
    """A block that only a temporary bench directory holds, named by a
    copy of bench_tiny's configuration, is the one a run goes through."""
    pytest.importorskip("jax")
    (tmp_path / "blocks").mkdir()
    src = (bench_tiny.BENCH / "blocks" / "dense_gqa.py").read_text()
    block = tmp_path / "blocks" / "counted_dense.py"
    block.write_text(src + _COUNTING)
    cfile = dict(copy.deepcopy(bench_tiny.CONFIG), block="counted_dense")
    res = bench_tiny.run(2**41 + 9, cfile=cfile, bench_dir=tmp_path)
    assert res["correct"], res["checks"]
    assert res["checks"]["unchecked"]["value"] == 0
    calls = (tmp_path / "blocks" / "counted_dense.py.calls").read_text()
    calls = calls.split()
    assert calls.count("program_config") == 1
    assert calls.count("plain_weights") == 1
    assert calls.count("request_gaps") >= 1


def _named_blocks():
    names = {json.loads((bench_tiny.BENCH / "configs" / p).read_text())
             .get("block") for p in os.listdir(bench_tiny.BENCH / "configs")}
    return names | {bench_tiny.CONFIG["block"]}


def test_every_configuration_names_a_block_and_every_block_is_named():
    blocks = {p.stem for p in (bench_tiny.BENCH / "blocks").glob("*.py")}
    assert None not in _named_blocks()
    assert _named_blocks() == blocks
    for name in blocks:
        mod = spec.load_block(name)
        for attr in ("Dims", "program_config", "plain_weights",
                     "request_gaps"):
            assert callable(getattr(mod, attr)), (name, attr)


@pytest.mark.parametrize("cfile,err", [
    ({k: v for k, v in bench_tiny.CONFIG.items() if k != "block"}, KeyError),
    (dict(bench_tiny.CONFIG, block="no_such_block"), FileNotFoundError),
], ids=["missing", "unknown"])
def test_a_missing_or_unknown_block_raises(cfile, err):
    with pytest.raises(err, match="bench/blocks/"):
        spec.block_of(cfile)
