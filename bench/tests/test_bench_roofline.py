"""The yardstick against hand counts at the two configurations' widths."""
import json

import pytest

import bench_tiny
from benchlib import roofline, spec

CONFIGS = bench_tiny.BENCH / "configs"


def _dims(name):
    with open(CONFIGS / f"{name}.json") as f:
        c = json.load(f)
    return spec.block_of(c).Dims.from_file(c)


def test_peaks_by_device_kind():
    pk = roofline.peaks("TPU v5 lite")
    assert (pk.bf16_flops, pk.hbm_bw) == (197e12, 819e9)
    assert "TPU v5e" in pk.source
    with pytest.raises(ValueError):
        roofline.peaks("TPU v9 imaginary")


def test_weight_bytes():
    # qwen2.5-3b: the program pads the vocabulary to 152,064 rows
    assert _dims("qwen2.5-3b").weight_bytes(152064) == 6_172_401_664
    # nemo, 10 layers: 10 x 545,280,000 + 2 x 1,342,177,280 + 10,240
    nemo = _dims("mistral-nemo-12b-d10")
    assert nemo.weight_bytes(131072) == 8_137_164_800
    assert 10 * 545_280_000 + 2 * 1_342_177_280 + 2 * 5120 == 8_137_164_800


def test_kv_bytes_per_token():
    """int8 K and V per token across nemo-d10's 10 layers: 20,480 B before
    scales; qwen's 36 layers in int8: 18,432 B."""
    nemo, qwen = _dims("mistral-nemo-12b-d10"), _dims("qwen2.5-3b")
    # one full page of 64 tokens, minus query, output, table and scales
    for dims, want in ((nemo, 20480), (qwen, 18432)):
        one = roofline.paged_attention_bytes(dims, [64], 64, 1)
        extra = (dims.heads * dims.head_dim * 6 + 4
                 + 2 * dims.kv_heads * 4)
        assert (one - extra) * dims.layers // 64 == want


def test_paged_attention_counts():
    nemo = _dims("mistral-nemo-12b-d10")
    # a row of 65 tokens reads two pages; bf16 pages have no scales
    b = roofline.paged_attention_bytes(nemo, [65], 64, 2)
    assert b == 2 * 2 * 64 * 8 * 128 * 2 + 32 * 128 * 6 + 2 * 4
    assert roofline.paged_attention_flops(nemo, [10, 20]) == \
        4 * 32 * 128 * 30
    t, bound = roofline.least_time(1e9, 819e9, roofline.peaks("TPU v5 lite"))
    assert bound == "memory" and t == pytest.approx(1.0)


def test_step_flops():
    qwen = _dims("qwen2.5-3b")
    per_layer = 2048 * 2048 * 2 + 2048 * 256 * 2 + 3 * 2048 * 11008
    assert roofline.token_flops(qwen) == 2 * 36 * per_layer
    head = 2 * 2048 * 151936
    assert roofline.decode_step_flops(qwen, [100]) == \
        2 * 36 * per_layer + head + 36 * 4 * 16 * 128 * 100
    assert roofline.prefill_flops(qwen, [3]) == \
        3 * 2 * 36 * per_layer + head + 36 * 4 * 16 * 128 * 6
