"""Data-driven discovery: every file in ``bench/configs``, ``bench/traffic``
and ``bench/metrics`` loads by its name, every cell of ``BENCHMARK.json``
resolves to them, and the file keeps to the benchmark's format."""
import json
import re

import pytest

import bench_tiny
from benchlib import REPO_ROOT, spec, traffic

BENCH = spec.load_benchmark(REPO_ROOT)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _stems(sub, suffix):
    return sorted(p.name[:-len(suffix)]
                  for p in (bench_tiny.BENCH / sub).glob("*" + suffix))


@pytest.mark.parametrize("name", _stems("configs", ".json"))
def test_config_loads(name):
    entry = next(c for c in BENCH["configs"]
                 if c["file"] == f"bench/configs/{name}.json")
    c = spec.load_config(BENCH, entry["name"])
    assert c["source"] == entry["source"]
    assert c["reduced"] == entry["reduced"]
    block = spec.block_of(c)
    dims = block.Dims.from_file(c)
    arch = block.program_config(c)
    assert (arch.num_layers, arch.d_model, arch.num_heads, arch.num_kv_heads,
            arch.head_dim, arch.d_ff, arch.vocab_size) == \
        (dims.layers, dims.d, dims.heads, dims.kv_heads, dims.head_dim,
         dims.d_ff, dims.vocab)
    assert arch.norm_eps == dims.eps and arch.tie_embeddings == dims.tied


@pytest.mark.parametrize("name", _stems("traffic", ".json"))
def test_traffic_loads(name):
    t = spec.load_traffic(name)
    assert t["kind"] in traffic.KINDS
    lo, hi = traffic.prompt_range(t)
    assert 0 < lo <= hi
    traffic.make(t, 1, 1000, ramp_s=1.0, window_s=1.0)


@pytest.mark.parametrize("name", [m["name"] for m in BENCH["per_layer"]])
def test_metric_reader_loads(name):
    assert callable(spec.load_reader(name))


def test_every_reader_file_is_read():
    used = {spec.reader_path(m["name"]).name for m in BENCH["per_layer"]}
    assert set(_stems("metrics", ".py")) == {n[:-3] for n in used}


@pytest.mark.parametrize("cell", [c["name"] for c in BENCH["workloads"]])
def test_cell_resolves(cell):
    w = spec.workload(BENCH, cell)
    c = spec.load_config(BENCH, w["config"])
    t = spec.load_traffic(w["traffic"])
    lo, hi = traffic.prompt_range(t)
    _, out_hi = traffic.output_range(t)
    assert hi + out_hi <= c["plan"]["cache_len"]
    e2e = spec.end_to_end_for(BENCH, cell)
    names = {m["name"] for m in e2e}
    assert "setup_s" in names and len(names) >= 2
    layer = spec.per_layer_for(BENCH, cell)
    assert layer
    for m in layer:
        assert m["moves"] in names
        spec.load_reader(m["name"])


def test_benchmark_format():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["end_to_end"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m.get("workloads", cells)) <= cells
    for m in BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert set(m["workloads"]) <= cells
    layers = {m["layer"] for m in BENCH["per_layer"]}
    perf = (REPO_ROOT / "PERF.md").read_text()
    for layer in layers:
        assert f"| {layer} |" in perf, layer
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    for w in BENCH["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    assert len(json.dumps(BENCH)) < 64 * 1024
