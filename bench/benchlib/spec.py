"""Data-driven discovery: ``BENCHMARK.json`` names every cell, metric and
configuration; each configuration, traffic mix and per-layer metric is a
file of its own that is found here by its name.

- ``bench/configs/<config>.json``: the configuration as it is run; its
  ``"block"`` names the block module;
- ``bench/blocks/<block>.py``: the block's sizes and yardstick counts,
  its program mapping and its reference (the contract is written in
  ``bench/blocks/dense_gqa.py``);
- ``bench/traffic/<mix>.json``: the generator kind and its parameters;
- ``bench/metrics/<metric>.py``: a reader ``read(rec)`` that returns the
  metric's value from a run's record, or None where it finds nothing. A
  metric split by the cells it serves (``decode_step_ms.lat``,
  ``decode_step_ms.tput``) is read by the file of its stem
  (``decode_step_ms.py``) unless it has a file of its own.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path
from typing import Callable, Dict, List

from benchlib import BENCH_DIR, REPO_ROOT


def load_benchmark(root: Path = REPO_ROOT) -> Dict:
    path = Path(root) / "BENCHMARK.json"
    with open(path) as f:
        return json.load(f)


def workload(bench: Dict, name: str) -> Dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    names = ", ".join(c["name"] for c in bench["workloads"])
    raise KeyError(f"no workload {name!r} in BENCHMARK.json (cells: {names})")


def config_entry(bench: Dict, name: str) -> Dict:
    for cfg in bench["configs"]:
        if cfg["name"] == name:
            return cfg
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def load_config(bench: Dict, name: str, root: Path = REPO_ROOT) -> Dict:
    """The configuration's file, as named by its ``BENCHMARK.json`` entry."""
    with open(Path(root) / config_entry(bench, name)["file"]) as f:
        return json.load(f)


def load_traffic(name: str, bench_dir: Path = BENCH_DIR) -> Dict:
    with open(Path(bench_dir) / "traffic" / f"{name}.json") as f:
        return json.load(f)


def reader_path(name: str, bench_dir: Path = BENCH_DIR) -> Path:
    """``bench/metrics/<name>.py``, or for a split metric ``<stem>.<part>``
    without a file of its own, ``bench/metrics/<stem>.py``."""
    path = Path(bench_dir) / "metrics" / f"{name}.py"
    if not path.exists() and "." in name:
        path = path.with_name(name.rsplit(".", 1)[0] + ".py")
    return path


def load_reader(name: str, bench_dir: Path = BENCH_DIR) -> Callable:
    """``read`` of the metric's file (``reader_path``). Metric names may
    hold dots, so the module is loaded from its path, not imported by
    name."""
    return _module(reader_path(name, bench_dir), "bench_metric_" + name).read


def _module(path: Path, name: str):
    """The module of the file ``path``, run under ``name`` (dots and dashes
    made underscores) and entered in ``sys.modules`` first, as a dataclass
    that the file defines looks its module up there."""
    name = name.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_block(name: str, bench_dir: Path = BENCH_DIR):
    """The block module ``bench/blocks/<name>.py``, loaded from its path as
    a reader is. There is no default block."""
    path = Path(bench_dir) / "blocks" / f"{name}.py"
    if not path.exists():
        have = sorted(p.stem for p in path.parent.glob("*.py"))
        raise FileNotFoundError(f"no block {name!r}: no file {path} (the "
                                f"blocks of bench/blocks/ here: {have})")
    return _module(path, "bench_block_" + name)


def block_of(cfile: Dict, bench_dir: Path = BENCH_DIR):
    """The block module that configuration file ``cfile`` names."""
    if "block" not in cfile:
        raise KeyError("the configuration names no \"block\": give it the "
                       "name of a module of bench/blocks/")
    return load_block(cfile["block"], bench_dir)


def end_to_end_for(bench: Dict, cell: str) -> List[Dict]:
    """End-to-end metrics the cell reports: those that list it, and those
    that list no cells at all (``setup_s``)."""
    return [m for m in bench["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]]


def per_layer_for(bench: Dict, cell: str) -> List[Dict]:
    """Per-layer metrics the cell reports: those that list it, and those
    without a list whose moved end-to-end metric the cell reports."""
    e2e = {m["name"] for m in end_to_end_for(bench, cell)}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)]
