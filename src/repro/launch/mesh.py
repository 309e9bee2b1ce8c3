"""Production mesh construction (DESIGN.md §5).

``pod`` is the paper's inter-cluster 2D-mesh level; (`data`,`model`) are the
intra-pod axes (the all-to-all-within-cluster level). Defined as FUNCTIONS so
importing this module never touches jax device state — only launch/dryrun.py
(which sets XLA_FLAGS first) ever builds the 256/512-device meshes.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _make(shape, axes) -> Mesh:
    n = 1
    for s in shape:
        n *= s
    devs = jax.devices()
    assert len(devs) >= n, (f"need {n} devices, have {len(devs)} — the dry-run "
                            "must set XLA_FLAGS=--xla_force_host_platform_"
                            "device_count=512 before importing jax")
    return jax.make_mesh(shape, axes, devices=devs[:n],
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make(shape, axes)


def make_local_mesh(model: int = 1) -> Mesh:
    """Mesh over whatever devices exist (CPU: 1) — examples and smoke tests."""
    n = len(jax.devices())
    assert n % model == 0
    return _make((n // model, model), ("data", "model"))


def make_scaled_mesh(chips: int, *, model: int = 16, pods: int = 1) -> Mesh:
    """Arbitrary-scale mesh for the strong-scaling study (Fig. 14 analogue)."""
    per_pod = chips // pods
    assert per_pod % model == 0
    data = per_pod // model
    if pods > 1:
        return _make((pods, data, model), ("pod", "data", "model"))
    return _make((data, model), ("data", "model"))
