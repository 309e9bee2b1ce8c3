"""Direct unit tests for the sharding primitives (ISSUE 10 satellite):
``sharding.specs`` spec construction and axis-size edge cases,
``sharding.collectives`` on the degenerate 1-device mesh (every collective
must be a no-op/identity) and — in a subprocess with a forced 8-device host
platform — against the flat jax.lax references, plus the exact-concat
shard helpers in ``sharding.tensor_parallel``."""
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.sharding import collectives, specs as sh
from repro.sharding import tensor_parallel as tpar


def _mesh1(*axis_names):
    """A mesh of the single host device with 1-sized named axes."""
    devs = np.array(jax.devices()[:1]).reshape((1,) * len(axis_names))
    return Mesh(devs, axis_names)


# ------------------------------------------------------------------- specs
def test_mesh_axis_sizes_and_dp_axes():
    mesh = _mesh1("pod", "data", "model")
    assert sh.mesh_axis_sizes(mesh) == {"pod": 1, "data": 1, "model": 1}
    assert sh.dp_axes({"pod": 2, "data": 4, "model": 2}) == ("pod", "data")
    assert sh.dp_axes({"data": 4, "model": 2}) == ("data",)
    assert sh.dp_axes({"model": 2}) == ()


def test_axes_size_forms():
    ax = {"pod": 2, "data": 4, "model": 8}
    assert sh.axes_size(ax, None) == 1
    assert sh.axes_size(ax, "model") == 8
    assert sh.axes_size(ax, ("pod", "data")) == 8
    assert sh.axes_size(ax, ()) == 1


def test_maybe_divisibility_fallback():
    """``maybe`` is the fall-back-to-BROADCAST rule: a dimension that does
    not divide over the axis group must shard on None (replicate)."""
    ax = {"data": 4, "model": 8}
    assert sh.maybe("model", 64, ax) == "model"
    assert sh.maybe("model", 4, ax) is None          # 4 % 8 != 0
    assert sh.maybe(None, 64, ax) is None
    assert sh.maybe("model", 0, ax) == "model"       # 0 divides anything
    # single-element sequences collapse to the bare axis name
    assert sh.maybe(["model"], 64, ax) == "model"
    assert sh.maybe(("data", "model"), 64, ax) == ("data", "model")
    assert sh.maybe(("data", "model"), 8, ax) is None  # 8 % 32 != 0
    # a 1-sized axis group never shards
    assert sh.maybe("model", 64, {"model": 1}) is None


def test_named_and_tree_named_build_shardings():
    mesh = _mesh1("data")
    ns = sh.named(mesh, P("data"))
    assert isinstance(ns, NamedSharding)
    assert ns.spec == P("data")
    tree = {"a": P(), "b": {"c": P("data")}}
    out = sh.tree_named(mesh, tree)
    assert out["a"].spec == P() and out["b"]["c"].spec == P("data")


# --------------------------------------- degenerate 1-device mesh: no-ops
def test_allreduce_stacked_one_device_is_identity_sum():
    mesh = _mesh1("data")
    x = jnp.arange(12, dtype=jnp.float32).reshape(1, 3, 4)
    out = collectives.allreduce_stacked(mesh, x)
    assert out.shape == (3, 4)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x[0]))


def test_hierarchical_psum_one_device_identity():
    mesh = _mesh1("pod", "data")
    x = jnp.arange(10, dtype=jnp.float32).reshape(2, 5)
    out = jax.shard_map(
        lambda v: collectives.hierarchical_psum(v, "pod", "data"),
        mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False)(x)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))


def test_ring_allgather_one_device_identity():
    mesh = _mesh1("model")
    x = jnp.arange(6, dtype=jnp.float32).reshape(2, 3)
    out = jax.shard_map(
        lambda v: collectives.ring_allgather(v, "model"),
        mesh=mesh, in_specs=P(), out_specs=P("model"), check_vma=False)(x)
    assert out.shape == (1, 2, 3)     # new leading gather dim, 1 source
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(x))


# --------------------------------------------- tensor_parallel: exact math
def test_shard_slice_partitions_exactly():
    x = jnp.arange(24).reshape(2, 12)
    parts = [tpar.shard_slice(x, 1, s, 4) for s in range(4)]
    assert all(p.shape == (2, 3) for p in parts)
    np.testing.assert_array_equal(
        np.asarray(jnp.concatenate(parts, axis=1)), np.asarray(x))
    with pytest.raises(AssertionError):
        tpar.shard_slice(x, 1, 0, 5)            # 12 % 5 != 0


def test_all_gather_single_part_no_op():
    x = jnp.ones((2, 3))
    assert tpar.all_gather([x], axis=0) is x    # identity, no concat/copy
    out = tpar.all_gather([x, 2 * x], axis=0)
    assert out.shape == (4, 3)


def test_sharded_expert_mlp_bit_identical():
    rng = np.random.default_rng(3)
    E, d, f = 8, 16, 32
    x = jnp.asarray(rng.standard_normal((2, 1, d)), jnp.float32)
    wg = jnp.asarray(rng.standard_normal((E, d, f)), jnp.float32)
    wu = jnp.asarray(rng.standard_normal((E, d, f)), jnp.float32)
    wd = jnp.asarray(rng.standard_normal((E, f, d)), jnp.float32)
    act = jax.nn.silu
    g = jnp.einsum("bsd,edf->ebsf", x, wg,
                   preferred_element_type=jnp.float32)
    u = jnp.einsum("bsd,edf->ebsf", x, wu,
                   preferred_element_type=jnp.float32)
    full = jnp.einsum("ebsf,efd->ebsd", act(g) * u, wd,
                      preferred_element_type=jnp.float32)
    for ep in (1, 2, 4, 8):
        shard = tpar.sharded_expert_mlp(
            x, wg, wu, wd, act=act, cast=lambda t: t, ep=ep,
            accum_dtype=jnp.float32, compute_dtype=jnp.float32)
        assert jnp.array_equal(full, shard), f"ep={ep} diverged"


def test_sharded_decode_attention_bit_identical():
    from repro.configs import get_config
    from repro.models import layers
    cfg = get_config("gemma2-2b-reduced")
    B, T = 2, 16
    KV, D, H = cfg.num_kv_heads, cfg.head_dim, cfg.num_heads
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.standard_normal((B, 1, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, T, KV, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, T, KV, D)), jnp.float32)
    mask = jnp.arange(T)[None, :] < jnp.asarray([[9], [13]])
    full = layers.decode_attention(q, k, v, mask, cfg)
    for tp in (1, KV):
        shard = tpar.sharded_decode_attention(q, k, v, mask, cfg, tp)
        assert jnp.array_equal(full, shard), f"tp={tp} diverged"


# ---------------------------------------- multi-device (subprocess, mesh8)
_MULTI = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.sharding import collectives

from jax.sharding import AxisType
mesh = jax.make_mesh((2, 4), ("pod", "data"),
                     axis_types=(AxisType.Auto,) * 2)
rng = np.random.default_rng(0)
x = jnp.asarray(rng.standard_normal((8, 3, 5)), jnp.float32)

# hierarchical RS->AR->AG == flat psum over both axes
hier = jax.shard_map(
    lambda v: collectives.hierarchical_psum(v[0], "pod", "data"),
    mesh=mesh, in_specs=P(("pod", "data")), out_specs=P(),
    check_vma=False)(x)
flat = jax.shard_map(
    lambda v: jax.lax.psum(v[0], ("pod", "data")),
    mesh=mesh, in_specs=P(("pod", "data")), out_specs=P(),
    check_vma=False)(x)
np.testing.assert_allclose(np.asarray(hier), np.asarray(flat),
                           rtol=1e-6, atol=1e-6)

# allreduce_stacked == plain sum over the stacked dim
out = collectives.allreduce_stacked(mesh, x)
np.testing.assert_allclose(np.asarray(out), np.asarray(x.sum(0)),
                           rtol=1e-6, atol=1e-6)

# ring all-gather == lax.all_gather (source-index order)
mesh_m = jax.make_mesh((8,), ("model",), axis_types=(AxisType.Auto,))
y = jnp.asarray(rng.standard_normal((16, 4)), jnp.float32)
ring = jax.shard_map(
    lambda v: collectives.ring_allgather(v, "model"),
    mesh=mesh_m, in_specs=P("model"), out_specs=P("model"),
    check_vma=False)(y)
ref = jax.shard_map(
    lambda v: jax.lax.all_gather(v, "model"),
    mesh=mesh_m, in_specs=P("model"), out_specs=P("model"),
    check_vma=False)(y)
np.testing.assert_array_equal(np.asarray(ring), np.asarray(ref))
print("MULTI_OK")
"""


def test_collectives_match_flat_references_on_8_devices():
    r = subprocess.run([sys.executable, "-c", _MULTI],
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr
    assert "MULTI_OK" in r.stdout
