"""Eyexam (paper Appendix A): step-wise bound tightening + HLO cost parser."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st  # optional dep, see tests/hypothesis_compat.py

from repro.core import eyexam, hloparse, reuse


# ------------------------------------------------------------- seven steps
def _acc(n_pes=256, noc="hmnoc"):
    side = int(np.sqrt(n_pes))
    return eyexam.AcceleratorModel(n_pes=n_pes, array_h=side, array_w=side,
                                   noc=noc)


@settings(max_examples=30, deadline=None)
@given(st.integers(1, 512), st.integers(1, 512), st.integers(1, 64))
def test_bounds_monotonically_tighten(n, c, m):
    """Each Eyexam step may only LOWER the bound (paper Table VIII)."""
    shape = reuse.gemm("g", n, c, m)
    steps = eyexam.seven_steps(shape, _acc())
    bounds = [s["bound"] for s in steps]
    assert all(b2 <= b1 + 1e-9 for b1, b2 in zip(bounds, bounds[1:]))
    assert bounds[0] == shape.macs


def test_hmnoc_scales_v1_saturates():
    """Fig. 14: broadcast NoC saturates with scale, HM-NoC keeps scaling."""
    dw = reuse.conv("dw", n=1, c=1, m=1, h=56, w=56, r=3, s=3, groups=64)
    perf_v1 = [eyexam.seven_steps(dw, _acc(n, "broadcast"))[-1]["bound"]
               for n in (256, 1024, 16384)]
    perf_v2 = [eyexam.seven_steps(dw, _acc(n, "hmnoc"))[-1]["bound"]
               for n in (256, 1024, 16384)]
    assert perf_v1[2] <= perf_v1[0] * 1.5          # v1 saturated
    assert perf_v2[2] > perf_v2[0] * 2.0           # v2 keeps scaling


def test_network_performance_aggregates():
    layers = [reuse.gemm(f"l{i}", 4096, 512, 512) for i in range(4)]
    mac_rate = eyexam.network_performance(layers, _acc())
    assert 0 < mac_rate <= 256


# ----------------------------------------------------------------- roofline
def test_roofline_terms_and_bound():
    r = eyexam.Roofline(flops=197e12, hbm_bytes=819e9, coll_bytes=0.0,
                        per_op_coll={}, chips=1)
    assert np.isclose(r.t_compute, 1.0)
    assert np.isclose(r.t_memory, 1.0)
    assert r.t_collective == 0.0
    r2 = eyexam.Roofline(flops=1e12, hbm_bytes=819e9 * 10, coll_bytes=1,
                         per_op_coll={}, chips=1)
    assert r2.bound == "memory"
    assert 0 < r2.fraction_of_roofline(1e12) <= 1.0


# --------------------------------------------------------------- HLO parser
def test_hloparse_counts_loop_iterations():
    """The reason this parser exists: cost_analysis counts scan bodies once."""
    def f(w, x):
        def body(c, wi):
            return jnp.tanh(c @ wi), ()
        c, _ = jax.lax.scan(body, x, w)
        return c

    w = jax.ShapeDtypeStruct((5, 64, 64), jnp.float32)
    x = jax.ShapeDtypeStruct((32, 64), jnp.float32)
    compiled = jax.jit(f).lower(w, x).compile()
    cost = hloparse.analyze(compiled.as_text())
    expect = 5 * 2 * 32 * 64 * 64          # 5 iterations x one (32,64)@(64,64)
    assert cost.flops == expect
    ca = compiled.cost_analysis()
    assert ca.get("flops", 0) < expect     # the builtin undercounts


def test_hloparse_plain_matmul():
    compiled = jax.jit(lambda a, b: a @ b).lower(
        jax.ShapeDtypeStruct((128, 256), jnp.float32),
        jax.ShapeDtypeStruct((256, 64), jnp.float32)).compile()
    cost = hloparse.analyze(compiled.as_text())
    assert cost.flops == 2 * 128 * 256 * 64
    assert cost.hbm_bytes > 0


def test_hloparse_shape_bytes():
    assert hloparse._shape_bytes("f32[4,8]{1,0}") == 128
    assert hloparse._shape_bytes("bf16[10]") == 20
    assert hloparse._shape_bytes("(f32[2,2], s32[4])") == 32
    assert hloparse._shape_bytes("pred[]") == 1


def test_hloparse_nested_scan_multiplies():
    def f(x):
        def outer(c, _):
            def inner(c2, _):
                return jnp.tanh(c2 @ c2), ()
            c2, _ = jax.lax.scan(inner, c, None, length=3)
            return c2, ()
        c, _ = jax.lax.scan(outer, x, None, length=4)
        return c

    x = jax.ShapeDtypeStruct((16, 16), jnp.float32)
    compiled = jax.jit(f).lower(x).compile()
    cost = hloparse.analyze(compiled.as_text())
    assert cost.flops == 4 * 3 * 2 * 16 * 16 * 16


def test_hloparse_inplace_dus_fusion_counts_slice():
    """A scan that appends one token to a big cache buffer must be charged
    O(slice) bytes per step, not O(buffer) (the decode KV-append pattern)."""
    def f(cache, xs):
        def body(c, x):
            c = jax.lax.dynamic_update_slice_in_dim(c, x[None], 3, axis=0)
            return c, ()
        c, _ = jax.lax.scan(body, cache, xs)
        return c

    cache = jax.ShapeDtypeStruct((4096, 256), jnp.float32)
    xs = jax.ShapeDtypeStruct((8, 256), jnp.float32)
    cost = hloparse.analyze(jax.jit(f).lower(cache, xs).compile().as_text())
    buf = 4096 * 256 * 4
    # allowed: ONE loop-entry copy of the buffer (write+read = 2 passes) +
    # slice-granular updates. Disallowed: per-iteration full-buffer charges
    # (8 iterations x 2 ops x buffer ≈ 16 passes — the pre-fix behaviour).
    assert cost.hbm_bytes < 3 * buf, cost.hbm_bytes
