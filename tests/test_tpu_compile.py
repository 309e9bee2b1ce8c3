"""Compile the Pallas kernels for a described TPU v5e, without a chip.

Interpret mode cannot see the TPU's tiling rules or its memory limits; the
TPU compiler can, for a chip it is only told about. Every case here compiles
the raw kernel with ``interpret=False`` at a published width: qwen2.5-3b
(GQA 16/2, head_dim 128, 64-token pages; and one tp=2 shard of it, one KV
head), mistral-nemo (GQA 32/8, pages read token-major) and a gemma-like
head_dim 256 with a logit softcap. The BCSC sparse kernels are held as strict expected
failures with the compiler's reason, so a fix that makes them compile flips
these tests (ROADMAP Design item 7 decides whether they stay).

The topology is described inside a fixture, never at import: only the
worker that runs this file loads the TPU compiler.
"""
import importlib

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# the package re-exports wrapper functions under the module names
bcsc_matmul = importlib.import_module("repro.kernels.bcsc_matmul")
bcsc_mlp = importlib.import_module("repro.kernels.bcsc_mlp")
local_attention = importlib.import_module("repro.kernels.local_attention")
paged = importlib.import_module("repro.kernels.paged_attention")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


def _compile(fn, *args):
    """Compile for the described chip; returns the compiled HLO text."""
    return jax.jit(fn).lower(*args).compile().as_text()


# ---------------------------------------------------------- paged attention
@pytest.mark.parametrize("quant", ["fp", "int8"])
@pytest.mark.parametrize("KV,R,D,softcap", [(2, 8, 128, 0.0),
                                            (4, 2, 256, 50.0),
                                            (8, 4, 128, 0.0),
                                            (1, 8, 128, 0.0)])
def test_paged_attention_compiles_for_v5e(spec, quant, KV, R, D, softcap):
    B, ps, MP, P = 16, 64, 64, 1024
    pool = jnp.int8 if quant == "int8" else jnp.bfloat16
    args = [spec((B, KV, R, D), jnp.float32),
            spec((P, ps, KV, D), pool), spec((P, ps, KV, D), pool),
            spec((B, MP), jnp.int32), spec((B,), jnp.int32)]
    if quant == "int8":
        args += [spec((P, KV), jnp.float32), spec((P, KV), jnp.float32)]

    def fn(q, k, v, bt, lens, *scales):
        ks, vs = scales or (None, None)
        return paged.paged_attention_raw(q, k, v, bt, lens, k_scale=ks,
                                         v_scale=vs, softcap=softcap)

    assert "tpu_custom_call" in _compile(fn, *args)


def test_sliding_window_attention_compiles_for_v5e(spec):
    B, H, KV, S, D = 1, 16, 2, 4096, 128
    q = spec((B, H, S, D), jnp.bfloat16)
    kv = spec((B, KV, S, D), jnp.bfloat16)
    hlo = _compile(
        lambda q, k, v: local_attention.sliding_window_attention_raw(
            q, k, v, window=S), q, kv, kv)
    assert "tpu_custom_call" in hlo


# ------------------------------------------------------ BCSC sparse kernels
class Refused(Exception):
    """The TPU compiler refused the kernel for the reason the test names."""


def _expect_refusal(fn, *args, because: str):
    try:
        _compile(fn, *args)
    except Exception as e:          # the compiler's own error types vary
        if because not in str(e):
            raise
        raise Refused(because) from e


TILING = "divisible by 8 and 128"
D_MODEL, D_FF = 2048, 11008       # qwen2.5-3b's MLP


@pytest.mark.xfail(strict=True, raises=Refused, reason=(
    "16x16 BCSC blocks give (bm, 16) x and out blocks, which break the "
    "(8, 128)-or-full-dim tiling rule; 128x128 blocks compile"))
def test_bcsc_gemv_16x16_compiles_for_v5e(spec):
    nnzb, bm, bk, bn = 512, 8, 16, 16
    _expect_refusal(
        lambda x, b, r, c: bcsc_matmul.bcsc_gemv_raw(x, b, r, c, n_out=D_FF,
                                                     bm=bm),
        spec((bm, D_MODEL), jnp.bfloat16), spec((nnzb, bk, bn), jnp.bfloat16),
        spec((nnzb,), jnp.int32), spec((nnzb,), jnp.int32), because=TILING)


@pytest.mark.xfail(strict=True, raises=Refused, reason=(
    "16x16 BCSC blocks give (bm, 16) x and out blocks, which break the "
    "(8, 128)-or-full-dim tiling rule; 128x128 blocks compile"))
def test_bcsc_matmul_16x16_compiles_for_v5e(spec):
    nnzb, bm, bk, bn = 512, 128, 16, 16
    _expect_refusal(
        lambda x, b, r, c: bcsc_matmul.bcsc_matmul_raw(x, b, r, c,
                                                       n_out=D_FF, bm=bm),
        spec((bm, D_MODEL), jnp.bfloat16), spec((nnzb, bk, bn), jnp.bfloat16),
        spec((nnzb,), jnp.int32), spec((nnzb,), jnp.int32), because=TILING)


@pytest.mark.xfail(strict=True, raises=Refused, reason=(
    "the fused MLP's chunk gather reads id vectors from its scalar-prefetch "
    "refs as vectors, and Mosaic only loads scalars from SMEM; this holds "
    "at every block size, 128x128 included"))
def test_bcsc_mlp_fused_compiles_for_v5e(spec):
    bm, bk, bn = 8, 128, 128
    PG = PU = (D_MODEL // bk) * (D_FF // bn) // 4
    PD = (D_FF // bk) * (D_MODEL // bn) // 4
    ids = lambda n: spec((n,), jnp.int32)
    blocks = lambda n: spec((n, bk, bn), jnp.bfloat16)

    def fn(x, gb, gr, gc, ub, ur, uc, db, dr, dc, counts):
        return bcsc_mlp.bcsc_mlp_raw(
            x, gb, gr, gc, db, dr, dc, counts, u_blocks=ub, u_rows=ur,
            u_cols=uc, d_ff=D_FF, n_out=D_MODEL, bm=bm, activation="silu")

    _expect_refusal(
        fn, spec((bm, D_MODEL), jnp.bfloat16), blocks(PG), ids(PG), ids(PG),
        blocks(PU), ids(PU), ids(PU), blocks(PD), ids(PD), ids(PD),
        spec((3,), jnp.int32), because="Can only load scalars from SMEM")
