"""Sequence-sharded flash attention (§Perf A1): exact parity with the
unsharded path on a real multi-device mesh. Runs in a subprocess because the
host device count must be set before jax initializes."""
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, PartitionSpec as P
from repro.models import flash

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
B, KV, R, S, D = 2, 2, 2, 64, 16
rng = np.random.default_rng(0)
q = jnp.asarray(rng.standard_normal((B, KV, R, S, D)), jnp.float32)
k = jnp.asarray(rng.standard_normal((B, KV, S, D)), jnp.float32)
v = jnp.asarray(rng.standard_normal((B, KV, S, D)), jnp.float32)
w = jnp.asarray(rng.standard_normal((B, KV, R, S, D)), jnp.float32)

def seq_sharded(qf, kf, vf, mode, msize):
    S_loc = S // 4
    def body(q_loc, k_full, v_full):
        off = jax.lax.axis_index("model") * S_loc
        qpos = off + jnp.arange(S_loc, dtype=jnp.int32)
        return flash.flash_attention(q_loc, k_full, v_full, mode, msize,
                                     0.0, 16, 16, qpos=qpos)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P("data", None, None, "model", None),
                  P("data", None, None, None), P("data", None, None, None)),
        out_specs=P("data", None, None, "model", None),
        check_vma=False)(qf, kf, vf)

for mode, msize in [("causal", S), ("window", 12), ("chunk", 16)]:
    ref = flash.flash_attention(q, k, v, mode, msize, 0.0, 16, 16)
    got = jax.jit(lambda a, b, c: seq_sharded(a, b, c, mode, msize))(q, k, v)
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32) -
                                 ref.astype(jnp.float32)))) == 0.0, mode
    for arg in range(3):
        g1 = jax.grad(lambda *xs: jnp.sum(flash.flash_attention(
            *xs, mode, msize, 0.0, 16, 16).astype(jnp.float32) * w),
            argnums=arg)(q, k, v)
        g2 = jax.grad(lambda *xs: jnp.sum(jax.jit(
            lambda a, b, c: seq_sharded(a, b, c, mode, msize)
        )(*xs).astype(jnp.float32) * w), argnums=arg)(q, k, v)
        assert float(jnp.max(jnp.abs(g1 - g2))) == 0.0, (mode, arg)
print("SEQSHARD_OK")
"""


def test_seq_sharded_flash_parity_8dev():
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert "SEQSHARD_OK" in out.stdout, out.stdout + out.stderr
