"""Fused BCSC MLP megakernel (ISSUE 2): oracle equivalence across sparsities
and decode shapes, ragged per-layer nnzb, activation fusion, the scratch-only
hidden-activation contract, the mlp_path dispatch rule, the ragged packing
stats, and the wall-clock-free fused-vs-two-call perf guards."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import dataflow
from repro.core.sparsity import block_magnitude_prune
from repro.kernels import bcsc_mlp as bmlp
from repro.kernels import ops
from repro.models import layers
from repro.serve import sparse as sps


def _mats(d, ff, sparsity, seed=0, gated=True):
    rng = np.random.default_rng(seed)

    def prune(shape):
        w = jnp.asarray(rng.standard_normal(shape), jnp.float32)
        if sparsity > 0:
            w = block_magnitude_prune(w, sparsity, 16, 16)
        return np.asarray(w)

    wg, wd = prune((d, ff)), prune((ff, d))
    wu = prune((d, ff)) if gated else None
    return wg, wu, wd


def _ref(x, wg, wu, wd, act):
    actf = jax.nn.silu if act == "silu" else \
        (lambda t: jax.nn.gelu(t, approximate=True))
    h = actf(x @ wg)
    if wu is not None:
        h = h * (x @ wu)
    return h @ wd


# ------------------------------------------------------------ oracle sweeps
@pytest.mark.parametrize("M", [1, 4, 8])
@pytest.mark.parametrize("sparsity", [0.5, 0.7, 0.9])
def test_fused_mlp_matches_oracle(M, sparsity):
    wg, wu, wd = _mats(64, 128, sparsity)
    pg, pu, pd = (sps.pack_weight(w, 16, 16) for w in (wg, wu, wd))
    x = jnp.asarray(np.random.default_rng(1).standard_normal((M, 64)),
                    jnp.float32)
    out = ops.bcsc_mlp_packed(x, pg, pu, pd, d_ff=128, n_out=64,
                              activation="silu")
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_ref(x, wg, wu, wd, "silu")),
                               rtol=5e-4, atol=5e-4)


@pytest.mark.parametrize("activation", ["silu", "gelu"])
def test_fused_mlp_ungated_and_activation_fusion(activation):
    wg, _, wd = _mats(64, 128, 0.7, seed=3, gated=False)
    pg, pd = sps.pack_weight(wg, 16, 16), sps.pack_weight(wd, 16, 16)
    x = jnp.asarray(np.random.default_rng(2).standard_normal((4, 64)),
                    jnp.float32)
    out = ops.bcsc_mlp_packed(x, pg, None, pd, d_ff=128, n_out=64,
                              activation=activation)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_ref(x, wg, None, wd, activation)),
                               rtol=5e-4, atol=5e-4)


def test_fused_mlp_gridded_variant_large_payload():
    """Payloads past UNROLL_CHUNKS_MAX chunks take the sequential-grid walk."""
    wg, wu, wd = _mats(128, 512, 0.5, seed=5)
    pg, pu, pd = (sps.pack_weight(w, 16, 16) for w in (wg, wu, wd))
    n_chunks = sum(p["blocks"].shape[0] // bmlp._pick_chunk(
        p["blocks"].shape[0]) for p in (pg, pu, pd))
    assert n_chunks > bmlp.UNROLL_CHUNKS_MAX     # really exercises the grid
    x = jnp.asarray(np.random.default_rng(6).standard_normal((8, 128)),
                    jnp.float32)
    out = ops.bcsc_mlp_packed(x, pg, pu, pd, d_ff=512, n_out=128,
                              activation="silu")
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_ref(x, wg, wu, wd, "silu")),
                               rtol=5e-3, atol=5e-3)


# ------------------------------------------------------- ragged per-layer nnzb
def test_fused_mlp_ragged_counts_across_stacked_layers():
    """Two layers with very different densities share one padded stack; each
    layer's prefetched counts must select exactly its own real blocks."""
    dense_l = _mats(64, 128, 0.3, seed=7)       # dense-ish layer
    sparse_l = _mats(64, 128, 0.9, seed=8)      # very sparse layer
    packs = []
    for (wg, wu, wd) in (dense_l, sparse_l):
        packs.append(tuple(sps.pack_weight(w, 16, 16) for w in (wg, wu, wd)))
    # pad each projection to the stack-wide capacity (ragged nnzb kept)
    stacked = []
    for i in range(3):
        cap = max(p[i]["blocks"].shape[0] for p in packs)
        stacked.append([sps.pad_packed(p[i], cap) for p in packs])
    x = jnp.asarray(np.random.default_rng(9).standard_normal((1, 64)),
                    jnp.float32)
    for li, (wg, wu, wd) in enumerate((dense_l, sparse_l)):
        pg, pu, pd = (stacked[i][li] for i in range(3))
        assert int(pg["nnzb"]) < pg["blocks"].shape[0] or li == 0
        out = ops.bcsc_mlp_packed(x, pg, pu, pd, d_ff=128, n_out=64,
                                  activation="silu")
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(_ref(x, wg, wu, wd, "silu")),
                                   rtol=5e-4, atol=5e-4)


def test_pad_packed_repeats_last_ids_and_keeps_nnzb():
    wg, _, _ = _mats(64, 128, 0.8, seed=11)
    p = sps.pack_weight(wg, 16, 16)
    real = int(p["nnzb"])
    padded = sps.pad_packed(p, p["blocks"].shape[0] + 16)
    assert int(padded["nnzb"]) == real
    rows, cols = np.asarray(padded["row_ids"]), np.asarray(padded["col_ids"])
    assert (rows[real:] == rows[real - 1]).all()
    assert (cols[real:] == cols[real - 1]).all()
    assert np.asarray(padded["blocks"])[real:].sum() == 0
    assert (np.diff(cols) >= 0).all()            # CSC order preserved


# ------------------------------------------------ scratch-only hidden contract
def test_fused_mlp_hidden_never_leaves_vmem():
    """The megakernel's only HBM output is the (M, n_out) result: no
    d_ff-sized buffer appears among pallas_call outputs, and the whole MLP is
    ONE pallas_call (vs three on the two-call path)."""
    cfg = get_config("qwen2.5-3b-reduced")
    wg, wu, wd = _mats(cfg.d_model, cfg.d_ff, 0.75, seed=13)
    mlp_params = {"wg": sps.pack_weight(wg, 16, 16),
                  "wu": sps.pack_weight(wu, 16, 16),
                  "wd": sps.pack_weight(wd, 16, 16)}
    x = jnp.ones((1, 1, cfg.d_model), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(lambda p, xx: layers.mlp(p, xx, cfg))(mlp_params, x)
    calls = [e for e in jaxpr.jaxpr.eqns if "pallas" in str(e.primitive)]
    assert len(calls) == 1                       # megakernel: one fused call
    for v in calls[0].outvars:
        assert cfg.d_ff not in v.aval.shape      # hidden never aliased to HBM


# ------------------------------------------------------------- dispatch rule
def test_mlp_path_dispatch_rule():
    # decode shapes with modest hidden: fused (scratch fits)
    assert dataflow.mlp_path(1, 4096, 1024) == "fused"
    assert dataflow.mlp_path(8, 11008, 2048) == "fused"
    # huge M: bm grows until the hidden scratch cannot stay resident
    assert dataflow.mlp_path(512, 11008, 2048) == "two_call"
    # near-dense blocks: skipping cannot pay — stay dense
    assert dataflow.mlp_path(1, 4096, 1024, density=0.95) == "dense"
    assert dataflow.mlp_path(1, 4096, 1024,
                             density=dataflow.DENSE_BLOCK_DENSITY) == "dense"
    assert dataflow.mlp_path(1, 4096, 1024, density=0.5) == "fused"


def test_sparsify_leaves_near_dense_weights_unpacked():
    cfg = get_config("qwen2.5-3b-reduced")
    from repro.models import transformer as tfm
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    packed, stats = sps.sparsify_mlp_params(params, cfg, sparsity=0.0)
    # unpruned weights are block-dense -> the dense arm of mlp_path
    assert stats["packed"] == 0
    assert set(stats["left_dense"]) == {"wg", "wu", "wd"}
    for slot in packed["blocks"]:
        mlp = packed["blocks"][slot]["mlp"]
        assert all(not ops.is_packed(mlp[k]) for k in ("wg", "wu", "wd"))


# ----------------------------------------------------- packing stats contract
def _pruned_packed_cfg(sparsity=0.75):
    cfg = get_config("qwen2.5-3b-reduced")
    from repro.models import transformer as tfm
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    for slot in params["blocks"]:
        mlp = params["blocks"][slot].get("mlp")
        if mlp:
            for nm in list(mlp):
                w = mlp[nm]
                mlp[nm] = jnp.stack([
                    block_magnitude_prune(w[l], sparsity, 16, 16)
                    for l in range(w.shape[0])])
    packed, stats = sps.sparsify_mlp_params(params, cfg, sparsity=0.0)
    return cfg, params, packed, stats


def test_packing_efficiency_stats():
    cfg, _, packed, stats = _pruned_packed_cfg()
    assert stats["packed"] == 3
    assert 0 < stats["packing_efficiency"] <= 1
    for nm, w in stats["weights"].items():
        assert len(w["real"]) == cfg.num_layers
        assert all(r <= p for r, p in zip(w["real"], w["padded"]))
        assert w["packing_efficiency"] == pytest.approx(
            sum(w["real"]) / sum(w["padded"]))
    # pack-time prepared counts ride the params pytree, one (3,) per layer
    mlp0 = packed["blocks"]["slot0"]["mlp"]
    counts = np.asarray(mlp0["_bcsc_counts"])
    assert counts.shape[-1] == 3
    np.testing.assert_array_equal(counts[..., 0],
                                  np.asarray(mlp0["wg"]["nnzb"]))


# -------------------------------------------- wall-clock-free perf guards
def _load_bench():
    import importlib.util
    import os
    bench_path = os.path.join(os.path.dirname(__file__), os.pardir,
                              "benchmarks", "sparse_decode.py")
    spec = importlib.util.spec_from_file_location(
        "sparse_decode_bench", bench_path)
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    return bench


def test_fused_proxies_beat_two_call_at_075():
    """Acceptance (ISSUE 2): fused grid steps <= two-call grid steps and the
    HBM-bytes-moved proxy strictly decreases, at 0.75 sparsity — enforceable
    in interpret mode on CPU (no wall clock)."""
    bench = _load_bench()
    _, _, _, stats = _pruned_packed_cfg(0.75)
    mp = bench.mlp_proxy(sparsity=0.75, stats=stats)
    assert mp["fused"]["grid_steps"] <= mp["two_call"]["grid_steps"]
    assert mp["fused"]["hbm_bytes"] < mp["two_call"]["hbm_bytes"]
    assert mp["fused"]["kernel_launches"] < mp["two_call"]["kernel_launches"]
    assert mp["fused"]["block_visits"] <= mp["two_call"]["block_visits"]
    assert mp["mixed_density"] is False       # bench config packs uniformly


def test_mlp_proxy_guards_mixed_density_archs():
    """ROADMAP latent bug (from PR 2): sparsify_mlp_params can route a
    weight dense in one layer group and packed in another, leaving
    stats["weights"][name] lists of UNEQUAL lengths. mlp_proxy must count
    only the projections packed in each layer instead of IndexError-ing."""
    bench = _load_bench()
    stats = {
        "block_density": 0.4, "packing_efficiency": 0.9,
        "weights": {
            "wg": {"real": [4, 4], "padded": [8, 8],
                   "packing_efficiency": 0.5, "dense_blocks": 16},
            "wu": {"real": [4, 4], "padded": [8, 8],
                   "packing_efficiency": 0.5, "dense_blocks": 16},
            # left dense in the second layer group: one entry only
            "wd": {"real": [4], "padded": [8],
                   "packing_efficiency": 0.5, "dense_blocks": 16},
        },
    }
    mp = bench.mlp_proxy(stats=stats)         # must not raise
    assert mp["mixed_density"] is True
    assert mp["two_call"]["grid_steps"] > 0
    assert mp["fused"]["block_visits"] <= mp["two_call"]["block_visits"]


def test_serve_equivalence_fused_vs_dense():
    """Full serve path: packed (fused megakernel) params produce the same
    logits as the dense pruned params — prefill and decode."""
    from repro.models import decoding
    cfg, pruned, packed, _ = _pruned_packed_cfg()
    toks = jnp.asarray([[5, 6, 7, 8]], jnp.int32)
    l_d, c_d = decoding.prefill(pruned, toks, cfg, 32)
    l_s, c_s = decoding.prefill(packed, toks, cfg, 32)
    np.testing.assert_allclose(np.asarray(l_d), np.asarray(l_s),
                               rtol=1e-2, atol=1e-2)
    nxt = jnp.argmax(l_d[:, -1], -1)[:, None]
    ld2, _ = decoding.serve_step(pruned, c_d, nxt, jnp.int32(4), cfg)
    ls2, _ = decoding.serve_step(packed, c_s, nxt, jnp.int32(4), cfg)
    np.testing.assert_allclose(np.asarray(ld2), np.asarray(ls2),
                               rtol=1e-2, atol=1e-2)
