"""Training step + loop: microbatched gradient accumulation, remat policies,
donated buffers. The returned step is a pure function suitable for pjit with
the autoshard in/out shardings.

Compute/communication overlap: with ``microbatches > 1`` the gradient
accumulation scan lets XLA's latency-hiding scheduler overlap microbatch i's
FSDP all-gathers / grad reduce-scatters with microbatch i±1's compute —
the structural enabler for the paper's "hide NoC time under MAC time".
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import transformer as tfm
from repro.train import optimizer as opt_lib


def _split_microbatches(batch, k: int):
    def r(x):
        assert x.shape[0] % k == 0, (x.shape, k)
        return x.reshape((k, x.shape[0] // k) + x.shape[1:])
    return jax.tree.map(r, batch)


def make_loss_fn(cfg, remat_policy: str, hints=None):
    def loss_fn(params, batch):
        return tfm.loss_fn(params, batch, cfg, remat_policy=remat_policy,
                           hints=hints)
    return loss_fn


def make_train_step(cfg, opt_cfg: opt_lib.OptimizerConfig,
                    remat_policy: str = "dots",
                    microbatches: int = 1, hints=None) -> Callable:
    """Returns train_step(params, opt_state, batch) -> (params, opt_state,
    metrics)."""
    loss_fn = make_loss_fn(cfg, remat_policy, hints)
    vg = jax.value_and_grad(loss_fn, has_aux=True)

    def train_step(params, opt_state, batch):
        if microbatches == 1:
            (loss, metrics), grads = vg(params, batch)
        else:
            mbs = _split_microbatches(batch, microbatches)

            def mb_step(carry, mb):
                g_acc, l_acc = carry
                (loss, metrics), g = vg(params, mb)
                g_acc = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), g_acc, g)
                return (g_acc, l_acc + loss), metrics

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (grads, loss), metrics_seq = jax.lax.scan(
                mb_step, (g0, jnp.zeros(())), mbs)
            grads = jax.tree.map(lambda g: g / microbatches, grads)
            loss = loss / microbatches
            metrics = jax.tree.map(lambda m: jnp.mean(m), metrics_seq)
        params, opt_state, om = opt_lib.adamw_update(
            opt_cfg, params, grads, opt_state)
        metrics = {**metrics, **om, "loss_total": loss}
        return params, opt_state, metrics

    return train_step


def make_eval_step(cfg) -> Callable:
    loss_fn = make_loss_fn(cfg, remat_policy="none")

    def eval_step(params, batch):
        loss, metrics = loss_fn(params, batch)
        return {**metrics, "loss_total": loss}

    return eval_step


def init_train_state(rng, cfg) -> Tuple[dict, opt_lib.AdamWState]:
    # fp32 master weights: a bf16 parameter would round away updates
    # smaller than its last bit; the layers cast to bf16 at each use
    params = jax.tree.map(lambda p: p.astype(jnp.float32),
                          tfm.init_params(rng, cfg))
    return params, opt_lib.init_adamw(params)


def abstract_train_state(cfg):
    return jax.eval_shape(lambda: init_train_state(jax.random.PRNGKey(0), cfg))
