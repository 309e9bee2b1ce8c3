"""The weights every block shares: ``make_params`` makes them from the seed
on the device, in one jitted call, in bf16 as they are served, in the
layout the program reads (its abstract parameter tree). What a block adds
(its sizes, its ``ArchConfig`` and the reference's view of these arrays)
lives in ``bench/blocks/<block>.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

NORM_OFFSET_STD = 0.05       # norm gains 1 + N(0, 0.05): the gain is used


def _path_str(path) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in path)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _init(key, shapes, vocab: int, init_range: float):
    leaves = []
    keys = jax.random.split(key, len(shapes))
    for k, (path, shape, dtype) in zip(keys, shapes):
        z = jax.random.normal(k, shape, jnp.float32)
        x = z * (NORM_OFFSET_STD if "norm" in path else init_range)
        if path.split("/")[0] in ("embed", "lm_head"):
            # rows (or columns) past the vocabulary pad the table
            ax = 0 if path.startswith("embed") else 1
            idx = jnp.arange(shape[ax]).reshape(
                [-1 if i == ax else 1 for i in range(len(shape))])
            x = jnp.where(idx < vocab, x, 0.0)
        leaves.append(x.astype(dtype))
    return leaves


def make_params(arch, seed_key, init_range: float):
    """Weights of ``arch`` from ``seed_key``, on the device, in one call:
    matrices and biases N(0, init_range) (the source's initializer range),
    norm gains 1 + N(0, 0.05), pad rows of the vocabulary 0."""
    from repro.models import transformer as tfm
    abstract = jax.eval_shape(lambda: tfm.init_params(
        jax.random.PRNGKey(0), arch))
    flat, tree = jax.tree_util.tree_flatten_with_path(abstract)
    shapes = tuple((_path_str(p), tuple(l.shape), np.dtype(l.dtype))
                   for p, l in flat)
    leaves = _init(seed_key, shapes, arch.vocab_size, float(init_range))
    return jax.tree_util.tree_unflatten(tree, leaves)
