"""Atomic, mesh-agnostic checkpointing with elastic restore.

 * **Atomic**: state is written to ``step_XXXX.tmp/`` then os.rename'd —
   a crash mid-write can never corrupt the latest checkpoint.
 * **Mesh-agnostic**: leaves are stored by *logical* shape (npz per leaf,
   flattened path → file). Restore device_puts each leaf against whatever
   shardings the *current* mesh/plan dictates — a checkpoint written on
   2×16×16 restores onto 16×16 (or a degraded 2×15×16 replacement mesh)
   without conversion. This is the elastic-scaling path (runtime.elastic).
 * **Retention**: keep the newest ``keep`` checkpoints.
"""
from __future__ import annotations

import json
import os
import re
import shutil
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np


_SEP = "__"


def _entry_name(e) -> str:
    """Path-entry name for DictKey/SequenceKey/GetAttrKey/FlattenedIndexKey."""
    for attr in ("key", "idx", "name"):
        if hasattr(e, attr):
            return str(getattr(e, attr))
    return str(e)


def _flatten(tree) -> Dict[str, np.ndarray]:
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = _SEP.join(_entry_name(p) for p in path)
        flat[key] = np.asarray(leaf)
    return flat


class CheckpointManager:
    def __init__(self, directory: str, keep: int = 3):
        self.directory = directory
        self.keep = keep
        os.makedirs(directory, exist_ok=True)

    # ------------------------------------------------------------------ save
    def save(self, step: int, state: Any, extra: Optional[Dict] = None) -> str:
        final = os.path.join(self.directory, f"step_{step:08d}")
        tmp = final + ".tmp"
        if os.path.exists(tmp):
            shutil.rmtree(tmp)
        os.makedirs(tmp)
        flat = _flatten(state)
        np.savez(os.path.join(tmp, "arrays.npz"), **flat)
        manifest = {
            "step": step,
            "time": time.time(),
            "keys": sorted(flat.keys()),
            "shapes": {k: list(v.shape) for k, v in flat.items()},
            "dtypes": {k: str(v.dtype) for k, v in flat.items()},
            "extra": extra or {},
        }
        with open(os.path.join(tmp, "manifest.json"), "w") as f:
            json.dump(manifest, f)
        f_dir = os.open(tmp, os.O_RDONLY)
        try:
            os.fsync(f_dir)
        finally:
            os.close(f_dir)
        if os.path.exists(final):
            shutil.rmtree(final)
        os.rename(tmp, final)
        self._gc()
        return final

    # ----------------------------------------------------------------- load
    def steps(self) -> List[int]:
        out = []
        for name in os.listdir(self.directory):
            m = re.fullmatch(r"step_(\d+)", name)
            if m:
                out.append(int(m.group(1)))
        return sorted(out)

    def latest_step(self) -> Optional[int]:
        s = self.steps()
        return s[-1] if s else None

    def restore(self, target: Any, step: Optional[int] = None,
                shardings=None) -> Tuple[Any, Dict]:
        """Restore into the structure of ``target`` (values ignored; may be
        ShapeDtypeStructs). ``shardings``: optional congruent pytree of
        NamedShardings for the *current* mesh (elastic re-shard)."""
        step = self.latest_step() if step is None else step
        assert step is not None, "no checkpoint found"
        path = os.path.join(self.directory, f"step_{step:08d}")
        with open(os.path.join(path, "manifest.json")) as f:
            manifest = json.load(f)
        arrays = np.load(os.path.join(path, "arrays.npz"))

        paths, treedef = jax.tree_util.tree_flatten_with_path(target)
        shard_leaves = (jax.tree.leaves(shardings)
                        if shardings is not None else [None] * len(paths))
        leaves = []
        for (p, leaf), shd in zip(paths, shard_leaves):
            key = _SEP.join(_entry_name(e) for e in p)
            arr = arrays[key]
            # npz stores bfloat16 as opaque 2-byte records; the manifest
            # keeps the real dtype
            arr = arr.view(jax.numpy.dtype(manifest["dtypes"][key]))
            assert tuple(arr.shape) == tuple(leaf.shape), (key, arr.shape,
                                                           leaf.shape)
            if shd is not None:
                leaves.append(jax.device_put(arr, shd))
            else:
                leaves.append(jax.numpy.asarray(arr))
        return jax.tree.unflatten(treedef, leaves), manifest

    # ------------------------------------------------------------------- gc
    def _gc(self):
        steps = self.steps()
        for s in steps[:-self.keep]:
            shutil.rmtree(os.path.join(self.directory, f"step_{s:08d}"),
                          ignore_errors=True)
