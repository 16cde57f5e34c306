"""Minimal pytree checkpointing: flatten-by-path -> compressed .npz.

No external deps (orbax unavailable offline); good enough for paper-scale
runs and example drivers, and layout-stable across sessions.
"""

from __future__ import annotations

import os
from typing import Any

import jax
import numpy as np

PyTree = Any
_SEP = "\x1f"  # unit separator: never appears in sane key names


def _flatten(tree: PyTree) -> dict[str, np.ndarray]:
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = _SEP.join(str(getattr(p, "key", getattr(p, "idx", p)))
                        for p in path)
        flat[key] = np.asarray(leaf)
    return flat


def save(path: str, tree: PyTree) -> None:
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    np.savez_compressed(path, **_flatten(tree))


def restore_flat(path: str) -> dict[str, np.ndarray]:
    """Raw path-keyed view of a checkpoint: ``{"a/b/c": array, ...}``.

    For readers that need keys the writer's ``like`` tree can't predict
    (e.g. the serve loader's per-leaf tile keeps, whose count and shapes
    live *in* the file).  Keys join the pytree path with "/"."""
    with np.load(path) as data:
        return {k.replace(_SEP, "/"): v for k, v in dict(data).items()}


def restore(path: str, like: PyTree) -> PyTree:
    """Restore into the structure of ``like`` (shapes/dtypes preserved)."""
    with np.load(path) as data:
        flat = dict(data)
    paths, treedef = jax.tree_util.tree_flatten_with_path(like)
    leaves = []
    for path, leaf in paths:
        key = _SEP.join(str(getattr(p, "key", getattr(p, "idx", p)))
                        for p in path)
        if key not in flat:
            raise KeyError(f"checkpoint missing leaf {key!r}")
        arr = flat[key]
        if arr.shape != leaf.shape:
            raise ValueError(f"shape mismatch for {key!r}: "
                             f"{arr.shape} vs {leaf.shape}")
        if arr.dtype.kind == "V":
            # .npy cannot name extension dtypes such as bfloat16 and
            # stores their raw bytes; reinterpret them as the leaf's dtype
            arr = arr.view(leaf.dtype)
        leaves.append(arr.astype(leaf.dtype))
    return jax.tree_util.tree_unflatten(treedef, leaves)
