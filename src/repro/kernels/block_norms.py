"""Pallas TPU kernel: per-tile squared L2 norms (mask generation input).

Reduces each (block_k x block_n) weight tile to one float32 — the ranking
statistic for block-structured magnitude pruning.  Grid: one step per
tile; the reduction runs on the VPU entirely out of VMEM.  Each step
writes its norm over one whole (8, 128) output tile (the smallest block
the TPU lowering accepts); the wrapper keeps element [0, 0] of each.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


_OUT_TILE = (8, 128)


def _kernel(w_ref, o_ref):
    t = w_ref[...].astype(jnp.float32)
    o_ref[...] = jnp.full(o_ref.shape, jnp.sum(t * t), jnp.float32)


@functools.partial(jax.jit, static_argnames=("block_k", "block_n",
                                             "interpret"))
def block_norms(w: jnp.ndarray, block_k: int, block_n: int, *,
                interpret: bool) -> jnp.ndarray:
    """w: (K, N) with K % block_k == 0 and N % block_n == 0 (ops.py pads).
    Returns (K//block_k, N//block_n) float32 squared norms."""
    k, n = w.shape
    tk, tn = k // block_k, n // block_n
    ok, on = _OUT_TILE
    out = pl.pallas_call(
        _kernel,
        grid=(tk, tn),
        in_specs=[pl.BlockSpec((block_k, block_n), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((ok, on), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((tk * ok, tn * on), jnp.float32),
        interpret=interpret,
    )(w)
    return out[::ok, ::on]
