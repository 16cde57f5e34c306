"""Pallas TPU kernel: block-sparse matmul for pruned weights.

y = x @ (W ⊙ M) — or, with ``transpose_rhs``, y = x @ (W ⊙ M)^T — where
M is a (K/bk, N/bn) block mask from block-structured magnitude pruning
(core/pruning.py).  The mask rides in scalar-prefetch (SMEM): each grid
step predicates its MXU dot on ``mask[k, n]``, so a pruning rate rho
skips rho of the (bm x bk x bn) passes — the compute-side realization of
the paper's (1 - rho) latency model.  The transposed variant is the
backward product of a pruned layer (dz @ (W ⊙ M)^T with the *same* mask
layout), so forward and backward share one mask array.

Grid: (M/bm, N/bn, K/bk) with the contraction innermost so the f32
accumulator lives in the output block across the sequential sweep
(contraction = K forward, N transposed).

TPU notes: block sizes default to (128, 128, 128) — MXU-aligned; the
accumulator is float32 regardless of input dtype, and the dots run at
full float32 precision (``HIGHEST``; a bf16 input is one pass anyway).
DMA for masked-off blocks is not elided (the BlockSpec still maps them in); a compacted
weight layout that skips the DMA too is recorded as a §Perf follow-up.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HI = jax.lax.Precision.HIGHEST

def _kernel(mask_ref, x_ref, w_ref, o_ref, acc_ref, *, n_k: int):
    k = pl.program_id(2)
    n = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(mask_ref[k, n] != 0)
    def _compute():
        acc_ref[...] += jnp.dot(x_ref[...], w_ref[...], precision=_HI,
                                preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _kernel_t(mask_ref, x_ref, w_ref, o_ref, acc_ref, *, n_n: int):
    """Transposed-RHS variant: grid (M/bm, K/bk, N/bn), N innermost is the
    contraction; the dot is x_tile @ w_tile^T and the predicate reads the
    same (K/bk, N/bn) mask at [k, n]."""
    n = pl.program_id(2)
    k = pl.program_id(1)

    @pl.when(n == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(mask_ref[k, n] != 0)
    def _compute():
        acc_ref[...] += jnp.dot(x_ref[...], w_ref[...].T, precision=_HI,
                                preferred_element_type=jnp.float32)

    @pl.when(n == n_n - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_m", "block_k", "block_n",
                                    "transpose_rhs", "interpret"))
def block_sparse_matmul(x: jnp.ndarray, w: jnp.ndarray, mask: jnp.ndarray,
                        block_m: int = 128, block_k: int = 128,
                        block_n: int = 128, transpose_rhs: bool = False, *,
                        interpret: bool) -> jnp.ndarray:
    """Block-masked matmul; ``mask``: (K//block_k, N//block_n) int32/bool.

    Forward (default): x: (M, K), w: (K, N) -> (M, N).
    ``transpose_rhs``:  x: (M, N), w: (K, N) -> (M, K) — the pruned
    layer's backward product, reusing the forward's mask layout.

    All dims must be divisible by their block sizes (ops.py pads).  On
    the TPU ``block_m`` must be a multiple of 8 and ``block_k`` /
    ``block_n`` multiples of 128 (ops.py pads every tile up to that).
    """
    m = x.shape[0]
    kdim, n = w.shape
    if transpose_rhs:
        n_n = n // block_n
        grid = (m // block_m, kdim // block_k, n_n)
        out = pl.pallas_call(
            functools.partial(_kernel_t, n_n=n_n),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=grid,
                in_specs=[
                    pl.BlockSpec((block_m, block_n),
                                 lambda i, j, k, *_: (i, k)),
                    pl.BlockSpec((block_k, block_n),
                                 lambda i, j, k, *_: (j, k)),
                ],
                out_specs=pl.BlockSpec((block_m, block_k),
                                       lambda i, j, k, *_: (i, j)),
                scratch_shapes=[pltpu.VMEM((block_m, block_k), jnp.float32)],
            ),
            out_shape=jax.ShapeDtypeStruct((m, kdim), x.dtype),
            interpret=interpret,
        )(mask.astype(jnp.int32), x, w)
        return out
    n_k = kdim // block_k
    grid = (m // block_m, n // block_n, n_k)
    out = pl.pallas_call(
        functools.partial(_kernel, n_k=n_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_m, block_k), lambda i, j, k, *_: (i, k)),
                pl.BlockSpec((block_k, block_n), lambda i, j, k, *_: (k, j)),
            ],
            out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k, *_: (i, j)),
            scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        interpret=interpret,
    )(mask.astype(jnp.int32), x, w)
    return out
