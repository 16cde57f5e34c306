"""Block-sparse linear layers over the training tile-mask layout.

Every implementation consumes the same ``(Tk, Tn)`` 0/1 keep grid that
``kernels.block_sparse_matmul`` (and the fleet's fused training path)
prunes with, so a serve layer is *defined* to compute
``x @ (w ⊙ expand(keep))`` — dense-masked equivalence is the contract,
sparsity only changes the cost.

A layer splits into a static ``plan`` (python ints / numpy index arrays,
closed over by the jitted step — never traced) and a device ``arrays``
pytree (passed through jit, so weights aren't baked into the executable):

  impl="gather"   the CPU serving path.  Kept tiles are gathered once at
                  build into a (T, bk, bn) stack (weight memory ∝ 1-rho);
                  each apply gathers the matching x tiles, runs one
                  batched (T, M, bk) x (T, bk, bn) einsum, and
                  segment-sums partial products into output tiles.
                  Compute and weight traffic scale with the kept-tile
                  count — this is where the rho-proportional tokens/s
                  comes from.
  impl="cond"     per-tile ``lax.cond`` skip, the direct analogue of
                  fleet_fused's training-side tile loop.  Trace size is
                  O(Tk*Tn) per layer: debug/small-model use.
  impl="pallas"   ``ops.masked_matmul_padded`` (the Pallas kernel;
                  interpreted off-TPU) on a weight padded to the hardware
                  tiling once, at build.
  impl="dense"    masked dense matmul — the oracle and the speedup
                  baseline.

Every impl multiplies at full float32 precision (``HIGHEST``).  The TPU's
default is one bf16 pass, which re-rounds each layer's input: two impls
that sum in different orders can then drift apart layer by layer until
a greedy argmax flips, breaking the dense-masked equality.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops

IMPLS = ("gather", "cond", "pallas", "dense")
_HI = jax.lax.Precision.HIGHEST


def _masked(w: jnp.ndarray, keep: np.ndarray, bk: int, bn: int) -> jnp.ndarray:
    k, n = w.shape
    em = np.repeat(np.repeat(np.asarray(keep) > 0, bk, axis=0), bn, axis=1)
    return w * jnp.asarray(em[:k, :n], w.dtype)


def make_linear(w: jnp.ndarray, keep, blocks: tuple[int, int],
                impl: str = "gather", bias=None) -> tuple[dict, dict]:
    """Build (plan, arrays) for y = x @ (w ⊙ expand(keep)) [+ bias].

    w: (K, N); keep: (ceil(K/bk), ceil(N/bn)) 0/1; blocks: (bk, bn).
    ``keep=None`` means fully dense (unprunable layer).
    """
    if impl not in IMPLS:
        raise ValueError(f"impl must be one of {IMPLS}, got {impl!r}")
    k, n = w.shape
    bk, bn = blocks
    tk, tn = -(-k // bk), -(-n // bn)
    if keep is None:
        keep = np.ones((tk, tn), np.float32)
    keep_np = np.asarray(keep)
    if keep_np.shape != (tk, tn):
        raise ValueError(f"keep shape {keep_np.shape} != tile grid "
                         f"({tk}, {tn}) for w {w.shape} blocks {blocks}")
    w = jnp.asarray(w, jnp.float32)
    wm = _masked(w, keep_np, bk, bn)
    plan = {"impl": impl, "k": k, "n": n, "bk": bk, "bn": bn,
            "tk": tk, "tn": tn}
    arrays: dict = {}
    if bias is not None:
        arrays["b"] = jnp.asarray(bias, jnp.float32)

    if impl == "gather":
        kk, nn = np.nonzero(keep_np > 0)
        order = np.argsort(nn, kind="stable")       # group tiles by out col
        kk, nn = kk[order], nn[order]
        plan["t"] = int(kk.size)
        plan["kk"], plan["nn"] = kk.astype(np.int32), nn.astype(np.int32)
        if kk.size:
            wp = jnp.pad(wm, ((0, tk * bk - k), (0, tn * bn - n)))
            tiles = wp.reshape(tk, bk, tn, bn).transpose(0, 2, 1, 3)
            arrays["wt"] = tiles[kk, nn]            # (T, bk, bn)
    elif impl == "cond":
        arrays["w"] = jnp.pad(wm, ((0, tk * bk - k), (0, tn * bn - n)))
        arrays["keep"] = jnp.asarray(keep_np > 0)
    elif impl == "pallas":
        arrays["w"] = ops.pad_weight_tiles(wm, bk, bn)
        arrays["keep"] = jnp.asarray(keep_np, jnp.float32)
    else:                                           # dense
        arrays["w"] = wm
    return plan, arrays


def _apply_gather(plan: dict, arrays: dict, x2: jnp.ndarray) -> jnp.ndarray:
    m = x2.shape[0]
    k, n = plan["k"], plan["n"]
    bk, bn, tk, tn = plan["bk"], plan["bn"], plan["tk"], plan["tn"]
    if plan["t"] == 0:
        return jnp.zeros((m, n), jnp.float32)
    xp = jnp.pad(x2, ((0, 0), (0, tk * bk - k)))
    xt = xp.reshape(m, tk, bk)
    xg = jnp.take(xt, jnp.asarray(plan["kk"]), axis=1)      # (M, T, bk)
    prod = jnp.einsum("mtk,tkn->mtn", xg, arrays["wt"],
                      precision=_HI)                        # (M, T, bn)
    y = jax.ops.segment_sum(prod.swapaxes(0, 1),
                            jnp.asarray(plan["nn"]), num_segments=tn,
                            indices_are_sorted=True)        # (Tn, M, bn)
    return y.transpose(1, 0, 2).reshape(m, tn * bn)[:, :n]


def _apply_cond(plan: dict, arrays: dict, x2: jnp.ndarray) -> jnp.ndarray:
    m = x2.shape[0]
    k, n = plan["k"], plan["n"]
    bk, bn, tk, tn = plan["bk"], plan["bn"], plan["tk"], plan["tn"]
    xp = jnp.pad(x2, ((0, 0), (0, tk * bk - k)))
    w, keep = arrays["w"], arrays["keep"]
    cols = []
    for tj in range(tn):
        acc = jnp.zeros((m, bn), jnp.float32)
        for ti in range(tk):
            xt = jax.lax.dynamic_slice_in_dim(xp, ti * bk, bk, 1)
            wt = jax.lax.dynamic_slice(w, (ti * bk, tj * bn), (bk, bn))

            def dot(acc, xt=xt, wt=wt):
                return acc + jnp.dot(xt, wt, precision=_HI)

            acc = jax.lax.cond(keep[ti, tj], dot, lambda a: a, acc)
        cols.append(acc)
    return jnp.concatenate(cols, axis=1)[:, :n]


def apply_linear(plan: dict, arrays: dict, x: jnp.ndarray) -> jnp.ndarray:
    """y = x @ (w ⊙ expand(keep)) [+ bias]; x: (..., K) -> (..., N), f32."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, plan["k"]).astype(jnp.float32)
    impl = plan["impl"]
    if impl == "gather":
        y = _apply_gather(plan, arrays, x2)
    elif impl == "cond":
        y = _apply_cond(plan, arrays, x2)
    elif impl == "pallas":
        y = ops.masked_matmul_padded(x2, arrays["w"], arrays["keep"],
                                     plan["n"], block_k=plan["bk"],
                                     block_n=plan["bn"])
    else:
        y = jnp.dot(x2, arrays["w"], precision=_HI)
    if "b" in arrays:
        y = y + arrays["b"]
    return y.reshape(*lead, plan["n"])
