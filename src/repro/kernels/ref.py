"""Pure-jnp oracles for every Pallas kernel (tests assert_allclose against
these across shape/dtype sweeps)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def block_sparse_matmul(x: jnp.ndarray, w: jnp.ndarray, mask: jnp.ndarray,
                        block_k: int, block_n: int) -> jnp.ndarray:
    """y = x @ (w * expand(mask)).  mask: (K//bk, N//bn) 0/1."""
    k, n = w.shape
    em = jnp.repeat(jnp.repeat(mask, block_k, axis=0), block_n, axis=1)
    em = em[:k, :n].astype(w.dtype)
    return jnp.dot(x.astype(jnp.float32), (w * em).astype(jnp.float32)
                   ).astype(x.dtype)


def block_sparse_matmul_t(x: jnp.ndarray, w: jnp.ndarray, mask: jnp.ndarray,
                          block_k: int, block_n: int) -> jnp.ndarray:
    """y = x @ (w * expand(mask))^T — the pruned backward product.
    x: (M, N), w: (K, N), mask: (K//bk, N//bn) 0/1; returns (M, K)."""
    k, n = w.shape
    em = jnp.repeat(jnp.repeat(mask, block_k, axis=0), block_n, axis=1)
    em = em[:k, :n].astype(w.dtype)
    return jnp.dot(x.astype(jnp.float32),
                   (w * em).astype(jnp.float32).T).astype(x.dtype)


def block_norms(w: jnp.ndarray, block_k: int, block_n: int) -> jnp.ndarray:
    """Squared L2 norm of every (block_k x block_n) tile. w: (K, N), K,N
    divisible by the block sizes."""
    k, n = w.shape
    t = w.astype(jnp.float32).reshape(k // block_k, block_k,
                                      n // block_n, block_n)
    return jnp.sum(t * t, axis=(1, 3))


def prefill_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                      causal: bool = True, window: int | None = None,
                      t_valid: int | None = None,
                      scale: float | None = None,
                      head_mask: jnp.ndarray | None = None) -> jnp.ndarray:
    """Full-sequence GQA attention oracle.

    q: (B, S, H, hd); k, v: (B, Hkv, T, hd).  Query i sits at absolute
    position i; keys at 0..T-1.  ``head_mask`` (Hkv,) zeros the output of
    dead KV heads (the lossless block-pruned-serving skip — see
    decode_attention.py).  Returns (B, S, H, hd) float32.
    """
    b, s, h, hd = q.shape
    hkv, t = k.shape[1], k.shape[2]
    g = h // hkv
    t_valid = t if t_valid is None else t_valid
    scale = hd ** -0.5 if scale is None else scale
    qg = q.reshape(b, s, hkv, g, hd).astype(jnp.float32)
    scores = jnp.einsum("bskgd,bktd->bskgt", qg,
                        k.astype(jnp.float32)) * scale
    qpos = jnp.arange(s)[:, None]
    kpos = jnp.arange(t)[None, :]
    valid = kpos < t_valid
    if causal:
        valid = valid & (kpos <= qpos)
    if window is not None:
        valid = valid & (kpos > qpos - window)
    scores = jnp.where(valid[None, :, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bskgt,bktd->bskgd", probs, v.astype(jnp.float32))
    if head_mask is not None:
        live = (jnp.asarray(head_mask) > 0).astype(jnp.float32)
        out = out * live[None, None, :, None, None]
    return out.reshape(b, s, h, hd)


def decode_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     pos: jnp.ndarray, window: int | None = None,
                     scale: float | None = None,
                     head_mask: jnp.ndarray | None = None) -> jnp.ndarray:
    """One-token GQA decode.

    q: (B, H, hd); k, v: (B, Hkv, S, hd); pos: (B,) absolute position of
    the query token (keys at indices <= pos are valid, and > pos - window
    if windowed).  ``head_mask`` (Hkv,) zeros the output of dead KV heads.
    Returns (B, H, hd) float32.
    """
    b, h, hd = q.shape
    hkv, s = k.shape[1], k.shape[2]
    g = h // hkv
    scale = hd ** -0.5 if scale is None else scale
    qg = q.reshape(b, hkv, g, hd).astype(jnp.float32)
    scores = jnp.einsum("bkgd,bksd->bkgs", qg,
                        k.astype(jnp.float32)) * scale
    kpos = jnp.arange(s)[None, :]
    valid = kpos <= pos[:, None]
    if window is not None:
        valid &= kpos > (pos[:, None] - window)
    scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgs,bksd->bkgd", probs, v.astype(jnp.float32))
    if head_mask is not None:
        live = (jnp.asarray(head_mask) > 0).astype(jnp.float32)
        out = out * live[None, :, None, None]
    return out.reshape(b, h, hd)
