"""Pallas TPU kernel: full-sequence GQA flash attention (prefill/train).

This is the fused kernel EXPERIMENTS.md §Roofline calls for: the pure-JAX
chunked path (models/attention.flash_attention) is what the SPMD dry-run
lowers — correct and shardable — but XLA materializes its per-chunk score
blocks in HBM.  Here the (block_q x block_s) score/probability tiles
live entirely in VMEM scratch: HBM traffic drops to the q/k/v/o stream,
which is the roofline floor for attention.

Grid: (B, Hkv, S/block_q, T/block_s) — the KV sweep is the innermost
(sequential) axis, so the online-softmax state (m, l, acc) persists in
VMEM scratch across it (same convention as decode_attention.py).  All
G = H/Hkv query heads of one KV head share each fetched K/V block.  K/V
come head-major, (B, Hkv, T, hd) — the serving KV-cache layout, so the
keys written to the cache are the keys read here — and the wrapper lays
q out as (B, Hkv, S*G, hd), row s*G + g, so every block's last two dims
are (rows, hd) as the TPU lowering wants.  That q relayout (and its
inverse on the output) is activation-sized and runs once per prefill.
Every dot runs at full float32 precision (``HIGHEST``), as in
decode_attention.py.

Causality prunes whole (q, k) block pairs via @pl.when before any MXU
work; sliding windows prune from the other side.

Mask-aware serving (PR 9): ``head_mask`` marks the live KV heads of a
block-pruned model (see decode_attention.py for why skipping a dead head
is lossless).  It rides scalar prefetch and joins the @pl.when block-skip
predicate; ``flash_prefill_xla`` is the tile-loop twin whose causal /
head skips are resolved at trace time (the CPU serving path).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30
_HI = jax.lax.Precision.HIGHEST


def _kernel(hm_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            block_q: int, block_s: int, n_k: int, causal: bool,
            window, t_valid: int, scale: float):
    h = pl.program_id(1)
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_lo = qi * block_q
    k_lo = ki * block_s
    # block-level pruning: pruned KV head -> the whole sweep is dead;
    # causal -> skip blocks fully above the diagonal; window -> skip
    # blocks fully left of the window; ragged T -> skip blocks past the
    # valid key length
    live = jnp.logical_and(hm_ref[h] > 0, k_lo < t_valid)
    if causal:
        live = jnp.logical_and(live, k_lo <= q_lo + block_q - 1)
    if window is not None:
        live = jnp.logical_and(
            live, k_lo + block_s - 1 > q_lo - window)

    @pl.when(live)
    def _compute():
        g = q_ref.shape[2] // block_q
        q = q_ref[0, 0].astype(jnp.float32)                  # (bq*G, hd)
        k = k_ref[0, 0].astype(jnp.float32)                  # (bs, hd)
        v = v_ref[0, 0].astype(jnp.float32)                  # (bs, hd)
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), precision=_HI,
            preferred_element_type=jnp.float32) * scale

        rows = jax.lax.broadcasted_iota(jnp.int32, (block_q * g, block_s), 0)
        qpos = q_lo + rows // g
        kpos = k_lo + jax.lax.broadcasted_iota(
            jnp.int32, (block_q * g, block_s), 1)
        valid = kpos < t_valid
        if causal:
            valid = jnp.logical_and(valid, kpos <= qpos)
        if window is not None:
            valid = jnp.logical_and(valid, kpos > qpos - window)
        scores = jnp.where(valid, scores, _NEG)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, -1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + \
            jnp.dot(p, v, precision=_HI, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == n_k - 1)
    def _flush():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "block_q", "block_s", "causal", "window", "t_valid", "interpret"))
def flash_prefill(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                  block_q: int = 256, block_s: int = 512,
                  causal: bool = True, window: int | None = None,
                  t_valid: int | None = None,
                  head_mask: jnp.ndarray | None = None, *,
                  interpret: bool) -> jnp.ndarray:
    """q: (B, S, H, hd); k, v: (B, Hkv, T, hd).  Returns (B, S, H, hd)
    float32.  S % block_q == 0 and T % block_s == 0 (ops.py pads);
    ``t_valid`` masks padded keys (defaults to T).  ``head_mask``:
    optional (Hkv,) live-head indicators; dead heads output zeros."""
    b, s, h, hd = q.shape
    hkv, t = k.shape[1], k.shape[2]
    g = h // hkv
    n_q, n_k = s // block_q, t // block_s
    t_valid = t if t_valid is None else t_valid
    scale = hd ** -0.5
    qg = q.reshape(b, s, hkv, g, hd).transpose(0, 2, 1, 3, 4) \
        .reshape(b, hkv, s * g, hd)
    hm = jnp.ones((hkv,), jnp.int32) if head_mask is None \
        else (jnp.asarray(head_mask) > 0).astype(jnp.int32)
    out = pl.pallas_call(
        functools.partial(_kernel, block_q=block_q, block_s=block_s,
                          n_k=n_k, causal=causal, window=window,
                          t_valid=t_valid, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, hkv, n_q, n_k),
            in_specs=[
                pl.BlockSpec((1, 1, block_q * g, hd),
                             lambda b_, h_, q_, k_, *_: (b_, h_, q_, 0)),
                pl.BlockSpec((1, 1, block_s, hd),
                             lambda b_, h_, q_, k_, *_: (b_, h_, k_, 0)),
                pl.BlockSpec((1, 1, block_s, hd),
                             lambda b_, h_, q_, k_, *_: (b_, h_, k_, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, block_q * g, hd),
                                   lambda b_, h_, q_, k_, *_: (b_, h_, q_, 0)),
            scratch_shapes=[
                pltpu.VMEM((block_q * g, 1), jnp.float32),
                pltpu.VMEM((block_q * g, 1), jnp.float32),
                pltpu.VMEM((block_q * g, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, s * g, hd), jnp.float32),
        interpret=interpret,
    )(hm, qg, k, v)
    return out.reshape(b, hkv, s, g, hd).transpose(0, 2, 1, 3, 4) \
        .reshape(b, s, h, hd)


def flash_prefill_xla(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                      block_q: int = 256, block_s: int = 512,
                      causal: bool = True, window: int | None = None,
                      t_valid: int | None = None,
                      head_mask=None) -> jnp.ndarray:
    """XLA tile-loop twin of ``flash_prefill``: the (q block, k block)
    sweep is a python loop whose causal / window / ragged-T / head skips
    are *static* — dead block pairs and statically dead KV heads never
    enter the trace, so prefill compute scales with the live fraction.
    A traced ``head_mask`` degrades to a per-head ``lax.cond``.  Ragged S
    and T are sliced short (no padding needed).  Layouts as in
    ``flash_prefill``."""
    b, s, h, hd = q.shape
    hkv, t = k.shape[1], k.shape[2]
    g = h // hkv
    scale = hd ** -0.5
    block_q = min(block_q, s)
    block_s = min(block_s, t)
    n_q, n_k = -(-s // block_q), -(-t // block_s)
    t_valid = t if t_valid is None else t_valid
    qg = q.reshape(b, s, hkv, g, hd).astype(jnp.float32)
    static_hm = head_mask is None or isinstance(head_mask, np.ndarray)
    heads = []
    for hi in range(hkv):
        if static_hm and head_mask is not None \
                and not bool(head_mask[hi] > 0):
            heads.append(jnp.zeros((b, s, g, hd), jnp.float32))
            continue
        q_blocks = []
        for qi in range(n_q):
            q_lo, q_hi = qi * block_q, min(s, (qi + 1) * block_q)
            qb = qg[:, q_lo:q_hi, hi]                        # (B, bq, G, hd)
            m = jnp.full((b, q_hi - q_lo, g, 1), _NEG, jnp.float32)
            l = jnp.zeros((b, q_hi - q_lo, g, 1), jnp.float32)
            acc = jnp.zeros((b, q_hi - q_lo, g, hd), jnp.float32)
            carry = (m, l, acc)
            for ki in range(n_k):
                k_lo, k_hi = ki * block_s, min(t, (ki + 1) * block_s)
                live = k_lo < t_valid
                if causal:
                    live = live and (k_lo <= q_hi - 1)
                if window is not None:
                    live = live and (k_hi - 1 > q_lo - window)
                if not live:
                    continue
                kb = k[:, hi, k_lo:k_hi].astype(jnp.float32)
                vb = v[:, hi, k_lo:k_hi].astype(jnp.float32)

                def upd(carry, kb=kb, vb=vb, k_lo=k_lo, k_hi=k_hi,
                        q_lo=q_lo, q_hi=q_hi, qb=qb):
                    m, l, acc = carry
                    scores = jnp.einsum("bqgd,bsd->bqgs", qb, kb,
                                        precision=_HI) * scale
                    qpos = q_lo + jnp.arange(q_hi - q_lo)[:, None]
                    kpos = k_lo + jnp.arange(k_hi - k_lo)[None, :]
                    valid = kpos < t_valid
                    if causal:
                        valid = jnp.logical_and(valid, kpos <= qpos)
                    if window is not None:
                        valid = jnp.logical_and(valid, kpos > qpos - window)
                    scores = jnp.where(valid[None, :, None, :], scores, _NEG)
                    m_new = jnp.maximum(m, jnp.max(scores, -1, keepdims=True))
                    alpha = jnp.exp(m - m_new)
                    p = jnp.exp(scores - m_new)
                    l_new = l * alpha + jnp.sum(p, -1, keepdims=True)
                    a_new = acc * alpha + \
                        jnp.einsum("bqgs,bsd->bqgd", p, vb, precision=_HI)
                    return (m_new, l_new, a_new)

                if static_hm:
                    carry = upd(carry)
                else:
                    carry = jax.lax.cond(head_mask[hi] > 0, upd,
                                         lambda c: c, carry)
            m, l, acc = carry
            out_q = acc / jnp.maximum(l, 1e-30)
            if not static_hm:
                out_q = out_q * (head_mask[hi] > 0).astype(jnp.float32)
            q_blocks.append(out_q)
        heads.append(jnp.concatenate(q_blocks, axis=1))
    out = jnp.stack(heads, axis=2)                           # (B, S, Hkv, G, hd)
    return out.reshape(b, s, h, hd)
