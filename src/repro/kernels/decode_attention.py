"""Pallas TPU kernel: one-token GQA decode attention (flash-decode style).

Computes attention for a single new token against a length-S KV cache with
optional sliding window, tiled over KV blocks with an online softmax: the
running (max, denominator, accumulator) live in VMEM scratch across the
sequential S-block sweep — the cache streams HBM->VMEM once, the classic
memory-bound decode pattern.  Every dot runs at full float32 precision
(``HIGHEST``): the serving contract is token equality with the dense
oracle, and a single bf16 pass would re-round each layer's inputs.

Grid: (B, Hkv, S/bs).  Each step handles the G = H/Hkv query heads of one
KV head so K/V blocks are fetched once per group (GQA's bandwidth win is
explicit in the tiling).  The KV cache is head-major, (B, Hkv, S, hd),
so that a block's last two dims are (bs, hd) as the TPU lowering wants;
the cache is allocated in that layout (serve/model.py), so no call
relayouts it.  The per-batch valid length ``pos`` rides in scalar
prefetch (SMEM) and prunes masked blocks' compute via @pl.when.

Mask-aware serving (PR 9): ``head_mask`` marks the *live* KV heads of a
block-pruned model (a KV head whose wv columns — or whose whole query
group's wo rows — fell to the tile threshold contributes exactly zero to
the residual, so skipping it is lossless).  The mask rides scalar
prefetch beside ``pos`` and folds into the same @pl.when block-skip
predicate, mirroring ``fleet_fused.py``'s per-tile ``lax.cond`` so decode
compute scales with the live-head fraction.  ``decode_attention_xla`` is
the tile-loop twin for backends where Pallas runs interpreted (CPU CI):
same skip rule expressed as per-(head, block) ``lax.cond``, with
statically dead heads dropped at trace time.
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


def _kernel(pos_ref, hm_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
            acc_ref, *, block_s: int, n_s: int, window, scale: float):
    b = pl.program_id(0)
    h = pl.program_id(1)
    s_idx = pl.program_id(2)

    @pl.when(s_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos = pos_ref[b]
    live = hm_ref[h] > 0
    blk_lo = s_idx * block_s
    # block-level skip: pruned KV head, or no valid key in this block ->
    # no compute at all (the scratch stays zero and the flush emits zeros)
    lo_ok = blk_lo <= pos
    hi_ok = True if window is None else (blk_lo + block_s - 1) > (pos - window)

    @pl.when(jnp.logical_and(live, jnp.logical_and(lo_ok, hi_ok)))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)                  # (G, hd)
        k = k_ref[0, 0].astype(jnp.float32)                  # (bs, hd)
        v = v_ref[0, 0].astype(jnp.float32)                  # (bs, hd)
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), precision=_HI,
            preferred_element_type=jnp.float32) * scale
        kpos = blk_lo + jax.lax.broadcasted_iota(jnp.int32, (1, block_s), 1)
        valid = kpos <= pos
        if window is not None:
            valid = jnp.logical_and(valid, kpos > pos - window)
        scores = jnp.where(valid, scores, _NEG)              # (G, bs)

        m_prev = m_ref[...]                                  # (G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new)                          # (G, bs)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, -1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + \
            jnp.dot(p, v, precision=_HI, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(s_idx == n_s - 1)
    def _flush():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_s", "window", "interpret"))
def decode_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     pos: jnp.ndarray, block_s: int = 512,
                     window: int | None = None,
                     head_mask: jnp.ndarray | None = None, *,
                     interpret: bool) -> jnp.ndarray:
    """q: (B, H, hd); k, v: (B, Hkv, S, hd); pos: (B,) int32.
    ``head_mask``: optional (Hkv,) live-head indicators (>0 = live); dead
    heads are skipped entirely and output zeros.
    Returns (B, H, hd) float32.  S % block_s == 0 (ops.py pads)."""
    b, h, hd = q.shape
    hkv, s = k.shape[1], k.shape[2]
    g = h // hkv
    n_s = s // block_s
    scale = hd ** -0.5
    qg = q.reshape(b, hkv, g, hd)
    hm = jnp.ones((hkv,), jnp.int32) if head_mask is None \
        else (jnp.asarray(head_mask) > 0).astype(jnp.int32)
    out = pl.pallas_call(
        functools.partial(_kernel, block_s=block_s, n_s=n_s, window=window,
                          scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, hkv, n_s),
            in_specs=[
                pl.BlockSpec((1, 1, g, hd), lambda b_, h_, s_, *_: (b_, h_, 0, 0)),
                pl.BlockSpec((1, 1, block_s, hd),
                             lambda b_, h_, s_, *_: (b_, h_, s_, 0)),
                pl.BlockSpec((1, 1, block_s, hd),
                             lambda b_, h_, s_, *_: (b_, h_, s_, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, g, hd),
                                   lambda b_, h_, s_, *_: (b_, h_, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, hd), jnp.float32),
        interpret=interpret,
    )(pos.astype(jnp.int32), hm, qg, k, v)
    return out.reshape(b, h, hd)


def decode_attention_xla(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                         pos: jnp.ndarray, block_s: int = 512,
                         window: int | None = None,
                         head_mask=None) -> jnp.ndarray:
    """XLA tile-loop twin of ``decode_attention`` (same skip rule, no
    Pallas): per (KV head, S block) the online-softmax update runs under a
    ``lax.cond`` whose predicate is the block's whole-batch liveness — the
    direct analogue of ``fleet_fused.fused_grads_xla``'s per-tile cond.

    ``head_mask`` may be a *numpy* array, in which case statically dead
    heads cost zero compute (dropped at trace time) — the serving path,
    where the mask comes from the exported tile keeps.  A traced mask
    falls back to the cond predicate.  Ragged S is handled directly (no
    padding): the last block is sliced short.
    """
    b, h, hd = q.shape
    hkv, s = k.shape[1], k.shape[2]
    g = h // hkv
    scale = hd ** -0.5
    block_s = min(block_s, s)
    n_s = -(-s // block_s)
    qg = q.reshape(b, hkv, g, hd).astype(jnp.float32)
    pos = pos.astype(jnp.int32)
    static_hm = isinstance(head_mask, np.ndarray)
    outs = []
    for hi in range(hkv):
        if static_hm and not bool(head_mask[hi] > 0):
            outs.append(jnp.zeros((b, g, hd), jnp.float32))
            continue
        m0 = jnp.full((b, g, 1), _NEG, jnp.float32)
        l0 = jnp.zeros((b, g, 1), jnp.float32)
        a0 = jnp.zeros((b, g, hd), jnp.float32)
        carry = (m0, l0, a0)
        for si in range(n_s):
            lo, hi_ = si * block_s, min(s, (si + 1) * block_s)
            kb = k[:, hi, lo:hi_].astype(jnp.float32)        # (B, bs, hd)
            vb = v[:, hi, lo:hi_].astype(jnp.float32)
            live = jnp.max(pos) >= lo
            if window is not None:
                live = jnp.logical_and(live,
                                       hi_ - 1 > jnp.min(pos) - window)
            if head_mask is not None and not static_hm:
                live = jnp.logical_and(live, head_mask[hi] > 0)

            def upd(carry, kb=kb, vb=vb, lo=lo, hi_=hi_):
                m, l, acc = carry
                scores = jnp.einsum("bgd,bsd->bgs", qg[:, hi], kb,
                                    precision=_HI) * scale
                kpos = lo + jnp.arange(hi_ - lo)[None, :]
                valid = kpos <= pos[:, None]
                if window is not None:
                    valid = jnp.logical_and(valid,
                                            kpos > pos[:, None] - window)
                scores = jnp.where(valid[:, None, :], scores, _NEG)
                m_new = jnp.maximum(m, jnp.max(scores, -1, keepdims=True))
                alpha = jnp.exp(m - m_new)
                p = jnp.exp(scores - m_new)
                l_new = l * alpha + jnp.sum(p, -1, keepdims=True)
                a_new = acc * alpha + jnp.einsum("bgs,bsd->bgd", p, vb,
                                                 precision=_HI)
                return (m_new, l_new, a_new)

            carry = jax.lax.cond(live, upd, lambda c: c, carry)
        m, l, acc = carry
        out_h = acc / jnp.maximum(l, 1e-30)
        if head_mask is not None and not static_hm:
            out_h = out_h * (head_mask[hi] > 0).astype(jnp.float32)
        outs.append(out_h)
    return jnp.stack(outs, axis=1).reshape(b, h, hd)
