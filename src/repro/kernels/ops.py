"""Public jit'd wrappers for the Pallas kernels: padding, dtype handling,
and automatic interpret-mode selection (interpret=True off-TPU so the
kernel bodies execute on CPU for validation).

The TPU lowering takes only blocks whose last two dims are multiples of
(8, 128).  Pruning grids are sized to their matrices (a smollm 576x1536
projection tiles as 72x192), so the matmul and norm wrappers zero-pad
every tile up to the next multiple of 128 on each axis before the call
and drop the padding after it.  Zero rows and columns change neither a
product nor a squared norm.  A weight that is multiplied many times
(a serving layer) is padded once with ``pad_weight_tiles`` and passed to
``masked_matmul_padded``; ``masked_matmul`` pads on every call.

The attention wrappers take K/V head-major, (B, Hkv, S, hd): the layout
the serving KV cache is allocated in.

This module and ``fleet_fused.fused_fleet_grads`` are the only places
that derive ``interpret`` from the backend; the kernel modules require
it."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import block_norms as _bn
from repro.kernels import block_sparse_matmul as _bsm
from repro.kernels import decode_attention as _da
from repro.kernels import flash_prefill as _fp
from repro.kernels import ref


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _pad_to(x: jnp.ndarray, mults: tuple[int, ...]) -> jnp.ndarray:
    pads = [(0, (-d) % m) for d, m in zip(x.shape, mults)]
    if any(p for _, p in pads):
        return jnp.pad(x, pads)
    return x


_LANE = 128


def _hw_block(block: int) -> int:
    """The smallest multiple of 128 that holds a ``block``-long tile."""
    return -(-block // _LANE) * _LANE


def _pad_tiles(a: jnp.ndarray, axis: int, block: int) -> jnp.ndarray:
    """Zero-pad each ``block``-long tile along ``axis`` to ``_hw_block``.
    ``a.shape[axis]`` must be a multiple of ``block``."""
    full = _hw_block(block)
    if full == block:
        return a
    n = a.shape[axis] // block
    a = a.reshape(a.shape[:axis] + (n, block) + a.shape[axis + 1:])
    pads = [(0, 0)] * a.ndim
    pads[axis + 1] = (0, full - block)
    a = jnp.pad(a, pads)
    return a.reshape(a.shape[:axis] + (n * full,) + a.shape[axis + 2:])


def _unpad_tiles(a: jnp.ndarray, block: int) -> jnp.ndarray:
    """Inverse of ``_pad_tiles`` on the last axis."""
    full = _hw_block(block)
    if full == block:
        return a
    n = a.shape[-1] // full
    a = a.reshape(a.shape[:-1] + (n, full))[..., :block]
    return a.reshape(a.shape[:-2] + (n * block,))


def pad_weight_tiles(w: jnp.ndarray, block_k: int = 128,
                     block_n: int = 128) -> jnp.ndarray:
    """w: (K, N) -> (ceil(K/bk)*hw(bk), ceil(N/bn)*hw(bn)): every
    (block_k, block_n) tile zero-padded to the hardware tiling, the
    layout ``masked_matmul_padded`` reads."""
    w2 = _pad_to(w, (block_k, block_n))
    return _pad_tiles(_pad_tiles(w2, 0, block_k), 1, block_n)


def masked_matmul_padded(x: jnp.ndarray, wp: jnp.ndarray, mask: jnp.ndarray,
                         out_dim: int, block_m: int = 128,
                         block_k: int = 128, block_n: int = 128,
                         transpose_rhs: bool = False,
                         interpret: bool | None = None) -> jnp.ndarray:
    """``masked_matmul`` on a weight already laid out by
    ``pad_weight_tiles``.  ``out_dim`` is the unpadded output width (N, or
    K with ``transpose_rhs``); x carries the unpadded contraction dim."""
    interpret = _interpret_default() if interpret is None else interpret
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    m = x2.shape[0]
    bm = min(block_m, max(8, 1 << (m - 1).bit_length()))
    x_block = block_n if transpose_rhs else block_k
    x2 = _pad_tiles(_pad_to(x2, (bm, x_block)), 1, x_block)
    y = _bsm.block_sparse_matmul(x2, wp, mask, bm, _hw_block(block_k),
                                 _hw_block(block_n),
                                 transpose_rhs=transpose_rhs,
                                 interpret=interpret)
    y = _unpad_tiles(y, block_k if transpose_rhs else block_n)
    return y[:m, :out_dim].reshape(*lead, out_dim)


def masked_matmul(x: jnp.ndarray, w: jnp.ndarray, mask: jnp.ndarray,
                  block_m: int = 128, block_k: int = 128, block_n: int = 128,
                  transpose_rhs: bool = False,
                  interpret: bool | None = None) -> jnp.ndarray:
    """y = x @ (w ⊙ blockmask); arbitrary (batched) x, auto padding.

    x: (..., K), w: (K, N), mask: (ceil(K/bk), ceil(N/bn)).
    With ``transpose_rhs`` (the pruned layer's backward product):
    x: (..., N) and y = x @ (w ⊙ blockmask)^T -> (..., K), reusing the
    forward's mask layout.
    """
    kdim, n = w.shape
    return masked_matmul_padded(
        x, pad_weight_tiles(w, block_k, block_n), mask,
        kdim if transpose_rhs else n, block_m, block_k, block_n,
        transpose_rhs=transpose_rhs, interpret=interpret)


def tile_norms(w: jnp.ndarray, block_k: int = 128, block_n: int = 128,
               interpret: bool | None = None) -> jnp.ndarray:
    """Per-tile squared L2 norms with auto padding; w: (K, N)."""
    interpret = _interpret_default() if interpret is None else interpret
    return _bn.block_norms(pad_weight_tiles(w, block_k, block_n),
                           _hw_block(block_k), _hw_block(block_n),
                           interpret=interpret)


def _cache_block(s: int, block_s: int) -> int | None:
    """A cache-length block that divides S, so the cache is read where it
    lies: S itself when it fits, else the largest multiple of 8 in
    [128, block_s].  None when there is none (the caller pads)."""
    if s <= block_s and s % 8 == 0:
        return s
    for b in range(block_s - block_s % 8, 127, -8):
        if s % b == 0:
            return b
    return None


def flash_decode(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                 pos: jnp.ndarray, block_s: int = 512,
                 window: int | None = None,
                 head_mask=None, impl: str = "pallas",
                 interpret: bool | None = None) -> jnp.ndarray:
    """One-token GQA decode.  q: (B, H, hd), k/v: (B, Hkv, S, hd),
    pos: (B,).  A cache whose length no block divides (``_cache_block``)
    is padded on every call; a serving page length that is a multiple of
    8 avoids that.

    ``head_mask`` (Hkv,) skips dead KV heads (block-pruned serving — see
    decode_attention.py); a numpy mask on ``impl="xla"`` drops them at
    trace time.  ``impl``: "pallas" (TPU / interpret) or "xla" (the
    tile-loop twin, the fast CPU path)."""
    if impl == "xla":
        return _da.decode_attention_xla(q, k, v, pos, block_s=block_s,
                                        window=window, head_mask=head_mask)
    interpret = _interpret_default() if interpret is None else interpret
    s = k.shape[2]
    bs = _cache_block(s, block_s)
    if bs is None:
        bs = min(block_s, max(128, 1 << (s - 1).bit_length()))
        k = _pad_to(k, (1, 1, bs, 1))
        v = _pad_to(v, (1, 1, bs, 1))
    hm = None if head_mask is None else jnp.asarray(head_mask)
    return _da.decode_attention(q, k, v, pos, block_s=bs, window=window,
                                head_mask=hm, interpret=interpret)


def flash_prefill(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                  causal: bool = True, window: int | None = None,
                  block_q: int = 256, block_s: int = 512,
                  head_mask=None, impl: str = "pallas",
                  interpret: bool | None = None) -> jnp.ndarray:
    """Full-sequence GQA flash attention with auto padding.
    q: (B, S, H, hd), k/v: (B, Hkv, T, hd) -> (B, S, H, hd) f32.

    ``head_mask`` / ``impl`` as in ``flash_decode``."""
    if impl == "xla":
        return _fp.flash_prefill_xla(q, k, v, block_q=block_q,
                                     block_s=block_s, causal=causal,
                                     window=window, t_valid=k.shape[2],
                                     head_mask=head_mask)
    interpret = _interpret_default() if interpret is None else interpret
    s, t = q.shape[1], k.shape[2]
    block_q = min(block_q, max(16, 1 << (s - 1).bit_length()))
    block_s = min(block_s, max(16, 1 << (t - 1).bit_length()))
    qp = _pad_to(q, (1, block_q, 1, 1))
    kp = _pad_to(k, (1, 1, block_s, 1))
    vp = _pad_to(v, (1, 1, block_s, 1))
    hm = None if head_mask is None else jnp.asarray(head_mask)
    out = _fp.flash_prefill(qp, kp, vp, block_q=block_q, block_s=block_s,
                            causal=causal, window=window, t_valid=t,
                            head_mask=hm, interpret=interpret)
    return out[:, :s]


# re-export oracles for tests/benchmarks
oracle_masked_matmul = ref.block_sparse_matmul
oracle_masked_matmul_t = ref.block_sparse_matmul_t
oracle_tile_norms = ref.block_norms
oracle_flash_decode = ref.decode_attention
oracle_flash_prefill = ref.prefill_attention
