"""Network pruning — the paper's compression mechanism, adapted to TPU.

The paper defines the pruning rate rho_i = D_P^i / D_M: the *fraction of
model bytes removed* before local training.  Two concrete instantiations:

* ``magnitude_masks`` — classic unstructured global magnitude pruning
  (exactly what edge-FL papers mean); used for the paper-scale MLP/DNN
  reproduction experiments.

* ``block_masks`` — TPU-native structured pruning: every 2-D weight matrix
  is partitioned into (block, block) tiles (default 128x128 = one MXU
  pass); tiles are ranked by L2 norm and the lowest-norm rho fraction is
  dropped.  ``kernels/block_sparse_matmul`` can then *skip* dropped tiles,
  so rho buys a real (1-rho)x FLOP/DMA reduction — making the paper's
  latency model t^c ~ (1-rho) physically accurate on TPU.

Masks are pytrees matching the parameter pytree; 1-D tensors (biases,
norm scales) are never pruned (negligible bytes, disproportionate damage).
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "prunable",
    "magnitude_masks",
    "magnitude_thresholds",
    "threshold_masks",
    "uses_selection",
    "block_masks",
    "apply_masks",
    "achieved_rate",
    "ones_masks",
    "BlockNormState",
    "block_norm_state",
    "block_thresholds",
    "block_keep",
    "masks_from_state",
    "masks_from_keep",
    "leaf_blocks",
]

PyTree = Any
DEFAULT_BLOCK = 128

# A block spec is an int (square tile edge), a (bk, bn) pair (rectangular
# tiles — tall/skinny matrices like embeddings get their own grid), or a
# *list* with one such entry per flattened leaf (None = unprunable /
# DEFAULT_BLOCK).  Per-leaf lists are what lets every layer of a
# heterogeneous model (transformer blocks vs the MLP) carry its own tile
# grid instead of one model-wide ``prune_block``.
BlockLike = Any


def _block_pair(block) -> tuple[int, int]:
    if isinstance(block, (int, np.integer)):
        return (int(block), int(block))
    bk, bn = block
    return (int(bk), int(bn))


def leaf_blocks(flags: list, block: BlockLike
                ) -> list[Optional[tuple[int, int]]]:
    """Normalize a block spec to one ``(bk, bn)`` pair per flattened leaf.

    ``flags`` marks the prunable leaves (``_flatten_prunable`` order).  A
    scalar/pair spec broadcasts over every prunable leaf; a *list* must
    align with the flattened leaves and may mix ints, pairs and ``None``
    (meaning ``DEFAULT_BLOCK``).  Unprunable leaves always map to ``None``.
    """
    if isinstance(block, list):
        if len(block) != len(flags):
            raise ValueError(
                f"per-leaf block list has {len(block)} entries for "
                f"{len(flags)} leaves")
        return [
            _block_pair(b if b is not None else DEFAULT_BLOCK) if f else None
            for f, b in zip(flags, block)
        ]
    pair = _block_pair(block)
    return [pair if f else None for f in flags]


def prunable(path: tuple, leaf: jnp.ndarray) -> bool:
    """Only >=2-D weight tensors are prunable; biases/scales stay dense."""
    del path
    return leaf.ndim >= 2


def _flatten_prunable(params: PyTree):
    leaves, treedef = jax.tree_util.tree_flatten(params)
    flags = [leaf.ndim >= 2 for leaf in leaves]
    return leaves, treedef, flags


def ones_masks(params: PyTree) -> PyTree:
    """rho = 0 masks (everything kept).  Masks are boolean pytrees: 1 byte
    per element instead of the weight dtype's width, and XLA fuses the
    select into neighbouring ops."""
    return jax.tree.map(lambda w: jnp.ones(w.shape, dtype=bool), params)


# Below this many prunable elements the magnitude threshold comes from one
# sort of |w|; from it on, from the bit-pattern selection of
# ``magnitude_thresholds``.  The sort is a compare-exchange network of
# ~log2(n)^2 / 2 passes over the n values; the selection is one pass per
# bit of the dtype, each one compare per value and rate.  On a TPU v5e
# (host clock, median of 7 calls, bf16): 134.5M weights and 16 rates, sort
# 602 ms against selection 33.7 ms (8.0 ms for one rate); with passes that
# compared int32 bit patterns (44 ms at that size), 2^20 weights took
# 2.20 ms sorted against 0.67 (one rate) and 0.95 (16), from 2^16 down both
# took the ~0.6 ms of a call, and 600 float32 weights with 4075 rates (the
# 1M fleet's chunk) read 0.66 ms sorted, 0.69 selected.  The cut keeps every
# small model's program as it was.  The rate count needs no rule of its
# own: 16 compares per weight and rate are a few passes over the (clients,
# weights) gradient batch that the caller's step writes anyway.
SELECT_MIN_ELEMENTS = 1 << 20


def _prunable_mags(params: PyTree) -> list:
    leaves, _, flags = _flatten_prunable(params)
    return [l for l, f in zip(leaves, flags) if f]


def uses_selection(params: PyTree) -> bool:
    """Whether ``magnitude_masks`` finds its threshold by selection: a rule
    on the number of prunable elements (large, and small enough that the
    int32 counts of the selection cannot overflow)."""
    n = sum(m.size for m in _prunable_mags(params))
    return SELECT_MIN_ELEMENTS <= n < 2 ** 31


def _count(mats: list, pred, vals: jnp.ndarray) -> jnp.ndarray:
    """int32 count, for each of ``vals``, of the |w| with ``pred(|w|,
    value)``: summed leaf by leaf, the compare fused into each leaf's
    reduction."""
    total = jnp.zeros(vals.shape, jnp.int32)
    for m in mats:
        v = vals.reshape(vals.shape + (1,) * m.ndim)
        total = total + jnp.sum(pred(jnp.abs(m), v), axis=tuple(
            range(vals.ndim, vals.ndim + m.ndim)), dtype=jnp.int32)
    return total


def _min_above(mats: list, vals: jnp.ndarray) -> jnp.ndarray:
    """For each of ``vals``, the smallest |w| above it (+inf where none)."""
    out = None
    for m in mats:
        a = jnp.abs(m)
        v = vals.reshape(vals.shape + (1,) * m.ndim)
        least = jnp.min(jnp.where(a > v, a, jnp.inf), axis=tuple(
            range(vals.ndim, vals.ndim + m.ndim)))
        out = least if out is None else jnp.minimum(out, least)
    return out


def magnitude_thresholds(params: PyTree, rates) -> jnp.ndarray:
    """Global magnitude threshold for each pruning rate in ``rates``.

    ``jnp.quantile(|w|, clip(rate, 0, 1))`` of the concatenated |w| of
    every prunable leaf (the default "linear" method, op for op as JAX 0.9
    computes it), without sorting or concatenating the weights.  The two
    order statistics the interpolation reads are found exactly, by
    bisection on the bit patterns of |w| (for a non-negative float the
    pattern orders as the value does): one pass of "count |w| below a
    candidate" per bit, for all rates at once, and one more pass for the
    next value above.  Where a rate falls between two order statistics, a
    backend that contracts the interpolation into a fused multiply-add may
    round it one float32 ulp apart from the quantile's program, as XLA:CPU
    does from one shape of program to the next; the rounding to the weights'
    dtype, and the masks, almost never see it.
    """
    mats = _prunable_mags(params)
    dtype = jnp.result_type(*mats)
    q = jnp.clip(jnp.asarray(rates), 0.0, 1.0)
    shape, q = q.shape, q.reshape(-1)
    n = sum(m.size for m in mats)

    # the positions and weights of jnp.quantile's "linear" rule, op for op
    # in q's dtype (at 134.5M weights n - 1 itself rounds in float32)
    nq = jax.lax.convert_element_type(n, q.dtype)
    pos = jax.lax.mul(q, nq - 1)
    low, high = jax.lax.floor(pos), jax.lax.ceil(pos)
    high_w = jax.lax.sub(pos, low)
    low_w = jax.lax.sub(jnp.ones((), high_w.dtype), high_w)
    low = jax.lax.clamp(jnp.zeros((), low.dtype), low, nq - 1)
    high = jax.lax.clamp(jnp.zeros((), high.dtype), high, nq - 1)
    # the quantile's gather clamps an index past the end to the last value
    k_low = jnp.clip(jax.lax.convert_element_type(low, int), 0, n - 1)
    k_high = jnp.clip(jax.lax.convert_element_type(high, int), 0, n - 1)

    nbits = jnp.dtype(dtype).itemsize * 8
    itype = {16: jnp.int16, 32: jnp.int32, 64: jnp.int64}[nbits]
    wide = jnp.int64 if nbits == 64 else jnp.int32
    value = lambda x: jax.lax.bitcast_convert_type(x.astype(itype), dtype)
    inf = jax.lax.bitcast_convert_type(
        jnp.asarray(jnp.inf, dtype), itype).astype(wide)

    # the largest pattern x with count(|w| < x) <= k is the k-th smallest;
    # candidates above +inf's pattern (NaNs) compare as +inf, so an
    # infinite k-th value lands on +inf's pattern once clipped there
    def step(i, x):
        cand = x | jnp.left_shift(jnp.ones((), wide), nbits - 2 - i)
        below = _count(mats, jnp.less, value(jnp.minimum(cand, inf)))
        return jnp.where(below <= k_low, cand, x)

    v_low = value(jnp.minimum(jax.lax.fori_loop(
        0, nbits - 1, step, jnp.zeros(q.shape, wide)), inf))
    # the k_high-th (k_low or k_low + 1) is v_low again while the count up
    # to v_low passes it, else the smallest |w| above v_low
    at_most = _count(mats, jnp.less_equal, v_low)
    v_high = jnp.where(at_most > k_high, v_low, _min_above(mats, v_low))

    # any NaN in |w| makes every order statistic NaN, as the quantile has it
    nan = functools.reduce(jnp.logical_or,
                           [jnp.any(jnp.isnan(m)) for m in mats])
    stat = lambda v: jnp.where(nan, jnp.nan, v.astype(q.dtype))
    out = jax.lax.add(jax.lax.mul(stat(v_low), low_w),
                      jax.lax.mul(stat(v_high), high_w))
    return jax.lax.convert_element_type(out, dtype).reshape(shape)


def threshold_masks(params: PyTree, thresh) -> PyTree:
    """Keep the prunable weights with |w| > ``thresh`` (one scalar over all
    prunable leaves); unprunable leaves are kept whole."""
    leaves, treedef, flags = _flatten_prunable(params)
    masked = [
        (jnp.abs(l) > thresh) if f
        else jnp.ones(l.shape, bool)
        for l, f in zip(leaves, flags)
    ]
    return jax.tree_util.tree_unflatten(treedef, masked)


def magnitude_masks(params: PyTree, prune_rate: float) -> PyTree:
    """Global unstructured magnitude pruning at rate ``prune_rate``.

    The threshold is computed over *all* prunable leaves jointly, matching
    rho = pruned-bytes / model-bytes as in the paper: the rho-quantile of
    |w|, from one sort, or for a large model (``uses_selection``) from
    ``magnitude_thresholds``' selection.
    """
    if uses_selection(params):
        return threshold_masks(params,
                               magnitude_thresholds(params, prune_rate))
    prune_rate = jnp.clip(prune_rate, 0.0, 1.0)
    mags = jnp.concatenate([jnp.abs(l).reshape(-1)
                            for l in _prunable_mags(params)])
    # threshold = rho-quantile of |w|; keep w where |w| > threshold
    return threshold_masks(params, jnp.quantile(mags, prune_rate))


def _pad_to_blocks(w: jnp.ndarray, block: BlockLike) -> jnp.ndarray:
    bk, bn = _block_pair(block)
    m, n = w.shape
    pm, pn = (-m) % bk, (-n) % bn
    if pm or pn:
        w = jnp.pad(w, ((0, pm), (0, pn)))
    return w


def block_l2_norms(w: jnp.ndarray, block: BlockLike = DEFAULT_BLOCK
                   ) -> jnp.ndarray:
    """Squared L2 norm of each (bk x bn) tile of a 2-D matrix.  ``block`` is
    an int (square tile) or a ``(bk, bn)`` pair."""
    bk, bn = _block_pair(block)
    w = _pad_to_blocks(w, (bk, bn))
    m, n = w.shape
    t = w.reshape(m // bk, bk, n // bn, bn)
    return jnp.sum(t.astype(jnp.float32) ** 2, axis=(1, 3))


def _tile_element_counts(m: int, n: int, lead: int,
                         block: BlockLike) -> jnp.ndarray:
    """Number of *real* (unpadded) elements in each tile of an (m, n) matrix,
    replicated over ``lead`` leading batch entries."""
    bk, bn = _block_pair(block)
    rows = jnp.minimum(bk, m - jnp.arange(0, m + (-m) % bk, bk))
    cols = jnp.minimum(bn, n - jnp.arange(0, n + (-n) % bn, bn))
    counts = rows[:, None] * cols[None, :]
    return jnp.broadcast_to(counts, (lead,) + counts.shape)


def _leaf_tile_norms(leaf: jnp.ndarray, block: BlockLike) -> jnp.ndarray:
    """Tile L2 norms over the *last two* dims; leading dims are batch-wise."""
    lead = leaf.shape[:-2]
    w2 = leaf.reshape((-1,) + leaf.shape[-2:])
    norms = jax.vmap(functools.partial(block_l2_norms, block=block))(w2)
    return norms.reshape(lead + norms.shape[1:])


def _leaf_tile_counts(leaf: jnp.ndarray, block: BlockLike) -> jnp.ndarray:
    m, n = leaf.shape[-2], leaf.shape[-1]
    lead = int(np.prod(leaf.shape[:-2], dtype=np.int64)) \
        if leaf.ndim > 2 else 1
    return _tile_element_counts(m, n, lead, block)


class BlockNormState(NamedTuple):
    """Once-per-round ranking statistics for one prunable leaf.

    The full sort happens *here*, once; per-client masks then cost one
    ``searchsorted`` each (see ``block_thresholds``), which is what makes
    per-client per-round block pruning affordable at fleet scale.
    """

    norms: jnp.ndarray         # lead + (Tk, Tn) tile squared-L2 norms
    sorted_norms: jnp.ndarray  # (T,) the same norms, ascending
    cum_frac: jnp.ndarray      # (T,) cumulative element mass of sorted tiles


def block_norm_state(params: PyTree, block: BlockLike = DEFAULT_BLOCK
                     ) -> list[Optional[BlockNormState]]:
    """Per-leaf ranking state, aligned with ``tree_flatten(params)`` order
    (``None`` for unprunable leaves).  Equivalent to the sort inside
    ``block_masks(scope="leaf")`` but factored out so a round computes it
    once and reuses it for every client's threshold.  ``block`` may be a
    per-leaf list (see ``leaf_blocks``) so every layer rides its own grid."""
    leaves, _, flags = _flatten_prunable(params)
    blocks = leaf_blocks(flags, block)
    out: list[Optional[BlockNormState]] = []
    for leaf, f, blk in zip(leaves, flags, blocks):
        if not f:
            out.append(None)
            continue
        norms = _leaf_tile_norms(leaf, blk)
        counts = _leaf_tile_counts(leaf, blk).reshape(-1).astype(jnp.float32)
        flat = norms.reshape(-1)
        order = jnp.argsort(flat)
        cum = jnp.cumsum(counts[order])
        out.append(BlockNormState(norms=norms, sorted_norms=flat[order],
                                  cum_frac=cum / cum[-1]))
    return out


def block_thresholds(state: BlockNormState, rate: jnp.ndarray) -> jnp.ndarray:
    """Smallest kept norm at pruning rate ``rate`` (scalar or batched).

    Tiles whose cumulative element mass is <= rate*total are dropped
    (side="right": an exact tile boundary drops the boundary tile; floor
    semantics otherwise) — identical to ``block_masks``'s quantile.  At
    rate 1 every tile's mass is <= the total, so every tile is dropped and
    the threshold is +inf.
    """
    rate = jnp.clip(jnp.asarray(rate), 0.0, 1.0)
    idx = jnp.searchsorted(state.cum_frac, rate, side="right")
    t = state.sorted_norms.size
    return jnp.where(idx >= t, jnp.inf,
                     state.sorted_norms[jnp.clip(idx, 0, t - 1)])


def block_keep(state: list[Optional[BlockNormState]], rates: jnp.ndarray
               ) -> list[Optional[jnp.ndarray]]:
    """Per-leaf tile-keep indicators for a *batch* of pruning rates.

    Returns, for each prunable leaf, a float array of shape
    ``rates.shape + norms.shape`` with 1.0 where the tile survives client
    c's threshold (rate <= 0 keeps everything, as in ``block_masks``).
    """
    rates = jnp.asarray(rates)
    out: list[Optional[jnp.ndarray]] = []
    for st in state:
        if st is None:
            out.append(None)
            continue
        thresh = block_thresholds(st, rates)          # rates.shape
        ext = thresh.reshape(thresh.shape + (1,) * st.norms.ndim)
        keep = (st.norms >= ext) | (rates.reshape(ext.shape) <= 0.0)
        out.append(keep.astype(jnp.float32))
    return out


def _expand_tiles(keep: jnp.ndarray, shape: tuple,
                  block: BlockLike) -> jnp.ndarray:
    """Tile-level keep -> element-level boolean mask of ``shape``."""
    bk, bn = _block_pair(block)
    m, n = shape[-2], shape[-1]
    keep = jnp.repeat(jnp.repeat(keep, bk, axis=-2), bn, axis=-1)
    return keep[..., :m, :n]


def masks_from_state(params: PyTree, state: list[Optional[BlockNormState]],
                     rate, block: BlockLike = DEFAULT_BLOCK) -> PyTree:
    """Element-level boolean masks for one scalar rate from a precomputed
    ``block_norm_state`` — equals ``block_masks(params, rate, block,
    scope="leaf")`` by construction (``block_masks`` is implemented on
    top of this).  ``block`` must match the spec the state was built with."""
    rate = jnp.clip(jnp.asarray(rate), 0.0, 1.0)
    leaves, treedef, flags = _flatten_prunable(params)
    blocks = leaf_blocks(flags, block)
    keep_all = rate <= 0.0
    masked = []
    for leaf, f, st, blk in zip(leaves, flags, state, blocks):
        if not f:
            masked.append(jnp.ones(leaf.shape, bool))
            continue
        thresh = block_thresholds(st, rate)
        keep = (st.norms >= thresh) | keep_all
        masked.append(_expand_tiles(keep, leaf.shape, blk))
    return jax.tree_util.tree_unflatten(treedef, masked)


def masks_from_keep(params: PyTree, keeps: list, block: BlockLike) -> PyTree:
    """One client's per-leaf tile-keep indicators -> element-level masks.

    ``keeps`` aligns with ``tree_flatten(params)`` (``None`` for unprunable
    leaves) and holds float/bool tile indicators shaped like the leaf's
    ``block_norm_state`` norms — i.e. one entry of ``block_keep``'s batched
    output.  The expansion matches ``masks_from_state`` tile-for-tile, so
    the fused per-client path and the reference ``block_masks`` path build
    identical masks from the same ranking state.
    """
    leaves, treedef, flags = _flatten_prunable(params)
    blocks = leaf_blocks(flags, block)
    masked = []
    for leaf, f, keep, blk in zip(leaves, flags, keeps, blocks):
        if not f:
            masked.append(jnp.ones(leaf.shape, bool))
            continue
        masked.append(_expand_tiles(keep > 0, leaf.shape, blk))
    return jax.tree_util.tree_unflatten(treedef, masked)


def block_masks(params: PyTree, prune_rate: float,
                block: BlockLike = DEFAULT_BLOCK, scope: str = "leaf"
                ) -> PyTree:
    """TPU block-structured magnitude pruning.

    Each >=2-D leaf is reduced to tile L2 norms over its *last two* dims
    (leading dims — layer stacks, experts — are treated batch-wise).  The
    threshold is an *element-count-weighted* quantile over tile norms, so
    the achieved rho matches the requested byte fraction even with ragged
    edge tiles.  rho = 0 keeps everything exactly.

    scope="leaf" (default) ranks tiles within each tensor, so every matmul
    loses the same rho fraction — this matches the paper's latency model
    t^c ~ (1-rho) per layer and is robust to per-layer init-scale
    differences (a globally ranked threshold can annihilate a small-scale
    tensor, e.g. 0.02-std embeddings vs fan-in-scaled dense weights).
    scope="global" ranks all tiles jointly (classic global magnitude
    pruning).
    """
    prune_rate = float(np.clip(prune_rate, 0.0, 1.0)) if not isinstance(
        prune_rate, jnp.ndarray) else jnp.clip(prune_rate, 0.0, 1.0)
    rate = jnp.asarray(prune_rate)

    if scope == "leaf":
        return masks_from_state(params, block_norm_state(params, block),
                                rate, block)
    if scope != "global":
        raise ValueError(f"scope must be 'leaf' or 'global', got {scope!r}")

    keep_all = rate <= 0.0
    leaves, treedef, flags = _flatten_prunable(params)
    blocks = leaf_blocks(flags, block)
    all_norms = [_leaf_tile_norms(l, b) if f else None
                 for l, f, b in zip(leaves, flags, blocks)]
    norms_cat = jnp.concatenate(
        [n.reshape(-1) for n, f in zip(all_norms, flags) if f])
    counts_cat = jnp.concatenate(
        [_leaf_tile_counts(l, b).reshape(-1)
         for l, f, b in zip(leaves, flags, blocks) if f]).astype(jnp.float32)
    order = jnp.argsort(norms_cat)
    cum = jnp.cumsum(counts_cat[order])
    g_state = BlockNormState(norms=norms_cat, sorted_norms=norms_cat[order],
                             cum_frac=cum / cum[-1])
    g_thresh = block_thresholds(g_state, rate)

    masked = [
        _expand_tiles((n >= g_thresh) | keep_all, l.shape, b)
        if f else jnp.ones(l.shape, bool)
        for l, f, n, b in zip(leaves, flags, all_norms, blocks)
    ]
    return jax.tree_util.tree_unflatten(treedef, masked)


def apply_masks(params: PyTree, masks: PyTree) -> PyTree:
    """W~ = W * M — the pruned local model the UE trains on.  Boolean masks
    apply as a select; numeric masks (legacy) as a multiply."""
    def one(w, m):
        if m.dtype == jnp.bool_:
            return jnp.where(m, w, jnp.zeros((), w.dtype))
        return w * m
    return jax.tree.map(one, params, masks)


def achieved_rate(params: PyTree, masks: PyTree) -> jnp.ndarray:
    """Realized rho = pruned-elements / total-elements over prunable leaves."""
    leaves, _, flags = _flatten_prunable(params)
    mask_leaves = jax.tree_util.tree_leaves(masks)
    kept = sum(jnp.sum(m.astype(jnp.float32))
               for m, f in zip(mask_leaves, flags) if f)
    # python float, not int: a >2^31-element model overflows the int32
    # weak-type promotion of (traced scalar / python int)
    total = float(sum(m.size for m, f in zip(mask_leaves, flags) if f))
    return 1.0 - kept / total
