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


def magnitude_masks(params: PyTree, prune_rate: float) -> PyTree:
    """Global unstructured magnitude pruning at rate ``prune_rate``.

    The threshold is computed over *all* prunable leaves jointly, matching
    rho = pruned-bytes / model-bytes as in the paper.
    """
    prune_rate = jnp.clip(prune_rate, 0.0, 1.0)
    leaves, treedef, flags = _flatten_prunable(params)
    mags = jnp.concatenate([jnp.abs(l).reshape(-1)
                            for l, f in zip(leaves, flags) if f])
    # threshold = rho-quantile of |w|; keep w where |w| > threshold
    thresh = jnp.quantile(mags, prune_rate)
    masked = [
        (jnp.abs(l) > thresh) if f
        else jnp.ones(l.shape, bool)
        for l, f in zip(leaves, flags)
    ]
    return jax.tree_util.tree_unflatten(treedef, masked)


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
