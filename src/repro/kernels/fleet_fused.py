"""Fused pruned-gradient hot path: block-sparse client kernels.

The fleet engine's inner loop is, per client i: build the local batch,
prune the global model at rho_i, run forward/backward on the pruned
model, re-mask the gradient, and accumulate it with the packet-error /
K_i C_i weight of Eq. (5).  The reference path materializes a
``(clients, params)`` gradient batch and reduces it afterwards; this
module fuses the whole chain so a *tile of clients* streams through the
accumulators and only the weighted gradient **sum** is ever written —
the compute-side realization of the paper's t^c ~ (1 - rho) latency
model (pruned tiles are skipped, cf. the on-device FLOP assumption of
hierarchical/adaptive federated pruning, arXiv:2305.09042 /
arXiv:2309.01816).

Masks are block-structured (``core.pruning.block_masks`` semantics,
scope="leaf"): each weight matrix is ranked once per round into a
``BlockNormState`` and every client's mask is one ``searchsorted``
against the shared sorted tile norms — no per-client sort.

Three implementations of identical math (equivalence-tested):

* ``fused_grads_xla`` — tile-loop XLA program: per (k, n) weight tile
  one dense dot over the flattened (clients x batch) rows, row-scaled by
  each client's tile-keep indicator.  This is the fast path on CPU/GPU
  and the semantics reference for the kernel.
* ``fused_grads_pallas`` — the Pallas TPU kernel: grid over client
  tiles, per-layer gradient accumulators live in VMEM scratch across the
  whole sweep, per-tile dots are predicated (``lax.cond``) on any client
  in the tile keeping the tile, and outputs are flushed once at the last
  grid step.  ``interpret=True`` executes the same kernel body on CPU
  (the CI fallback).
* ``reference_grads`` — vmap + ``jax.value_and_grad`` per client over
  ``pruning.block_masks``; the oracle the other two are tested against.

``fused_fleet_grads`` dispatches: Pallas when the backend is TPU,
XLA otherwise.  The fused paths' dots run at full float32 precision
(``HIGHEST``), so on the TPU they match the oracle to float32 rounding
rather than to one bf16 pass; at the MLP's widths the extra MXU passes
are not what bounds the kernel.

The three kernels above are layer-structured (the MLP's ``layer{i}``
layout).  ``masked_scan_grads`` is the *model-agnostic* sibling used by
every other ``FleetTask``: clients stream through a ``lax.scan`` whose
carry is the accumulated weighted gradient sum, with masks expanded from
the shared ranking state on per-leaf tile grids — same
never-materialize-the-batch property, arbitrary loss/pytree.
"""

from __future__ import annotations

import functools
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core import pruning

PyTree = Any

DEFAULT_TILE_CLIENTS = 8
_HI = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# MLP parameter plumbing
# ---------------------------------------------------------------------------

def layer_weights(params: dict) -> tuple[list[jnp.ndarray], list[jnp.ndarray]]:
    """``models.mlp`` params -> ([w_0..w_L-1], [b_0..b_L-1]) in layer order
    (explicit ``layer{i}`` keys, not tree-flatten order, which sorts
    ``layer10`` before ``layer2``)."""
    n = len(params)
    ws = [params[f"layer{i}"]["w"] for i in range(n)]
    bs = [params[f"layer{i}"]["b"] for i in range(n)]
    return ws, bs


def grads_tree(layer_grads: Sequence[tuple[jnp.ndarray, jnp.ndarray]]) -> dict:
    """[(dw, db), ...] in layer order -> params-shaped pytree."""
    return {f"layer{i}": {"w": dw, "b": db}
            for i, (dw, db) in enumerate(layer_grads)}


def layer_norm_states(params: dict, block: int
                      ) -> list[pruning.BlockNormState]:
    """One ``BlockNormState`` per weight matrix, in layer order.  Computed
    once per round; per-leaf scope makes the single-leaf call identical to
    ``block_norm_state`` over the full tree."""
    ws, _ = layer_weights(params)
    return [pruning.block_norm_state({"w": w}, block)[0] for w in ws]


def layer_keeps(states: Sequence[pruning.BlockNormState],
                rates: jnp.ndarray) -> list[jnp.ndarray]:
    """Per-layer tile-keep indicators ``(clients, Tk, Tn)`` for a batch of
    client pruning rates — one searchsorted per layer, no sorting."""
    return [pruning.block_keep([st], rates)[0] for st in states]


def _tile_slices(dim: int, block: int) -> list[tuple[int, int]]:
    return [(s, min(s + block, dim)) for s in range(0, dim, block)]


# ---------------------------------------------------------------------------
# XLA implementation (fast path off-TPU; semantics reference for the kernel)
# ---------------------------------------------------------------------------

def fused_grads_xla(params: dict, x: jnp.ndarray, y: jnp.ndarray,
                    keeps: Sequence[jnp.ndarray], weights: jnp.ndarray,
                    block: int) -> tuple[dict, jnp.ndarray]:
    """Weighted-sum block-pruned gradients + per-client losses.

    CPU/GPU-tuned layout: every stage is a handful of dense
    flop-proportional dots over the flattened (clients x batch) rows,
    with each client's tile-keep indicators folded into whichever
    operand has the *short* producer chain — the forward masks the
    activations per output-column tile (``(a ⊙ keep) @ W``), the
    gradient reduction masks the *dz* side per input-row tile
    (``a_t^T @ (dz ⊙ keep ⊙ w)``) so the contraction runs against the
    live activation array instead of a cached masked copy XLA would
    rematerialize.  Mask and Eq.-(5) weight apply inside the reduction,
    so a (clients, params) gradient batch is never materialized.

    Args:
      params: ``models.mlp`` parameter dict (the *dense* global model).
      x: (clients, batch, dim) local batches.
      y: (clients, batch) int labels.
      keeps: per-layer (clients, Tk, Tn) tile-keep indicators
        (``layer_keeps``); tile t of layer l is live for client c iff
        ``keeps[l][c, t] > 0``.
      weights: (clients,) aggregation weights (K_i C_i, or the async
        staleness-discounted merge weight; zero drops the client).
      block: pruning block size (tile edge).

    Returns:
      ``(grad_wsum, losses)`` — the params-shaped weighted gradient sum
      and per-client training losses (unweighted, for metrics).
    """
    ws, bs = layer_weights(params)
    nl = len(ws)
    c, batch, _ = x.shape
    rows = c * batch
    yf = y.reshape(-1).astype(jnp.int32)

    acts3, zs = [x], []          # (c, batch, K_l) activations per layer
    kexp_cache = []              # (c, K_l) column-expanded keeps per u-tile
    for l in range(nl):
        kdim, ndim = ws[l].shape
        kt = _tile_slices(kdim, block)
        nt = _tile_slices(ndim, block)
        ksizes = np.asarray([k1 - k0 for k0, k1 in kt])
        kexps, cols = [], []
        for uj, (n0, n1) in enumerate(nt):
            kexp = jnp.repeat(keeps[l][:, :, uj], ksizes, axis=1,
                              total_repeat_length=kdim)       # (c, K_l)
            kexps.append(kexp)
            xs = (acts3[-1] * kexp[:, None, :]).reshape(rows, kdim)
            cols.append(jnp.dot(xs, ws[l][:, n0:n1], precision=_HI))
        z = jnp.concatenate(cols, axis=-1) + bs[l]
        zs.append(z)
        a_next = jax.nn.relu(z) if l < nl - 1 else z
        acts3.append(a_next.reshape(c, batch, ndim))
        kexp_cache.append(kexps)

    logits = zs[-1]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, yf[:, None], axis=1)[:, 0]
    losses = nll.reshape(c, batch).mean(axis=-1)

    onehot = (yf[:, None] == jnp.arange(logits.shape[-1])[None, :]
              ).astype(logits.dtype)
    dz = (jnp.exp(logp) - onehot) / batch
    w_rows = jnp.repeat(weights, batch)

    layer_grads: list = [None] * nl
    for l in reversed(range(nl)):
        kdim, ndim = ws[l].shape
        kt = _tile_slices(kdim, block)
        nt = _tile_slices(ndim, block)
        nsizes = np.asarray([n1 - n0 for n0, n1 in nt])
        dzw3 = (dz * w_rows[:, None]).reshape(c, batch, ndim)
        a2 = acts3[l].reshape(rows, kdim)
        dw_rows = []
        for ti, (k0, k1) in enumerate(kt):
            kexpn = jnp.repeat(keeps[l][:, ti, :], nsizes, axis=1,
                               total_repeat_length=ndim)      # (c, N_l)
            dzm = (dzw3 * kexpn[:, None, :]).reshape(rows, ndim)
            dw_rows.append(jnp.dot(a2[:, k0:k1].T, dzm, precision=_HI))
        dw = jnp.concatenate(dw_rows, axis=0)
        db = jnp.sum(dzw3.reshape(rows, ndim), axis=0)
        layer_grads[l] = (dw, db)
        if l > 0:
            da3 = None
            for uj, (n0, n1) in enumerate(nt):
                part = jnp.dot(dz[:, n0:n1], ws[l][:, n0:n1].T,
                               precision=_HI) \
                    .reshape(c, batch, kdim) * kexp_cache[l][uj][:, None, :]
                da3 = part if da3 is None else da3 + part
            dz = da3.reshape(rows, kdim) * (zs[l - 1] > 0)
    return grads_tree(layer_grads), losses


# ---------------------------------------------------------------------------
# Pallas kernel (client tiles stream through VMEM accumulators)
# ---------------------------------------------------------------------------

def _pad_axis(a: jnp.ndarray, axis: int, mult: int) -> jnp.ndarray:
    pad = (-a.shape[axis]) % mult
    if not pad:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths)


def _build_fused_kernel(nl: int, dims: list[tuple[int, int]], block: int,
                        n_classes: int):
    """Close over the static layer layout and return the kernel body.

    Every per-client quantity arrives already expanded to one row per
    sample (the wrapper repeats keeps and weights over the batch), so the
    body is 2-D throughout: Mosaic has no layout for the (clients, batch)
    <-> rows reshapes or for 1-D vectors.

    Ref order: x, y, row_wts, keep_0..keep_{L-1}, w_0, b_0, .., w_{L-1},
    b_{L-1} | row_nll, dw_0, db_0, .., dw_{L-1}, db_{L-1} | per-layer
    (acc_dw, acc_db) VMEM scratch.
    """
    n_tiles = [(len(_tile_slices(k, block)), len(_tile_slices(n, block)))
               for k, n in dims]

    def kernel(*refs):
        x_ref, y_ref, wts_ref = refs[0], refs[1], refs[2]
        keep_refs = refs[3:3 + nl]
        w_refs = [refs[3 + nl + 2 * l] for l in range(nl)]
        b_refs = [refs[3 + nl + 2 * l + 1] for l in range(nl)]
        out0 = 3 + 3 * nl
        nll_ref = refs[out0]
        dw_refs = [refs[out0 + 1 + 2 * l] for l in range(nl)]
        db_refs = [refs[out0 + 2 + 2 * l] for l in range(nl)]
        acc0 = out0 + 1 + 2 * nl
        acc_dw = [refs[acc0 + 2 * l] for l in range(nl)]
        acc_db = [refs[acc0 + 2 * l + 1] for l in range(nl)]

        step = pl.program_id(0)

        @pl.when(step == 0)
        def _init():
            for l in range(nl):
                acc_dw[l][...] = jnp.zeros_like(acc_dw[l])
                acc_db[l][...] = jnp.zeros_like(acc_db[l])

        def keep_col(l, ti, uj):                     # (rows, 1)
            t = ti * n_tiles[l][1] + uj
            return keep_refs[l][:, t:t + 1]

        # -- forward: per-tile dots, predicated on any client keeping it
        a = x_ref[...].astype(jnp.float32)
        acts, zs = [a], []
        for l in range(nl):
            kt = _tile_slices(dims[l][0], block)
            nt = _tile_slices(dims[l][1], block)
            cols = []
            for uj, (n0, n1) in enumerate(nt):
                acc = jnp.zeros((a.shape[0], n1 - n0), jnp.float32)
                for ti, (k0, k1) in enumerate(kt):
                    kvec = keep_col(l, ti, uj)
                    acc = acc + jax.lax.cond(
                        jnp.max(kvec) > 0,
                        lambda a_=acts[l], kv=kvec, k0=k0, k1=k1,
                        n0=n0, n1=n1, wr=w_refs[l]: jnp.dot(
                            a_[:, k0:k1], wr[k0:k1, n0:n1], precision=_HI,
                            preferred_element_type=jnp.float32) * kv,
                        lambda s=acc.shape: jnp.zeros(s, jnp.float32))
                cols.append(acc)
            z = jnp.concatenate(cols, axis=-1) + b_refs[l][...]
            zs.append(z)
            acts.append(jax.nn.relu(z) if l < nl - 1 else z)

        # -- loss + dlogits (padded class columns are masked out)
        logits = zs[-1]
        col = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        logits = jnp.where(col < n_classes, logits, -1e30)
        logp = jax.nn.log_softmax(logits, axis=-1)
        onehot = (y_ref[...] == col).astype(jnp.float32)
        nll_ref[...] = -jnp.sum(logp * onehot, axis=-1, keepdims=True)
        dz = jnp.exp(logp) - onehot

        # -- backward sweep, accumulating into VMEM scratch.  Row weights
        # carry the client's aggregation weight over its batch size.
        w_rows = wts_ref[...]                            # (rows, 1)
        for l in reversed(range(nl)):
            kt = _tile_slices(dims[l][0], block)
            nt = _tile_slices(dims[l][1], block)
            for ti, (k0, k1) in enumerate(kt):
                for uj, (n0, n1) in enumerate(nt):
                    svec = keep_col(l, ti, uj) * w_rows
                    contrib = jax.lax.cond(
                        jnp.max(svec) > 0,
                        lambda a_=acts[l], sv=svec, d=dz, k0=k0, k1=k1,
                        n0=n0, n1=n1: jax.lax.dot_general(
                            a_[:, k0:k1] * sv, d[:, n0:n1],
                            (((0,), (0,)), ((), ())), precision=_HI,
                            preferred_element_type=jnp.float32),
                        lambda s=(k1 - k0, n1 - n0): jnp.zeros(
                            s, jnp.float32))
                    acc_dw[l][k0:k1, n0:n1] += contrib
            acc_db[l][...] += jnp.sum(dz * w_rows, axis=0, keepdims=True)
            if l > 0:
                cols = []
                for ti, (k0, k1) in enumerate(kt):
                    acc = jnp.zeros((dz.shape[0], k1 - k0), jnp.float32)
                    for uj, (n0, n1) in enumerate(nt):
                        kvec = keep_col(l, ti, uj)
                        acc = acc + jax.lax.cond(
                            jnp.max(kvec) > 0,
                            lambda d=dz, kv=kvec, k0=k0, k1=k1, n0=n0,
                            n1=n1, wr=w_refs[l]: jax.lax.dot_general(
                                d[:, n0:n1], wr[k0:k1, n0:n1],
                                (((1,), (1,)), ((), ())), precision=_HI,
                                preferred_element_type=jnp.float32) * kv,
                            lambda s=acc.shape: jnp.zeros(s, jnp.float32))
                    cols.append(acc)
                dz = jnp.concatenate(cols, axis=-1) * (zs[l - 1] > 0)

        @pl.when(step == pl.num_programs(0) - 1)
        def _flush():
            for l in range(nl):
                dw_refs[l][...] = acc_dw[l][...]
                db_refs[l][...] = acc_db[l][...]

    return kernel


def fused_grads_pallas(params: dict, x: jnp.ndarray, y: jnp.ndarray,
                       keeps: Sequence[jnp.ndarray], weights: jnp.ndarray,
                       block: int, *, interpret: bool,
                       tile_clients: int = DEFAULT_TILE_CLIENTS
                       ) -> tuple[dict, jnp.ndarray]:
    """Pallas streaming version of ``fused_grads_xla`` (same signature and
    semantics).  Clients are swept ``tile_clients`` at a time; gradient
    accumulators live in VMEM scratch across the sweep and the
    ``(clients, params)`` batch is never materialized.  Padded clients
    carry zero keep/weight so they contribute nothing."""
    from jax.experimental.pallas import tpu as pltpu  # deferred: CPU-safe

    ws, bs = layer_weights(params)
    nl = len(ws)
    c, batch, d = x.shape
    cp = c + (-c) % tile_clients
    tile_r = tile_clients * batch

    wsp = [_pad_axis(_pad_axis(w, 0, block), 1, block) for w in ws]
    bsp = [_pad_axis(b, 0, block)[None, :].astype(jnp.float32)
           for b in bs]
    dims = [tuple(w.shape) for w in wsp]

    def rows(a):                    # (clients, ...) -> one row per sample
        a = _pad_axis(a.reshape(c, -1), 0, tile_clients)
        return jnp.repeat(a, batch, axis=0).astype(jnp.float32)

    xf = _pad_axis(_pad_axis(x.reshape(c * batch, d), 0, tile_r), 1, block)
    yf = _pad_axis(y.reshape(c * batch, 1).astype(jnp.int32), 0, tile_r)
    wts = rows(weights) / batch
    keeps2 = [rows(k) for k in keeps]

    grid = (cp // tile_clients,)
    kernel = _build_fused_kernel(nl, dims, block, bs[-1].shape[0])

    in_specs = [
        pl.BlockSpec((tile_r, xf.shape[1]), lambda i: (i, 0)),
        pl.BlockSpec((tile_r, 1), lambda i: (i, 0)),
        pl.BlockSpec((tile_r, 1), lambda i: (i, 0)),
    ]
    for k in keeps2:
        in_specs.append(pl.BlockSpec((tile_r, k.shape[1]),
                                     lambda i: (i, 0)))
    for w, b in zip(wsp, bsp):
        in_specs.append(pl.BlockSpec(w.shape, lambda i: (0, 0)))
        in_specs.append(pl.BlockSpec(b.shape, lambda i: (0, 0)))

    out_shapes = [jax.ShapeDtypeStruct((cp * batch, 1), jnp.float32)]
    out_specs = [pl.BlockSpec((tile_r, 1), lambda i: (i, 0))]
    scratch = []
    for w, b in zip(wsp, bsp):
        out_shapes.append(jax.ShapeDtypeStruct(w.shape, jnp.float32))
        out_specs.append(pl.BlockSpec(w.shape, lambda i: (0, 0)))
        out_shapes.append(jax.ShapeDtypeStruct(b.shape, jnp.float32))
        out_specs.append(pl.BlockSpec(b.shape, lambda i: (0, 0)))
        scratch.append(pltpu.VMEM(w.shape, jnp.float32))
        scratch.append(pltpu.VMEM(b.shape, jnp.float32))

    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shapes,
        scratch_shapes=scratch,
        interpret=interpret,
    )(xf.astype(jnp.float32), yf, wts, *keeps2,
      *[a for pair in zip(
          (w.astype(jnp.float32) for w in wsp), bsp) for a in pair])

    losses = outs[0][:c * batch, 0].reshape(c, batch).mean(axis=-1)
    layer_grads = []
    for l in range(nl):
        dw = outs[1 + 2 * l][:ws[l].shape[0], :ws[l].shape[1]]
        db = outs[2 + 2 * l][0, :bs[l].shape[0]]
        layer_grads.append((dw, db))
    return grads_tree(layer_grads), losses


# ---------------------------------------------------------------------------
# Generic task path: fused Eq.-(5) reduction for arbitrary loss functions
# ---------------------------------------------------------------------------

def masked_scan_grads(loss_fn, params: PyTree, batch: PyTree,
                      keeps: Sequence[Optional[jnp.ndarray]],
                      weights: jnp.ndarray, block
                      ) -> tuple[PyTree, jnp.ndarray]:
    """Weighted-sum block-pruned gradients for an arbitrary task.

    The model-agnostic sibling of ``fused_grads_xla``: clients stream one
    at a time through a ``lax.scan`` whose carry is the *accumulated*
    weighted gradient sum, so — like the MLP kernels — the
    ``(clients, params)`` gradient batch is never materialized.  Masks come
    from the same once-per-round ranking state (``pruning.block_norm_state``
    + one ``searchsorted`` per client via ``pruning.block_keep``), expanded
    per leaf on that leaf's own tile grid (``block`` may be a per-leaf
    list — non-square transformer matrices ride their own grids).

    Args:
      loss_fn: ``loss_fn(params, batch_i) -> scalar`` per-client loss.
      params: the dense global model (any pytree).
      batch: pytree of per-client batches, every leaf leading-dim clients.
      keeps: per-leaf tile-keep indicators batched over clients
        (``pruning.block_keep`` output; ``None`` for unprunable leaves).
      weights: (clients,) Eq.-(5) aggregation weights (zero drops a client).
      block: block spec the keeps were ranked with (int | pair | per-leaf
        list, see ``pruning.leaf_blocks``).

    Returns:
      ``(grad_wsum, losses)`` — params-shaped weighted gradient sum and the
      per-client (unweighted) training losses.
    """
    keep_idx = [i for i, k in enumerate(keeps) if k is not None]
    keeps_p = tuple(keeps[i] for i in keep_idx)
    n_leaves = len(keeps)

    def body(acc, xs):
        batch_i, keeps_i, w_i = xs
        full = [None] * n_leaves
        for i, k in zip(keep_idx, keeps_i):
            full[i] = k
        masks = pruning.masks_from_keep(params, full, block)
        pruned = pruning.apply_masks(params, masks)
        loss, g = jax.value_and_grad(loss_fn)(pruned, batch_i)
        g = pruning.apply_masks(g, masks)
        acc = jax.tree.map(lambda a, gi: a + w_i * gi, acc, g)
        return acc, loss

    # accumulate at >= f32 whatever the param dtype (bf16 sums drift); the
    # weight dtype participates too (x64 weights promote f32 grads)
    acc_dtype = jnp.promote_types(weights.dtype, jnp.float32)
    init = jax.tree.map(
        lambda p: jnp.zeros(p.shape, jnp.promote_types(p.dtype, acc_dtype)),
        params)
    g_wsum, losses = jax.lax.scan(body, init, (batch, keeps_p, weights))
    return g_wsum, losses


# ---------------------------------------------------------------------------
# vmap + AD oracle and the public dispatcher
# ---------------------------------------------------------------------------

def reference_grads(params: dict, x: jnp.ndarray, y: jnp.ndarray,
                    rho: jnp.ndarray, weights: jnp.ndarray,
                    block: int) -> tuple[dict, jnp.ndarray]:
    """The vmap oracle: per-client ``block_masks`` + ``value_and_grad`` +
    re-mask, weighted-reduced with einsum.  Materializes the
    (clients, params) batch — test/benchmark baseline only."""
    from repro.models import mlp

    def one(xi, yi, ri):
        masks = pruning.block_masks(params, ri, block=block)
        pruned = pruning.apply_masks(params, masks)
        loss, g = jax.value_and_grad(
            lambda p: mlp.classifier_loss(p, xi, yi))(pruned)
        return loss, pruning.apply_masks(g, masks)

    losses, grads = jax.vmap(one)(x, y, rho)
    g_wsum = jax.tree.map(
        lambda g: jnp.einsum("c,c...->...", weights, g), grads)
    return g_wsum, losses


def fused_fleet_grads(params: dict, x: jnp.ndarray, y: jnp.ndarray,
                      keeps: Sequence[jnp.ndarray], weights: jnp.ndarray,
                      block: int, impl: str = "auto",
                      interpret: Optional[bool] = None
                      ) -> tuple[dict, jnp.ndarray]:
    """Dispatch the fused pruned-gradient computation.

    ``impl``: "auto" (Pallas on TPU, XLA elsewhere), "xla", or "pallas".
    ``interpret`` forces/disables Pallas interpret mode (default: interpret
    off-TPU so the kernel body still executes — the CI fallback).
    """
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl == "xla":
        return fused_grads_xla(params, x, y, keeps, weights, block)
    if impl == "pallas":
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        return fused_grads_pallas(params, x, y, keeps, weights, block,
                                  interpret=interpret)
    raise ValueError(f"impl must be 'auto', 'xla' or 'pallas', got {impl!r}")
