"""Block-sparse transformer for serving pruned fleet checkpoints.

Takes a ``PrunedBundle`` (params + the training tile keeps) and builds a
decode/prefill model whose every weight matrix is a ``sparse.make_linear``
layer over the *same* tile grid the training round pruned with.  The
contract is dense-masked equivalence: for any impl, outputs match
``models.model.decode_step`` on ``pruning.apply_masks``-masked params (up
to matmul reassociation) while compute scales with the kept-tile count.

Layers are unrolled at build time (the stacked leading-``repeats`` dim of
the training layout is host-sliced per layer) because the gather impl
needs *static* per-layer tile index sets — the serving analogue of the
training side's traced per-tile ``lax.cond``.

Attention gets a second, coarser skip for free: a KV head whose ``wv``
columns are all pruned produces exactly-zero values, and one whose whole
query group's ``wo`` rows are pruned contributes exactly zero to the
residual — either way the head's attention is dead weight, so its
per-head ``head_mask`` entry is dropped and the mask-aware kernels
(``ops.flash_decode`` / ``ops.flash_prefill``) never touch its cache.
(``wv`` liveness is only used when there is no qkv bias — a bias makes
pruned-column values nonzero.)

Scope: llama-family decoders (pre-norm attn+MLP blocks, global causal
GQA, no MoE/MLA/recurrent mixers, no encoder/memory) — which covers the
fleet tasks' smoke variants.  Everything computes in float32, every
matmul at full float32 precision (see serve/sparse.py for why).  KV
caches are head-major, (B, Hkv, S, hd), the layout the attention kernels
read, so a decode step never relayouts the cache.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.models import attention as A
from repro.models import blocks as B
from repro.models import layers as L
from repro.serve import sparse

PyTree = Any


def _validate(cfg) -> None:
    if getattr(cfg, "encoder_layers", 0) or getattr(cfg, "num_memory_tokens", 0):
        raise NotImplementedError("serve: encoder/memory models unsupported")
    for stage in cfg.stages:
        for spec in stage.blocks:
            if spec.kind != "attn":
                raise NotImplementedError(
                    f"serve: block kind {spec.kind!r} unsupported "
                    "(llama-family attn blocks only)")
            if spec.ffn not in ("mlp", "none", None):
                raise NotImplementedError(
                    f"serve: ffn kind {spec.ffn!r} unsupported")
    aspec = cfg.attn_spec("attn")
    if aspec.window is not None:
        raise NotImplementedError("serve: windowed attention unsupported")
    if aspec.softmax_scale is not None \
            and aspec.softmax_scale != aspec.head_dim ** -0.5:
        raise NotImplementedError("serve: custom softmax scale unsupported")


def _tile_live(keep: np.ndarray, block: int, axis: int,
               span: int, count: int) -> np.ndarray:
    """Per-head liveness: head h is live iff any kept tile intersects its
    [h*span, (h+1)*span) slice of the given axis of the tile grid."""
    kp = np.asarray(keep) > 0
    live = np.zeros(count, bool)
    for h in range(count):
        lo, hi = h * span, (h + 1) * span
        t_lo, t_hi = lo // block, -(-hi // block)
        sub = kp[:, t_lo:t_hi] if axis == 1 else kp[t_lo:t_hi, :]
        live[h] = bool(sub.any())
    return live


def _expand_keep(keep: np.ndarray, blk: tuple[int, int],
                 shape: tuple[int, ...]) -> np.ndarray:
    bk, bn = blk
    em = np.repeat(np.repeat(np.asarray(keep) > 0, bk, axis=-2), bn, axis=-1)
    return em[..., :shape[-2], :shape[-1]]


class SparseModel:
    """Unrolled block-sparse decoder over a ``PrunedBundle``.

    Static structure (tile plans, head masks, shapes) lives on ``self``;
    device weights live in ``self.arrays`` — pass them through your jit
    boundary so they aren't baked into executables.
    """

    def __init__(self, cfg, bundle, impl: str = "gather",
                 attn_impl: str = "xla", interpret: Optional[bool] = None):
        _validate(cfg)
        self.cfg = cfg
        self.impl = impl
        self.attn_impl = attn_impl
        self.interpret = interpret
        self.aspec = cfg.attn_spec("attn")
        params, keeps, grid = bundle.params, bundle.keeps, bundle.grid

        leaves, treedef = jax.tree_util.tree_flatten(params)
        idx = jax.tree_util.tree_unflatten(treedef, list(range(len(leaves))))

        def leaf_info(inode, r=None):
            """(masked f32 leaf, keep, (bk, bn)) for one flat index,
            optionally sliced at stacked-layer ``r``."""
            i = inode
            leaf = jnp.asarray(leaves[i], jnp.float32)
            keep, blk = keeps[i], grid[i]
            if keep is not None:
                em = _expand_keep(keep, blk, np.shape(leaves[i]))
                leaf = leaf * jnp.asarray(em, jnp.float32)
            if r is not None:
                leaf = leaf[r]
                keep = None if keep is None else np.asarray(keep)[r]
            return leaf, keep, blk

        def lin(pnode, inode, r=None):
            w, keep, blk = leaf_info(inode["w"], r)
            bias = None
            if "b" in pnode:
                b = jnp.asarray(leaves[inode["b"]], jnp.float32)
                bias = b if r is None else b[r]
            if blk is None:
                blk = (w.shape[0], w.shape[1])
            return sparse.make_linear(w, keep, blk, impl=impl, bias=bias)

        def norm(pnode, inode, r=None):
            out = {}
            for key in pnode:
                v = jnp.asarray(leaves[inode[key]], jnp.float32)
                out[key] = v if r is None else v[r]
            return out

        arrays: dict = {"layers": []}
        self.layers: list[dict] = []
        hkv, hd, g = (self.aspec.num_kv_heads, self.aspec.head_dim,
                      self.aspec.num_heads // self.aspec.num_kv_heads)
        for si, stage in enumerate(cfg.stages):
            for r in range(stage.repeats):
                for bi, spec in enumerate(stage.blocks):
                    pn = params["stages"][si][f"b{bi}"]
                    ix = idx["stages"][si][f"b{bi}"]
                    plan: dict = {"has_ffn": "ffn" in pn}
                    la: dict = {"norm_mix": norm(pn["norm_mix"],
                                                 ix["norm_mix"], r)}
                    for nm in ("wq", "wk", "wv", "wo"):
                        plan[nm], la[nm] = lin(pn["attn"][nm],
                                               ix["attn"][nm], r)
                    plan["head_mask"] = self._head_mask(
                        keeps, grid, ix["attn"], r, hkv, hd, g)
                    if plan["has_ffn"]:
                        la["norm_ffn"] = norm(pn["norm_ffn"],
                                              ix["norm_ffn"], r)
                        for nm in pn["ffn"]:
                            plan[nm], la[nm] = lin(pn["ffn"][nm],
                                                   ix["ffn"][nm], r)
                        plan["gated"] = "w_gate" in pn["ffn"]
                    self.layers.append(plan)
                    arrays["layers"].append(la)

        # embedding / final norm / unembedding (embedding masked too — the
        # dense oracle sees masked params everywhere)
        e_leaf, e_keep, e_blk = leaf_info(idx["embed"]["embedding"])
        arrays["embed"] = e_leaf
        arrays["final_norm"] = norm(params["final_norm"], idx["final_norm"])
        if cfg.tie_embeddings:
            ub_keep = None if e_keep is None else np.asarray(e_keep).T
            ub_blk = (e_blk[1], e_blk[0]) if e_blk is not None \
                else (e_leaf.shape[1], e_leaf.shape[0])
            self.unembed, arrays["unembed"] = sparse.make_linear(
                e_leaf.T, ub_keep, ub_blk, impl=impl)
        else:
            self.unembed, arrays["unembed"] = lin(params["unembed"],
                                                  idx["unembed"])
        self.arrays = arrays

    # -- head liveness ----------------------------------------------------

    def _head_mask(self, keeps, grid, ix_attn, r, hkv, hd, g) -> np.ndarray:
        live = np.ones(hkv, bool)
        k_wo, b_wo = keeps[ix_attn["wo"]["w"]], grid[ix_attn["wo"]["w"]]
        if k_wo is not None:
            # wo rows of KV head h's query group: [h*g*hd, (h+1)*g*hd)
            live &= _tile_live(np.asarray(k_wo)[r], b_wo[0], 0, g * hd, hkv)
        if not self.aspec.qkv_bias:
            k_wv, b_wv = keeps[ix_attn["wv"]["w"]], grid[ix_attn["wv"]["w"]]
            if k_wv is not None:
                live &= _tile_live(np.asarray(k_wv)[r], b_wv[1], 1, hd, hkv)
        return live.astype(np.float32)

    # -- caches -----------------------------------------------------------

    def init_caches(self, batch: int, cache_len: int) -> list[dict]:
        shape = (batch, self.aspec.num_kv_heads, cache_len,
                 self.aspec.head_dim)
        return [{"k": jnp.zeros(shape, jnp.float32),
                 "v": jnp.zeros(shape, jnp.float32)}
                for _ in self.layers]

    # -- qkv helper -------------------------------------------------------

    def _qkv(self, plan, la, y, positions):
        sp = self.aspec
        q = A._split_heads(sparse.apply_linear(plan["wq"], la["wq"], y),
                           sp.num_heads)
        k = A._split_heads(sparse.apply_linear(plan["wk"], la["wk"], y),
                           sp.num_kv_heads)
        v = A._split_heads(sparse.apply_linear(plan["wv"], la["wv"], y),
                           sp.num_kv_heads)
        if sp.use_rope:
            q = L.apply_rope(q, positions, sp.rope_theta)
            k = L.apply_rope(k, positions, sp.rope_theta)
        return q, k, v

    def _ffn(self, plan, la, x):
        cfg = self.cfg
        y = B.norm_apply(cfg, la["norm_ffn"], x)
        h = sparse.apply_linear(plan["w_in"], la["w_in"], y)
        if plan["gated"]:
            h = L.ACTS[cfg.act](
                sparse.apply_linear(plan["w_gate"], la["w_gate"], y)) * h
        else:
            h = L.ACTS[cfg.act](h)
        return x + sparse.apply_linear(plan["w_out"], la["w_out"], h)

    # -- one-token decode -------------------------------------------------

    def decode_step(self, arrays, token: jnp.ndarray, caches: list,
                    pos: jnp.ndarray) -> tuple[jnp.ndarray, list]:
        """token: (B, 1) int32; pos: (B,) absolute position of ``token``.
        Returns (logits (B, V) f32, new caches)."""
        cfg = self.cfg
        b = token.shape[0]
        x = jnp.take(arrays["embed"], token, axis=0)      # (B, 1, d) f32
        new_caches = []
        for plan, la, cache in zip(self.layers, arrays["layers"], caches):
            y = B.norm_apply(cfg, la["norm_mix"], x)
            q, k, v = self._qkv(plan, la, y, pos[:, None])
            cache_len = cache["k"].shape[2]
            slot = jnp.minimum(pos, cache_len - 1)
            onehot = (jnp.arange(cache_len)[None, None, :, None]
                      == slot[:, None, None, None])
            new_k = jnp.where(onehot, k.transpose(0, 2, 1, 3), cache["k"])
            new_v = jnp.where(onehot, v.transpose(0, 2, 1, 3), cache["v"])
            attn = ops.flash_decode(q[:, 0], new_k, new_v, pos,
                                    head_mask=plan["head_mask"],
                                    impl=self.attn_impl,
                                    interpret=self.interpret)
            h = sparse.apply_linear(plan["wo"], la["wo"],
                                    attn.reshape(b, 1, -1))
            x = x + h
            if plan["has_ffn"]:
                x = self._ffn(plan, la, x)
            new_caches.append({"k": new_k, "v": new_v})
        x = B.norm_apply(cfg, arrays["final_norm"], x)
        logits = sparse.apply_linear(self.unembed, arrays["unembed"], x)
        return logits[:, 0], new_caches

    # -- full-sequence prefill --------------------------------------------

    def prefill(self, arrays, tokens: jnp.ndarray,
                cache_len: int) -> tuple[jnp.ndarray, list]:
        """tokens: (B, P) int32 at positions 0..P-1.  Returns
        (logits (B, P, V) f32, caches filled at [0, P))."""
        cfg = self.cfg
        b, p = tokens.shape
        sp = self.aspec
        x = jnp.take(arrays["embed"], tokens, axis=0)     # (B, P, d) f32
        positions = jnp.arange(p)[None, :]
        caches = []
        for plan, la in zip(self.layers, arrays["layers"]):
            y = B.norm_apply(cfg, la["norm_mix"], x)
            q, k, v = self._qkv(plan, la, y, positions)
            k, v = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
            attn = ops.flash_prefill(q, k, v, causal=True,
                                     head_mask=plan["head_mask"],
                                     impl=self.attn_impl,
                                     interpret=self.interpret)
            h = sparse.apply_linear(plan["wo"], la["wo"],
                                    attn.reshape(b, p, -1))
            x = x + h
            if plan["has_ffn"]:
                x = self._ffn(plan, la, x)
            shape = (b, sp.num_kv_heads, cache_len, sp.head_dim)
            ck = jnp.zeros(shape, jnp.float32).at[:, :, :p].set(k)
            cv = jnp.zeros(shape, jnp.float32).at[:, :, :p].set(v)
            caches.append({"k": ck, "v": cv})
        x = B.norm_apply(cfg, arrays["final_norm"], x)
        logits = sparse.apply_linear(self.unembed, arrays["unembed"], x)
        return logits, caches
