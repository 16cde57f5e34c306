"""Block-sparse serving engine: dense-masked equivalence, mask round-trip,
continuous batching, and the serving-cost trade-off term (PR 9).

The serve contract is that every layer of the stack — sparse linear,
mask-aware attention, SparseModel, ServeEngine — computes exactly what
the dense path computes on ``pruning.apply_masks``-masked params, while
compute scales with the kept-tile fraction.  Equivalence is asserted on
*logits* (argmax-token comparisons would hide drift); the export
round-trip is asserted bitwise (serve masks == training masks).
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, BlockSpec, StageSpec
from repro.core import pruning, tradeoff
from repro.fleet.task import TransformerTask
from repro.kernels import ops
from repro.models import model as M
from repro.serve import (PrunedBundle, ServeConfig, ServeEngine, SparseModel,
                         export_from_result, export_pruned, load_pruned,
                         make_bundle)
from repro.serve import sparse


# ---------------------------------------------------------------------------
# Shared tiny llama-family instance
# ---------------------------------------------------------------------------

def tiny_arch(**kw):
    base = dict(name="tiny-serve", family="dense", source="test",
                d_model=32, num_heads=4, num_kv_heads=2, d_ff=64,
                vocab_size=64,
                stages=(StageSpec(2, (BlockSpec("attn", "mlp"),)),))
    base.update(kw)
    return ArchConfig(**base)


@pytest.fixture(scope="module")
def setup():
    arch = tiny_arch()
    task = TransformerTask(arch=arch, target_tiles=4)
    params = task.init_params(jax.random.PRNGKey(0))
    return arch, task, params


# ---------------------------------------------------------------------------
# Sparse linear layers vs the masked-matmul oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", sparse.IMPLS)
@pytest.mark.parametrize("rho", [0.0, 0.5, 0.9, 1.0])
def test_linear_impls_match_oracle(impl, rho):
    """Every impl == x @ (w ⊙ expand(keep)), incl. ragged K/N tails."""
    key = jax.random.PRNGKey(3)
    k1, k2, k3 = jax.random.split(key, 3)
    kdim, n, bk, bn = 50, 70, 16, 32              # ragged: 50 % 16, 70 % 32
    tk, tn = -(-kdim // bk), -(-n // bn)
    w = jax.random.normal(k1, (kdim, n), jnp.float32)
    x = jax.random.normal(k2, (5, kdim), jnp.float32)
    drop = jax.random.uniform(k3, (tk, tn)) < rho
    keep = (~drop).astype(jnp.float32)
    plan, arrays = sparse.make_linear(w, keep, (bk, bn), impl=impl)
    got = sparse.apply_linear(plan, arrays, x)
    want = ops.oracle_masked_matmul(jnp.pad(x, ((0, 0), (0, tk * bk - kdim))),
                                    jnp.pad(w, ((0, tk * bk - kdim),
                                                (0, tn * bn - n))),
                                    keep, bk, bn)[:, :n]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_pallas_linear_pads_weight_once():
    """The pallas layer stores its weight in the kernel's hardware tiling
    at build (every 16x32 tile zero-padded to 128x128), so an apply pads
    only the activations."""
    w = jax.random.normal(jax.random.PRNGKey(0), (50, 70), jnp.float32)
    keep = jnp.asarray([[1.0, 0.0, 1.0], [0.0, 1.0, 1.0], [1.0, 1.0, 0.0],
                        [1.0, 0.0, 0.0]])
    plan, arrays = sparse.make_linear(w, keep, (16, 32), impl="pallas")
    assert arrays["w"].shape == (4 * 128, 3 * 128)
    x = jax.random.normal(jax.random.PRNGKey(1), (5, 50), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda a, x: sparse.apply_linear(plan, a, x))(arrays, x)
    call, = [e for e in jaxpr.jaxpr.eqns
             if e.params.get("name") == "block_sparse_matmul"]
    assert call.invars[1] in jaxpr.jaxpr.invars     # the stored weight
    got = sparse.apply_linear(plan, arrays, x)
    want = ops.masked_matmul(x, w, keep, block_k=16, block_n=32)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("impl", ["gather", "cond"])
def test_linear_all_pruned_and_all_dense(impl):
    w = jnp.ones((32, 48), jnp.float32)
    x = jnp.ones((3, 32), jnp.float32)
    plan, arrays = sparse.make_linear(w, jnp.zeros((2, 3)), (16, 16),
                                      impl=impl)
    np.testing.assert_array_equal(
        np.asarray(sparse.apply_linear(plan, arrays, x)), 0.0)
    plan, arrays = sparse.make_linear(w, jnp.ones((2, 3)), (16, 16),
                                      impl=impl)
    np.testing.assert_allclose(
        np.asarray(sparse.apply_linear(plan, arrays, x)), 32.0, rtol=1e-6)


def test_linear_bias_and_lead_dims():
    w = jax.random.normal(jax.random.PRNGKey(0), (32, 48), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(1), (48,), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 3, 32), jnp.float32)
    keep = jnp.ones((2, 3))
    plan, arrays = sparse.make_linear(w, keep, (16, 16), impl="gather",
                                      bias=b)
    got = sparse.apply_linear(plan, arrays, x)
    assert got.shape == (2, 3, 48)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(x.reshape(-1, 32) @ w + b
                                          ).reshape(2, 3, 48),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("impl", ["gather", "cond"])
def test_linear_impls_differentiable(impl):
    """The jnp/lax impls stay AD-able (serving-time calibration paths)."""
    w = jax.random.normal(jax.random.PRNGKey(0), (32, 32), jnp.float32)
    keep = (jax.random.uniform(jax.random.PRNGKey(1), (2, 2)) > 0.5
            ).astype(jnp.float32)
    plan, arrays = sparse.make_linear(w, keep, (16, 16), impl=impl)
    plan_d, arrays_d = sparse.make_linear(w, keep, (16, 16), impl="dense")

    def loss(fn_arrays, plan):
        def f(x):
            return jnp.sum(sparse.apply_linear(plan, fn_arrays, x) ** 2)
        return f

    x = jax.random.normal(jax.random.PRNGKey(2), (4, 32), jnp.float32)
    g = jax.grad(loss(arrays, plan))(x)
    g_ref = jax.grad(loss(arrays_d, plan_d))(x)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                               rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------------------
# Mask-aware attention kernels vs oracles
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("mask", [None, [1, 0, 1], [0, 0, 0]])
def test_decode_attention_head_mask(impl, mask):
    b, h, hkv, hd, s = 3, 6, 3, 8, 40
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (b, h, hd), jnp.float32)
    k = jax.random.normal(ks[1], (b, hkv, s, hd), jnp.float32)
    v = jax.random.normal(ks[2], (b, hkv, s, hd), jnp.float32)
    pos = jnp.array([0, 17, 39], jnp.int32)
    hm = None if mask is None else np.asarray(mask, np.float32)
    got = ops.flash_decode(q, k, v, pos, block_s=16, head_mask=hm, impl=impl)
    want = ops.oracle_flash_decode(q, k, v, pos, head_mask=hm)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("mask", [None, [0, 1]])
def test_prefill_attention_head_mask(impl, mask):
    b, s, h, hkv, hd = 2, 24, 4, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    q = jax.random.normal(ks[0], (b, s, h, hd), jnp.float32)
    k = jax.random.normal(ks[1], (b, hkv, s, hd), jnp.float32)
    v = jax.random.normal(ks[2], (b, hkv, s, hd), jnp.float32)
    hm = None if mask is None else np.asarray(mask, np.float32)
    got = ops.flash_prefill(q, k, v, causal=True, block_q=8, block_s=8,
                            head_mask=hm, impl=impl)
    want = ops.oracle_flash_prefill(q, k, v, causal=True, head_mask=hm)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# SparseModel == dense decode on masked params
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", ["gather", "dense"])
@pytest.mark.parametrize("rho", [0.0, 0.75, 1.0])
def test_sparse_model_matches_dense_masked(setup, impl, rho):
    arch, task, params = setup
    bundle = make_bundle(task, params, rho)
    masked = bundle.masked_params()
    model = SparseModel(arch, bundle, impl=impl, attn_impl="xla")
    b, t = 3, 6
    toks = jax.random.randint(jax.random.PRNGKey(1), (b, t), 0,
                              arch.vocab_size)
    cache = M.init_cache(arch, b, 16)
    caches = model.init_caches(b, 16)
    for i in range(t):
        ld, cache = M.decode_step(arch, masked, toks[:, i:i + 1], cache)
        ls, caches = model.decode_step(model.arrays, toks[:, i:i + 1],
                                       caches, jnp.full((b,), i, jnp.int32))
        np.testing.assert_allclose(np.asarray(ls),
                                   np.asarray(ld, np.float32),
                                   rtol=2e-4, atol=2e-4)


def test_sparse_model_pallas_impls(setup):
    """The Pallas matmul + Pallas attention stack agrees too."""
    arch, task, params = setup
    bundle = make_bundle(task, params, 0.5)
    masked = bundle.masked_params()
    model = SparseModel(arch, bundle, impl="pallas", attn_impl="pallas")
    b = 2
    toks = jax.random.randint(jax.random.PRNGKey(2), (b, 3), 0,
                              arch.vocab_size)
    cache = M.init_cache(arch, b, 8)
    caches = model.init_caches(b, 8)
    for i in range(3):
        ld, cache = M.decode_step(arch, masked, toks[:, i:i + 1], cache)
        ls, caches = model.decode_step(model.arrays, toks[:, i:i + 1],
                                       caches, jnp.full((b,), i, jnp.int32))
        np.testing.assert_allclose(np.asarray(ls),
                                   np.asarray(ld, np.float32),
                                   rtol=2e-4, atol=2e-4)


def test_prefill_matches_decode(setup):
    """Prefill logits == teacher-forced decode logits, and the prefilled
    cache continues identically."""
    arch, task, params = setup
    bundle = make_bundle(task, params, 0.5)
    model = SparseModel(arch, bundle, impl="gather", attn_impl="xla")
    b, t = 2, 5
    toks = jax.random.randint(jax.random.PRNGKey(4), (b, t), 0,
                              arch.vocab_size)
    lp, pcaches = model.prefill(model.arrays, toks, 8)
    caches = model.init_caches(b, 8)
    for i in range(t):
        ls, caches = model.decode_step(model.arrays, toks[:, i:i + 1],
                                       caches, jnp.full((b,), i, jnp.int32))
        np.testing.assert_allclose(np.asarray(lp[:, i]), np.asarray(ls),
                                   rtol=2e-4, atol=2e-4)
    nxt = jnp.argmax(lp[:, -1], -1)[:, None].astype(jnp.int32)
    l1, _ = model.decode_step(model.arrays, nxt, pcaches,
                              jnp.full((b,), t, jnp.int32))
    l2, _ = model.decode_step(model.arrays, nxt, caches,
                              jnp.full((b,), t, jnp.int32))
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                               rtol=2e-4, atol=2e-4)


def test_head_mask_derivation(setup):
    """Dead KV heads (wv columns or wo group rows fully pruned) are
    dropped; rho=0 keeps every head, rho=1 kills every head."""
    arch, task, params = setup
    live0 = SparseModel(arch, make_bundle(task, params, 0.0)).layers
    assert all(np.all(lp["head_mask"] > 0) for lp in live0)
    live1 = SparseModel(arch, make_bundle(task, params, 1.0)).layers
    assert all(np.all(lp["head_mask"] == 0) for lp in live1)


def test_validation_rejects_non_llama():
    arch = tiny_arch(stages=(StageSpec(1, (BlockSpec("mlstm", "mlp"),)),))
    task = TransformerTask(arch=arch, target_tiles=4)
    params = task.init_params(jax.random.PRNGKey(0))
    with pytest.raises(NotImplementedError):
        SparseModel(arch, make_bundle(task, params, 0.5))


# ---------------------------------------------------------------------------
# Export round-trip: serve masks == training masks, bitwise
# ---------------------------------------------------------------------------

def test_export_round_trip_bitwise(setup, tmp_path):
    arch, task, params = setup
    path = os.path.join(tmp_path, "bundle.npz")
    b0 = export_pruned(path, task, params, 0.75)
    b1 = load_pruned(path, task)
    assert b1.rho == pytest.approx(0.75)
    # masks through the file == masks straight from the training code path
    m_train = pruning.block_masks(params, jnp.float32(0.75),
                                  block=task.tile_grid(params))
    for m0, m1 in zip(jax.tree_util.tree_leaves(m_train),
                      jax.tree_util.tree_leaves(b1.masks())):
        np.testing.assert_array_equal(np.asarray(m0), np.asarray(m1))
    # params and keeps bitwise
    for a, b in zip(jax.tree_util.tree_leaves(b0.params),
                    jax.tree_util.tree_leaves(b1.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for ka, kb in zip(b0.keeps, b1.keeps):
        assert (ka is None) == (kb is None)
        if ka is not None:
            np.testing.assert_array_equal(np.asarray(ka), np.asarray(kb))


def test_export_from_fleet_result(setup, tmp_path):
    """A FleetResult-shaped record exports at its final mean prune rate."""
    arch, task, params = setup

    class FakeResult:
        pass

    res = FakeResult()
    res.params = params
    res.mean_prune = np.array([0.1, 0.3, 0.6])
    path = os.path.join(tmp_path, "fleet.npz")
    bundle = export_from_result(path, task, res)
    assert bundle.rho == pytest.approx(0.6)
    assert load_pruned(path, task).rho == pytest.approx(0.6)


# ---------------------------------------------------------------------------
# ServeEngine: continuous batching
# ---------------------------------------------------------------------------

def test_engine_slot_invariance_and_host_match(setup):
    """Tokens are independent of the slot count, equal to a per-request
    host loop, and wave (prefill+decode) mode agrees."""
    arch, task, params = setup
    model = SparseModel(arch, make_bundle(task, params, 0.5))
    r, p, g = 5, 3, 4
    prompts = np.random.RandomState(0).randint(
        0, arch.vocab_size, (r, p)).astype(np.int32)
    outs = {}
    for slots in (2, 8):
        eng = ServeEngine(model, ServeConfig(max_slots=slots, page_len=16,
                                             max_new=g))
        outs[slots] = eng.generate(prompts)
    np.testing.assert_array_equal(outs[2], outs[8])
    ref = []
    for rr in range(r):
        caches = model.init_caches(1, 16)
        gen = []
        for t in range(p + g - 1):
            tok = np.int32(prompts[rr, t] if t < p else gen[-1])
            lg, caches = model.decode_step(
                model.arrays, jnp.full((1, 1), tok, jnp.int32), caches,
                jnp.full((1,), t, jnp.int32))
            if t >= p - 1:
                gen.append(int(jnp.argmax(lg, -1)[0]))
        ref.append(gen)
    np.testing.assert_array_equal(outs[2], np.asarray(ref))
    eng = ServeEngine(model, ServeConfig(max_slots=8, page_len=16, max_new=g))
    np.testing.assert_array_equal(eng.generate_prefilled(prompts), outs[2])


def test_engine_logits_sparse_equals_dense(setup):
    """End-to-end: generated logits at rho=0.75 equal the dense engine on
    masked params (tokens can tie-break differently; logits cannot)."""
    arch, task, params = setup
    bundle = make_bundle(task, params, 0.75)
    sparse_m = SparseModel(arch, bundle, impl="gather")
    dense_m = SparseModel(arch, bundle, impl="dense")
    prompts = np.random.RandomState(1).randint(
        0, arch.vocab_size, (4, 3)).astype(np.int32)
    cfg = ServeConfig(max_slots=4, page_len=16, max_new=3)
    _, ls = ServeEngine(sparse_m, cfg).generate(prompts, return_logits=True)
    _, ld = ServeEngine(dense_m, cfg).generate(prompts, return_logits=True)
    np.testing.assert_allclose(ls, ld, rtol=2e-4, atol=2e-4)


def test_engine_rejects_overlong(setup):
    arch, task, params = setup
    model = SparseModel(arch, make_bundle(task, params, 0.5))
    eng = ServeEngine(model, ServeConfig(max_slots=2, page_len=8, max_new=8))
    with pytest.raises(ValueError):
        eng.generate(np.zeros((1, 4), np.int32))


# ---------------------------------------------------------------------------
# Serving-cost term in the trade-off objective
# ---------------------------------------------------------------------------

def _problem(weight=0.0004, seed=0, n=5):
    from repro.core.convergence import ConvergenceBound, SmoothnessParams
    from repro.core import wireless as W
    cfg = W.WirelessConfig()
    ch = W.Channel(n, seed=seed)
    h_up, h_down = ch.sample_gains()
    samples = np.resize([30, 40, 50], n).astype(np.float64)
    return tradeoff.TradeoffProblem(
        cfg=cfg, bound=ConvergenceBound(SmoothnessParams(), samples),
        h_up=h_up, h_down=h_down,
        tx_power=np.full(n, cfg.tx_power_ue_w), cpu_hz=np.full(n, 5e9),
        num_samples=samples, max_prune=np.full(n, 0.7), weight=weight)


def test_serving_cost_model_decreases_with_rho():
    sv = tradeoff.ServingCostModel(base_latency_s=0.02, overhead_frac=0.25)
    lats = [sv.per_token_latency(r) for r in (0.0, 0.25, 0.5, 1.0)]
    assert all(a > b for a, b in zip(lats, lats[1:]))
    assert lats[0] == pytest.approx(0.02)
    assert lats[-1] == pytest.approx(0.02 * 0.25)      # overhead floor


def test_serving_zero_weight_matches_plain():
    prob = _problem()
    base = tradeoff.solve_alternating(prob)
    z = tradeoff.solve_alternating(prob, serving=tradeoff.ServingCostModel(
        base_latency_s=0.02, weight=0.0))
    np.testing.assert_allclose(z.prune, base.prune, atol=1e-12)
    assert z.deadline == pytest.approx(base.deadline, rel=1e-12)


def test_serving_term_shifts_optimum_to_higher_rho():
    """At latency-dominated lambda the uplink-only solve prunes nothing;
    pricing serving in pulls the optimum to the high-rho vertex."""
    prob = _problem(weight=0.01)
    base = tradeoff.solve_alternating(prob)
    serv = tradeoff.solve_alternating(prob, serving=tradeoff.ServingCostModel(
        base_latency_s=0.02, overhead_frac=0.25, tokens_per_round=2000.0))
    assert float(np.mean(base.prune)) == pytest.approx(0.0, abs=1e-9)
    assert float(np.mean(serv.prune)) > 0.3
    assert serv.deadline < base.deadline


def test_serving_incompatible_with_scheduling_extensions():
    prob = _problem()
    sv = tradeoff.ServingCostModel(base_latency_s=0.02)
    with pytest.raises(NotImplementedError):
        tradeoff.solve_alternating(prob, mask=np.ones(5), serving=sv)
    with pytest.raises(NotImplementedError):
        tradeoff.solve_alternating(prob, deadline_cap=1.0, serving=sv)
