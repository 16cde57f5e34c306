"""Tests for core/pruning.py: unstructured + TPU block-structured masks."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import pruning


def _params(key=0):
    k = jax.random.PRNGKey(key)
    ks = jax.random.split(k, 4)
    return {
        "w1": jax.random.normal(ks[0], (96, 160)),
        "w2": jax.random.normal(ks[1], (160, 64)),
        "bias": jax.random.normal(ks[2], (160,)),
        "stacked": jax.random.normal(ks[3], (3, 64, 96)),  # layer-stacked
    }


def test_ones_masks_identity():
    p = _params()
    m = pruning.ones_masks(p)
    out = pruning.apply_masks(p, m)
    for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(out)):
        np.testing.assert_allclose(a, b)


@pytest.mark.parametrize("rate", [0.0, 0.3, 0.5, 0.7, 0.9])
def test_magnitude_masks_rate(rate):
    p = _params()
    m = pruning.magnitude_masks(p, rate)
    achieved = float(pruning.achieved_rate(p, m))
    assert achieved == pytest.approx(rate, abs=0.02)


def test_magnitude_masks_keep_biases():
    p = _params()
    m = pruning.magnitude_masks(p, 0.9)
    np.testing.assert_allclose(np.asarray(m["bias"]), 1.0)


def test_magnitude_masks_prune_smallest():
    p = {"w": jnp.asarray([[0.01, -5.0], [3.0, -0.02]])}
    m = pruning.magnitude_masks(p, 0.5)
    np.testing.assert_allclose(np.asarray(m["w"]), [[0.0, 1.0], [1.0, 0.0]])


@pytest.mark.parametrize("rate", [0.0, 0.25, 0.5, 0.75])
@pytest.mark.parametrize("block", [16, 32])
def test_block_masks_rate(rate, block):
    p = _params()
    m = pruning.block_masks(p, rate, block=block)
    achieved = float(pruning.achieved_rate(p, m))
    # block granularity: achieved within one tile mass of requested
    assert achieved == pytest.approx(rate, abs=0.08)


def test_block_masks_are_block_structured():
    p = _params()
    block = 32
    m = pruning.block_masks(p, 0.5, block=block)
    w = np.asarray(m["w1"])  # (96, 160)
    tiles = w.reshape(96 // block, block, 160 // block, block)
    per_tile = tiles.sum(axis=(1, 3))
    # every tile fully kept or fully dropped
    assert np.all((per_tile == 0) | (per_tile == block * block))


def test_block_masks_rank_by_norm():
    """Lowest-L2 tiles go first."""
    w = np.ones((64, 64), np.float32)
    w[:32, :32] = 0.01        # weakest tile
    p = {"w": jnp.asarray(w)}
    m = pruning.block_masks(p, 0.25, block=32)
    mm = np.asarray(m["w"])
    assert mm[:32, :32].sum() == 0
    assert mm[32:, 32:].sum() == 32 * 32


def test_block_masks_ragged_edges():
    """Non-multiple shapes: padding never keeps phantom elements."""
    p = {"w": jax.random.normal(jax.random.PRNGKey(0), (70, 50))}
    m = pruning.block_masks(p, 0.4, block=32)
    assert m["w"].shape == (70, 50)
    achieved = float(pruning.achieved_rate(p, m))
    assert 0.1 < achieved < 0.7


def test_block_masks_stacked_leading_dims():
    p = {"w": jax.random.normal(jax.random.PRNGKey(0), (4, 64, 64))}
    m = pruning.block_masks(p, 0.5, block=32)
    assert m["w"].shape == (4, 64, 64)
    achieved = float(pruning.achieved_rate(p, m))
    assert achieved == pytest.approx(0.5, abs=0.1)


def test_apply_masks_zeroes():
    p = _params()
    m = pruning.magnitude_masks(p, 0.5)
    out = pruning.apply_masks(p, m)
    w = np.asarray(out["w1"])
    mask = np.asarray(m["w1"])
    assert np.all(w[mask == 0.0] == 0.0)
    np.testing.assert_allclose(w[mask == 1.0],
                               np.asarray(p["w1"])[mask == 1.0])


def test_block_masks_leaf_scope_preserves_every_tensor():
    """Per-leaf ranking: a small-scale tensor (0.02-std embedding) is never
    annihilated by large-scale neighbours (the scope='global' failure)."""
    k = jax.random.PRNGKey(0)
    p = {"embed": jax.random.normal(k, (96, 64)) * 0.02,
         "dense": jax.random.normal(jax.random.PRNGKey(1), (96, 64)) * 0.1}
    m = pruning.block_masks(p, 0.5, block=16, scope="leaf")
    for name in ("embed", "dense"):
        kept = float(jnp.mean(m[name]))
        assert kept == pytest.approx(0.5, abs=0.1), name
    # global scope on the same params kills the embedding first
    g = pruning.block_masks(p, 0.5, block=16, scope="global")
    assert float(jnp.mean(g["embed"])) < 0.1
    assert float(jnp.mean(g["dense"])) > 0.9


def test_block_masks_jittable():
    """rho can be a traced scalar (per-client on-the-fly mask generation)."""
    p = _params()

    @jax.jit
    def f(rate):
        m = pruning.block_masks(p, rate, block=32)
        return pruning.achieved_rate(p, m)

    a = float(f(jnp.asarray(0.5)))
    assert a == pytest.approx(0.5, abs=0.1)


# ---------------------------------------------------------------------------
# once-per-round threshold state (the fleet engine's fused-path mask source)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rate", [0.0, 0.25, 0.5, 0.75, 1.0])
def test_masks_from_state_equals_block_masks(rate):
    """The factored state path is the same function as block_masks."""
    p = _params()
    state = pruning.block_norm_state(p, block=32)
    got = pruning.masks_from_state(p, state, rate, block=32)
    want = pruning.block_masks(p, rate, block=32, scope="leaf")
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_block_thresholds_monotone_in_rate():
    p = _params()
    state = pruning.block_norm_state(p, block=32)
    rates = jnp.linspace(0.0, 1.0, 11)
    for st in state:
        if st is None:
            continue
        t = np.asarray(pruning.block_thresholds(st, rates))
        assert np.all(np.diff(t) >= 0.0)      # more pruning, higher bar


def test_block_keep_batched_matches_scalar():
    """One searchsorted per client == per-client block_masks, tile-wise."""
    p = {"w": jax.random.normal(jax.random.PRNGKey(0), (96, 64))}
    state = pruning.block_norm_state(p, block=32)
    rates = jnp.asarray([0.0, 0.2, 0.5, 0.9])
    keeps = pruning.block_keep(state, rates)[0]     # (4, 3, 2)
    assert keeps.shape == (4, 3, 2)
    for ci, r in enumerate(rates):
        m = np.asarray(pruning.block_masks(p, r, block=32)["w"])
        tiles = m.reshape(3, 32, 2, 32).sum(axis=(1, 3)) > 0
        np.testing.assert_array_equal(np.asarray(keeps[ci]) > 0, tiles)


@pytest.mark.parametrize("block", [4, (3, 5)])
def test_block_rate_one_keeps_nothing(block):
    """rho = 1: every tile's mass is <= the total, so every tile drops —
    the keep indicators and the element masks agree."""
    params = {"w": jax.random.normal(jax.random.PRNGKey(1), (12, 20)),
              "b": jnp.ones((20,))}
    state = pruning.block_norm_state(params, block)
    keep = pruning.block_keep(state, jnp.ones((2,)))
    assert float(jnp.sum(keep[1])) == 0.0
    masks = pruning.block_masks(params, 1.0, block=block)
    assert not bool(jnp.any(masks["w"]))
    assert bool(jnp.all(masks["b"]))


def test_block_norm_state_skips_unprunable_leaves():
    p = _params()
    state = pruning.block_norm_state(p, block=32)
    leaves, _, flags = pruning._flatten_prunable(p)
    assert len(state) == len(leaves)
    for st, f in zip(state, flags):
        assert (st is None) == (not f)


# ---------------------------------------------------------------------------
# magnitude thresholds by bit-pattern selection == the jnp.quantile rule
# ---------------------------------------------------------------------------

from fractions import Fraction  # noqa: E402

SIZES = {"below": (41, 37), "above": (1024, 1024)}   # + a (3, 7, 11) leaf


def _select_params(kind, size, dtype):
    rng = np.random.default_rng(
        ["normal", "ties", "zeros", "signed_zeros", "inf", "nan"].index(kind))
    a = rng.normal(size=SIZES[size]).astype(np.float32)
    b = rng.normal(size=(3, 7, 11)).astype(np.float32)
    if kind == "ties":          # a handful of distinct magnitudes
        a, b = np.round(a * 2) / 2, np.round(b * 2) / 2
    elif kind == "zeros":
        a, b = 0.0 * a, 0.0 * b
    elif kind == "signed_zeros":  # +0 and -0 half and half, a few values
        a = np.where(rng.random(a.shape) < 0.5, 0.0, -0.0) \
            * np.ones_like(a)
        a.flat[:5] = [1.0, -2.0, 0.5, -0.5, 3.0]
    elif kind == "inf":
        a.flat[:3] = [np.inf, -np.inf, np.inf]
    elif kind == "nan":
        b.flat[4] = np.nan
    return {"a": jnp.asarray(a, dtype), "b": jnp.asarray(b, dtype),
            "bias": jnp.ones((7,), dtype)}


def _select_rates(n, seed=0):
    """0, 1, out-of-range rates, rates whose q (n - 1) is an exact float32
    integer, and random rates."""
    nm1 = np.float32(np.float32(n) - np.float32(1))
    exact = [q for k in (1, n // 3, n // 2, n - 2)
             for q in [np.float32(k / (n - 1))]
             if np.floor(q * nm1) == q * nm1]
    rand = np.random.default_rng(seed).random(4)
    return np.asarray([0.0, 1.0, -0.25, 1.5, *exact, *rand], np.float32)


def _mags(p):
    return jnp.concatenate([jnp.abs(l).reshape(-1)
                            for l in jax.tree.leaves(p) if l.ndim >= 2])


def _round32(x: Fraction) -> np.float32:
    """x rounded once to float32, to nearest, ties to even."""
    f = np.float32(float(x))
    near = [np.nextafter(f, np.float32(-np.inf)), f,
            np.nextafter(f, np.float32(np.inf))]
    return min(near, key=lambda c: (abs(Fraction(float(c)) - x),
                                    int(c.view(np.uint32)) & 1))


def _rule_roundings(mags: np.ndarray, q: np.float32) -> set:
    """The float32 values jnp.quantile's linear rule may give at rate q:
    products rounded on their own, or either one contracted into the sum
    by a fused multiply-add (the backend's choice)."""
    n = mags.size
    nq = np.float32(n)
    pos = np.float32(q * np.float32(nq - np.float32(1)))
    lo, hi = np.floor(pos), np.ceil(pos)
    hw = np.float32(pos - lo)
    lw = np.float32(np.float32(1) - hw)
    lv = mags[min(int(min(lo, nq - 1)), n - 1)]
    hv = mags[min(int(min(hi, nq - 1)), n - 1)]
    with np.errstate(invalid="ignore"):
        p_lo, p_hi = np.float32(lv * lw), np.float32(hv * hw)
        out = {float(np.float32(p_lo + p_hi))}
    if np.isfinite(lv) and np.isfinite(hv):
        out.add(float(_round32(Fraction(float(lv)) * Fraction(float(lw))
                               + Fraction(float(p_hi)))))
        out.add(float(_round32(Fraction(float(hv)) * Fraction(float(hw))
                               + Fraction(float(p_lo)))))
    return out


@pytest.mark.parametrize("size", sorted(SIZES))
@pytest.mark.parametrize("path", ["plain", "vmap"])
@pytest.mark.parametrize("kind", ["normal", "ties", "zeros", "signed_zeros",
                                  "inf", "nan"])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_magnitude_selection_equals_quantile_rule(dtype, kind, path, size):
    p = _select_params(kind, size, jnp.dtype(dtype))
    n = int(_mags(p).size)
    assert pruning.uses_selection(p) == (size == "above")
    rates = _select_rates(n)

    def quantile(p, r):
        return jnp.quantile(_mags(p), jnp.clip(r, 0.0, 1.0))

    def parent_masks(p, r):
        return pruning.threshold_masks(p, quantile(p, r))

    if path == "vmap":
        got = jax.jit(jax.vmap(pruning.magnitude_thresholds, (None, 0)))(
            p, rates)
        want = jax.jit(jax.vmap(quantile, (None, 0)))(p, rates)
        masks = jax.jit(jax.vmap(pruning.magnitude_masks, (None, 0)))(
            p, rates)
        parent = jax.jit(jax.vmap(parent_masks, (None, 0)))(p, rates)
    else:
        got = jax.jit(pruning.magnitude_thresholds)(p, rates)
        want = jax.jit(quantile)(p, rates)
        # one rate a call, against the quantile's masks at its threshold
        one, ref = (jax.jit(pruning.magnitude_masks),
                    jax.jit(pruning.threshold_masks))
        stack = lambda ms: jax.tree.map(lambda *l: jnp.stack(l), *ms)
        masks = stack([one(p, r) for r in rates])
        parent = stack([ref(p, t) for t in want])
    assert got.dtype == want.dtype == jnp.dtype(dtype)
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)

    sorted_mags = np.sort(np.asarray(_mags(p), np.float32))
    if np.isnan(sorted_mags).any():
        assert np.isnan(got).all() and np.isnan(want).all()
    for q, g, w in zip(rates, got, want):
        qc = np.float32(np.clip(q, 0.0, 1.0))
        pos = np.float32(qc * np.float32(np.float32(n) - np.float32(1)))
        if dtype == "bfloat16" or np.floor(pos) == pos or np.isnan(w):
            # nothing for a contraction to round: equal bit for bit
            assert g.tobytes() == w.tobytes() or (np.isnan(g) and np.isnan(w)
                                                  ), (q, g, w)
        else:
            allowed = _rule_roundings(sorted_mags, qc)
            assert float(w) in allowed, (q, w, allowed)
            assert float(g) in allowed, (q, g, allowed)
    for a, b in zip(jax.tree.leaves(masks), jax.tree.leaves(parent)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
