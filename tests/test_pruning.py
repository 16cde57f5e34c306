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
