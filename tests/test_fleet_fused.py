"""Fused pruned-gradient hot path (kernels/fleet_fused.py) + engine wiring.

Pins the ISSUE-3 contract:

* the XLA tile-loop implementation and the Pallas kernel (interpret mode
  on CPU) equal the vmap + AD + ``block_masks`` oracle per call;
* the engine's ``kernel="fused"`` trajectory equals the vmap reference
  (``kernel="reference"``, ``mask_kind="block"``) to 1e-5 in *both*
  aggregation modes (run under x64 so only the algorithm — not fp32
  reduction order — can separate the paths);
* fused runs are deterministic, learn, and validate their config.
"""

import contextlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.fleet import AsyncConfig, FleetConfig, FleetTopology, run_fleet
from repro.kernels import fleet_fused as FF
from repro.models import mlp

BLOCK = 8


@contextlib.contextmanager
def x64():
    with jax.enable_x64(True):
        yield


def _problem(c=13, batch=8, dim=32, hidden=(16,), classes=4, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    params = mlp.init_mlp_classifier(ks[0], dim, hidden, classes)
    x = jax.random.normal(ks[1], (c, batch, dim))
    y = jax.random.randint(ks[2], (c, batch), 0, classes)
    rho = jnp.concatenate([jnp.zeros(1), jnp.full((1,), 0.7),
                           jax.random.uniform(ks[3], (c - 2,)) * 0.7])
    w = jnp.concatenate([jnp.zeros(1),
                         jax.random.uniform(ks[4], (c - 1,)) * 50])
    return params, x, y, rho, w


def _assert_trees_close(a, b, **tol):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb), **tol)


# ---------------------------------------------------------------------------
# per-call equivalence: oracle vs XLA vs Pallas(interpret)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("hidden", [(16,), (16, 8)])
def test_fused_xla_matches_vmap_oracle(hidden):
    params, x, y, rho, w = _problem(hidden=hidden)
    keeps = FF.layer_keeps(FF.layer_norm_states(params, BLOCK), rho)
    g_ref, l_ref = FF.reference_grads(params, x, y, rho, w, BLOCK)
    g_xla, l_xla = FF.fused_grads_xla(params, x, y, keeps, w, BLOCK)
    _assert_trees_close(g_ref, g_xla, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(l_ref), np.asarray(l_xla),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("hidden", [(16,), (16, 8)])
def test_fused_pallas_interpret_matches_xla(hidden):
    params, x, y, rho, w = _problem(hidden=hidden)
    keeps = FF.layer_keeps(FF.layer_norm_states(params, BLOCK), rho)
    g_xla, l_xla = FF.fused_grads_xla(params, x, y, keeps, w, BLOCK)
    g_pl, l_pl = FF.fused_grads_pallas(params, x, y, keeps, w, BLOCK,
                                       interpret=True)
    _assert_trees_close(g_xla, g_pl, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(l_xla), np.asarray(l_pl),
                               rtol=1e-5, atol=1e-6)


def test_fused_zero_weights_drop_clients():
    """weights = 0 removes a client from the gradient sum exactly."""
    params, x, y, rho, w = _problem()
    keeps = FF.layer_keeps(FF.layer_norm_states(params, BLOCK), rho)
    g_all, _ = FF.fused_grads_xla(params, x, y, keeps, w, BLOCK)
    w0 = w.at[3].set(0.0)
    g_drop, _ = FF.fused_grads_xla(params, x, y, keeps, w0, BLOCK)
    keeps1 = [k[3:4] for k in keeps]
    g_one, _ = FF.fused_grads_xla(params, x[3:4], y[3:4], keeps1, w[3:4],
                                  BLOCK)
    recomposed = jax.tree.map(lambda a, b: a + b, g_drop, g_one)
    _assert_trees_close(g_all, recomposed, rtol=2e-5, atol=2e-5)


def test_fused_all_pruned_client_has_zero_weight_grads():
    """rho = 1 keeps nothing: that client's weight gradients vanish (its
    bias path survives — biases are never pruned)."""
    params, x, y, _, _ = _problem(c=3)
    rho = jnp.ones((3,))
    keeps = FF.layer_keeps(FF.layer_norm_states(params, BLOCK), rho)
    g, _ = FF.fused_grads_xla(params, x, y, keeps, jnp.ones((3,)), BLOCK)
    for name in g:
        np.testing.assert_allclose(np.asarray(g[name]["w"]), 0.0)


def test_layer_keeps_match_block_masks():
    """Tile keeps from the shared norm state == pruning.block_masks."""
    from repro.core import pruning
    params, _, _, rho, _ = _problem()
    states = FF.layer_norm_states(params, BLOCK)
    keeps = FF.layer_keeps(states, rho)
    for ci in range(rho.shape[0]):
        masks = pruning.block_masks(params, rho[ci], block=BLOCK)
        ws, _ = FF.layer_weights(params)
        for l in range(len(ws)):
            m = np.asarray(masks[f"layer{l}"]["w"])
            tk, tn = keeps[l].shape[1:]
            got = np.asarray(keeps[l][ci])
            for ti in range(tk):
                for uj in range(tn):
                    tile = m[ti * BLOCK:(ti + 1) * BLOCK,
                             uj * BLOCK:(uj + 1) * BLOCK]
                    assert (tile.any() > 0) == (got[ti, uj] > 0)


def test_fused_dispatch_validates():
    params, x, y, rho, w = _problem(c=3)
    keeps = FF.layer_keeps(FF.layer_norm_states(params, BLOCK), rho)
    with pytest.raises(ValueError, match="impl"):
        FF.fused_fleet_grads(params, x, y, keeps, w, BLOCK, impl="tpu")


# ---------------------------------------------------------------------------
# engine trajectories: fused == vmap reference (sync and async)
# ---------------------------------------------------------------------------

def tiny(rounds=6, **kw):
    return FleetConfig(
        topology=FleetTopology(num_cells=3, clients_per_cell=8),
        rounds=rounds, **kw)


def test_engine_fused_sync_matches_vmap_reference():
    with x64():
        ref = run_fleet(tiny(kernel="reference", mask_kind="block"))
        fused = run_fleet(tiny(kernel="fused"))
    np.testing.assert_allclose(fused.losses, ref.losses, rtol=1e-5,
                               atol=1e-8)
    np.testing.assert_allclose(fused.accuracy, ref.accuracy, rtol=1e-5,
                               atol=1e-8)
    np.testing.assert_allclose(fused.latencies, ref.latencies, rtol=1e-5)
    np.testing.assert_allclose(fused.mean_prune, ref.mean_prune, rtol=1e-5,
                               atol=1e-8)
    _assert_trees_close(fused.params, ref.params, rtol=1e-5, atol=1e-8)


def test_engine_fused_async_matches_vmap_reference():
    kw = dict(rounds=6,
              async_config=AsyncConfig(buffer_size=6, max_staleness=4))
    with x64():
        ref = run_fleet(tiny(kernel="reference", mask_kind="block", **kw),
                        mode="async")
        fused = run_fleet(tiny(kernel="fused", **kw), mode="async")
    np.testing.assert_allclose(fused.losses, ref.losses, rtol=1e-5,
                               atol=1e-8)
    np.testing.assert_allclose(fused.staleness, ref.staleness, rtol=1e-5,
                               atol=1e-8)
    np.testing.assert_allclose(fused.wall_clock, ref.wall_clock, rtol=1e-5)
    _assert_trees_close(fused.params, ref.params, rtol=1e-5, atol=1e-8)


def test_engine_fused_sync_chunked_matches_unchunked():
    """Chunked accumulation stays exact on the fused path too."""
    with x64():
        a = run_fleet(tiny(rounds=3, kernel="fused"))
        b = run_fleet(tiny(rounds=3, kernel="fused", cell_chunk=2))
    np.testing.assert_allclose(a.losses, b.losses, rtol=1e-6, atol=1e-9)


def test_engine_fused_learns_and_is_deterministic():
    a = run_fleet(tiny(rounds=8, kernel="fused"))
    assert np.all(np.isfinite(a.losses))
    assert a.losses[-1] < a.losses[0]
    b = run_fleet(tiny(rounds=8, kernel="fused"))
    np.testing.assert_allclose(a.losses, b.losses)
    c = run_fleet(tiny(rounds=8, kernel="fused", seed=1))
    assert not np.allclose(a.losses, c.losses)


def test_engine_fused_pallas_interpret_smoke():
    """The Pallas kernel body executes end-to-end inside the round scan
    (interpret mode on CPU — the CI fallback)."""
    cfg = FleetConfig(topology=FleetTopology(num_cells=1,
                                             clients_per_cell=4),
                      rounds=2, kernel="fused_pallas")
    res = run_fleet(cfg)
    assert np.all(np.isfinite(res.losses))
    xla = run_fleet(FleetConfig(topology=FleetTopology(
        num_cells=1, clients_per_cell=4), rounds=2, kernel="fused_xla"))
    np.testing.assert_allclose(res.losses, xla.losses, rtol=2e-5, atol=1e-6)


def test_engine_kernel_validation():
    with pytest.raises(ValueError, match="kernel"):
        run_fleet(tiny(rounds=2, kernel="turbo"))
    with pytest.raises(ValueError, match="mask_kind"):
        run_fleet(tiny(rounds=2, mask_kind="row"))


def test_engine_cache_data_matches_streaming():
    """The build-time data cache is a pure optimization: identical draws,
    identical trajectory."""
    a = run_fleet(tiny(rounds=3, kernel="fused", cache_data=True))
    b = run_fleet(tiny(rounds=3, kernel="fused", cache_data=False))
    np.testing.assert_allclose(a.losses, b.losses, rtol=1e-6, atol=1e-7)
