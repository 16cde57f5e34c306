"""Tests for packet-error-aware aggregation (paper Eq. (5)/(6))."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import aggregation as agg


def _grads(i=3, shape=(4, 5)):
    k = jax.random.PRNGKey(0)
    return {"w": jax.random.normal(k, (i,) + shape),
            "b": jax.random.normal(jax.random.PRNGKey(1), (i, shape[1]))}


def test_aggregate_matches_eq5():
    g = _grads()
    k = jnp.asarray([30.0, 40.0, 50.0])
    c = jnp.asarray([1.0, 0.0, 1.0])
    out = agg.aggregate(g, k, c)
    expect = (30 * np.asarray(g["w"][0]) + 50 * np.asarray(g["w"][2])) / 80.0
    np.testing.assert_allclose(np.asarray(out["w"]), expect, rtol=1e-6)


def test_aggregate_all_arrived_is_weighted_mean():
    g = _grads()
    k = jnp.asarray([1.0, 1.0, 2.0])
    c = jnp.ones(3)
    out = agg.aggregate(g, k, c)
    expect = (np.asarray(g["b"][0]) + np.asarray(g["b"][1])
              + 2 * np.asarray(g["b"][2])) / 4.0
    np.testing.assert_allclose(np.asarray(out["b"]), expect, rtol=1e-6)


def test_aggregate_all_dropped_returns_zero():
    """BS skips the update when every packet errored."""
    g = _grads()
    out = agg.aggregate(g, jnp.asarray([30.0, 40.0, 50.0]), jnp.zeros(3))
    np.testing.assert_allclose(np.asarray(out["w"]), 0.0)
    np.testing.assert_allclose(np.asarray(out["b"]), 0.0)


def test_sample_arrivals_statistics():
    per = jnp.asarray([0.0, 1.0, 0.5])
    keys = jax.random.split(jax.random.PRNGKey(0), 2000)
    draws = jax.vmap(lambda k: agg.sample_arrivals(k, per))(keys)
    mean = np.asarray(jnp.mean(draws, axis=0))
    assert mean[0] == pytest.approx(1.0)
    assert mean[1] == pytest.approx(0.0)
    assert mean[2] == pytest.approx(0.5, abs=0.05)


def test_psum_aggregate_matches_host_aggregate():
    """Device-side Eq. (5) == host Eq. (5) on a 1-axis mesh."""
    from jax.sharding import PartitionSpec as P

    n = jax.device_count()   # 1 on CPU: degenerate but still exercises psum
    mesh = jax.make_mesh((n,), ("clients",))
    g = _grads(i=n)
    k = jnp.arange(1.0, n + 1.0)
    c = jnp.ones(n)

    def body(gs, ks, cs):
        return agg.psum_aggregate(jax.tree.map(lambda x: x[0], gs),
                                  ks[0], cs[0], "clients")

    out = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P("clients"), P("clients"), P("clients")),
        out_specs=P()))(g, k, c)
    expect = agg.aggregate(g, k, c)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(expect)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)
