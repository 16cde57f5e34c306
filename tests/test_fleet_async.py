"""Asynchronous (FedBuff-style) buffered aggregation: equivalence + behaviour.

Covers the ISSUE-2 contract:

* buffer = cohort + zero staleness discount  ==  synchronous engine
  (trajectory equivalence to 1e-6, run under x64 so only algorithm — not
  summation order — can separate the paths);
* staleness discount schedules are monotone non-increasing in tau and
  normalized to s(0) = 1;
* the numpy and jax paths of ``core.aggregation.buffered_aggregate`` agree,
  and zero staleness reduces it to the paper's Eq. (5) ``aggregate``;
* on a straggler-heavy fleet the async engine reaches a target loss in
  less *simulated* wall-clock than the sync barrier (the FedBuff claim).
"""

import contextlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import aggregation as agg
from repro.fleet import (AsyncConfig, FleetConfig, FleetTopology,
                         ScheduleConfig, run_fleet, time_to_loss)
from repro.fleet import scheduler as SCHED


def tiny(rounds=6, **kw):
    return FleetConfig(
        topology=FleetTopology(num_cells=3, clients_per_cell=8),
        rounds=rounds, **kw)


@contextlib.contextmanager
def x64():
    """Run both engine modes in float64 so the equivalence tolerance tests
    the algorithm, not fp32 reduction-order noise."""
    with jax.enable_x64(True):
        yield


# ---------------------------------------------------------------------------
# staleness discount + buffered merge (core.aggregation)
# ---------------------------------------------------------------------------

def test_staleness_scale_monotone_and_normalized():
    tau = np.arange(0, 30)
    for kind in ("polynomial", "exponential"):
        for xp in (np, jnp):
            s = np.asarray(agg.staleness_scale(tau, kind=kind, alpha=0.5,
                                               xp=xp))
            assert s[0] == pytest.approx(1.0)
            assert np.all(np.diff(s) < 0.0)          # strictly decreasing
            assert np.all((s > 0.0) & (s <= 1.0))
    s_none = np.asarray(agg.staleness_scale(tau, kind="none", xp=np))
    np.testing.assert_allclose(s_none, 1.0)


def test_staleness_scale_alpha_orders_discounts():
    weak = np.asarray(agg.staleness_scale(10, kind="polynomial", alpha=0.1,
                                          xp=np))
    strong = np.asarray(agg.staleness_scale(10, kind="polynomial", alpha=2.0,
                                            xp=np))
    assert strong < weak < 1.0


def test_staleness_scale_rejects_unknown_kind():
    with pytest.raises(ValueError, match="unknown staleness"):
        agg.staleness_scale(1, kind="linear", xp=np)


def _grads(i=4, shape=(3, 5)):
    k = jax.random.PRNGKey(0)
    return {"w": jax.random.normal(k, (i,) + shape),
            "b": jax.random.normal(jax.random.PRNGKey(1), (i, shape[1]))}


def test_buffered_aggregate_numpy_jax_equivalence():
    """One staleness-weighted merge implementation, two namespaces."""
    g = _grads()
    g_np = jax.tree.map(np.asarray, g)
    k = np.asarray([30.0, 40.0, 50.0, 20.0])
    c = np.asarray([1.0, 0.0, 1.0, 1.0])
    tau = np.asarray([0, 1, 3, 7])
    kw = dict(kind="polynomial", alpha=0.5, max_staleness=5)
    out_np = agg.buffered_aggregate(g_np, k, c, tau, xp=np, **kw)
    out_jax = agg.buffered_aggregate(g, jnp.asarray(k), jnp.asarray(c),
                                     jnp.asarray(tau), xp=jnp, **kw)
    for a, b in zip(jax.tree.leaves(out_np), jax.tree.leaves(out_jax)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


def test_buffered_aggregate_zero_staleness_is_eq5():
    """tau = 0 with any schedule reduces to the paper's aggregate()."""
    g = _grads()
    k = jnp.asarray([30.0, 40.0, 50.0, 20.0])
    c = jnp.asarray([1.0, 0.0, 1.0, 1.0])
    want = agg.aggregate(g, k, c)
    for kind in ("none", "polynomial", "exponential"):
        got = agg.buffered_aggregate(g, k, c, jnp.zeros(4), kind=kind)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6)


def test_buffered_aggregate_fractional_weight_total_stays_normalized():
    """A heavily-discounted buffer whose weights sum below 1 must still
    return the weighted *mean* (regression: a max(denom, 1) zero-guard
    silently shrank the update)."""
    g = {"w": jnp.ones((1, 3))}
    out = agg.buffered_aggregate(g, jnp.asarray([1.0]), jnp.asarray([1.0]),
                                 jnp.asarray([20]), kind="polynomial",
                                 alpha=0.5, max_staleness=20)
    np.testing.assert_allclose(np.asarray(out["w"]), 1.0, rtol=1e-6)
    out_np = agg.buffered_aggregate(
        jax.tree.map(np.asarray, g), np.asarray([1.0]), np.asarray([1.0]),
        np.asarray([20]), kind="polynomial", alpha=0.5, max_staleness=20,
        xp=np)
    np.testing.assert_allclose(np.asarray(out_np["w"]), 1.0, rtol=1e-6)


def test_buffered_aggregate_drops_overstale_updates():
    g = _grads()
    k = jnp.asarray([30.0, 40.0, 50.0, 20.0])
    c = jnp.ones(4)
    tau = jnp.asarray([0, 0, 99, 99])           # two updates too old
    out = agg.buffered_aggregate(g, k, c, tau, kind="none", max_staleness=5)
    want = agg.aggregate(g, k, jnp.asarray([1.0, 1.0, 0.0, 0.0]))
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)
    # everything overstale -> server skips the update (zero gradient)
    all_old = agg.buffered_aggregate(g, k, c, jnp.full(4, 99),
                                     max_staleness=5)
    for leaf in jax.tree.leaves(all_old):
        np.testing.assert_allclose(np.asarray(leaf), 0.0)


# ---------------------------------------------------------------------------
# scheduler: arrival-time modelling
# ---------------------------------------------------------------------------

def test_arrival_times_clamps_infinite_latency():
    t = SCHED.arrival_times(jnp.asarray(10.0),
                            jnp.asarray([[0.5, jnp.inf, 2.0]]))
    out = np.asarray(t)
    assert np.all(np.isfinite(out))
    np.testing.assert_allclose(out[0, 0], 10.5)
    assert out[0, 1] == pytest.approx(10.0 + SCHED.MAX_CLIENT_LATENCY_S)
    # unschedulable clients re-register after the retry backoff instead of
    # absorbing into the far future (which would drain the pending pool)
    retry = SCHED.arrival_times(jnp.asarray(10.0),
                                jnp.asarray([[0.5, jnp.inf, 2.0]]),
                                retry_s=60.0)
    np.testing.assert_allclose(np.asarray(retry)[0], [10.5, 70.0, 12.0])


def test_select_arrivals_picks_earliest_k():
    ready = jnp.asarray([[3.0, 1.0], [2.0, 5.0]])
    sel, t_fill = SCHED.select_arrivals(ready, 2)
    assert sorted(np.asarray(sel).tolist()) == [1, 2]   # flat idx of 1.0, 2.0
    assert float(t_fill) == pytest.approx(2.0)
    # buffer = everyone: fill time is the straggler tail (the sync barrier)
    _, t_all = SCHED.select_arrivals(ready, 4)
    assert float(t_all) == pytest.approx(5.0)


def test_async_config_validation():
    assert AsyncConfig(buffer_size=0).cohort_buffer(24) == 24
    assert AsyncConfig(buffer_size=8).cohort_buffer(24) == 8
    assert AsyncConfig(buffer_size=999).cohort_buffer(24) == 24
    assert AsyncConfig(max_staleness=4).history_len == 5
    with pytest.raises(ValueError):
        AsyncConfig(buffer_size=-1)
    with pytest.raises(ValueError):
        AsyncConfig(max_staleness=-2)
    with pytest.raises(ValueError):
        AsyncConfig(retry_backoff_s=0.0)


# ---------------------------------------------------------------------------
# engine: sync equivalence
# ---------------------------------------------------------------------------

def test_async_buffer_equals_cohort_matches_sync():
    """K = cohort, no staleness discount: the event timeline degenerates to
    the round barrier and every trajectory statistic must coincide."""
    cfg = tiny(rounds=6, async_config=AsyncConfig(
        buffer_size=0, max_staleness=3, staleness_discount="none"))
    with x64():
        s = run_fleet(cfg)
        a = run_fleet(cfg, mode="async")
    np.testing.assert_allclose(a.losses, s.losses, rtol=1e-6, atol=1e-9)
    np.testing.assert_allclose(a.accuracy, s.accuracy, rtol=1e-6, atol=1e-9)
    np.testing.assert_allclose(a.latencies, s.latencies, rtol=1e-6)
    np.testing.assert_allclose(a.deadlines, s.deadlines, rtol=1e-6)
    np.testing.assert_allclose(a.mean_prune, s.mean_prune, rtol=1e-6,
                               atol=1e-9)
    np.testing.assert_allclose(a.mean_per, s.mean_per, rtol=1e-6, atol=1e-9)
    np.testing.assert_allclose(a.participants, s.participants)
    np.testing.assert_allclose(a.bandwidth_util, s.bandwidth_util, rtol=1e-6)
    np.testing.assert_allclose(a.wall_clock, np.cumsum(s.latencies),
                               rtol=1e-6)
    np.testing.assert_allclose(a.staleness, 0.0)     # lockstep: never stale
    assert a.bound_final == pytest.approx(s.bound_final, rel=1e-6)
    for pa, ps in zip(jax.tree.leaves(a.params), jax.tree.leaves(s.params)):
        np.testing.assert_allclose(pa, ps, rtol=1e-6, atol=1e-9)


def test_async_discount_changes_nothing_at_zero_staleness():
    """In lockstep every merge has tau = 0 and s(0) = 1 for every schedule,
    so the discount choice cannot matter when the buffer is the cohort."""
    with x64():
        runs = [run_fleet(tiny(rounds=4, async_config=AsyncConfig(
            buffer_size=0, staleness_discount=kind)), mode="async")
            for kind in ("none", "polynomial")]
    np.testing.assert_allclose(runs[0].losses, runs[1].losses, rtol=1e-6)


# ---------------------------------------------------------------------------
# engine: genuinely asynchronous behaviour
# ---------------------------------------------------------------------------

def test_async_buffered_runs_and_tracks():
    cfg = tiny(rounds=10, async_config=AsyncConfig(buffer_size=6,
                                                   max_staleness=8))
    res = run_fleet(cfg, mode="async")
    assert res.mode == "async"
    assert res.losses.shape == (10,) and res.staleness.shape == (10,)
    assert np.all(np.isfinite(res.losses))
    assert np.all(res.latencies >= 0)
    assert np.all(np.diff(res.wall_clock) >= 0)      # time moves forward
    assert np.all(res.participants <= 6)             # never more than buffer
    assert np.all(res.staleness >= 0)
    assert res.staleness.max() > 0                   # buffering ages updates
    # events are shorter than the sync barrier on the same fleet
    sync = run_fleet(tiny(rounds=10))
    assert res.latencies.mean() < sync.latencies.mean()


def test_async_deterministic():
    cfg = tiny(rounds=5, async_config=AsyncConfig(buffer_size=6))
    a = run_fleet(cfg, mode="async")
    b = run_fleet(cfg, mode="async")
    np.testing.assert_allclose(a.losses, b.losses)
    np.testing.assert_allclose(a.wall_clock, b.wall_clock)
    c = run_fleet(tiny(rounds=5, seed=1,
                       async_config=AsyncConfig(buffer_size=6)),
                  mode="async")
    assert not np.allclose(a.losses, c.losses)


def test_async_beats_sync_wall_clock_with_stragglers():
    """Regression: on a straggler-heavy cell (wide CPU speed and distance
    spread -> a long per-round latency tail) buffered aggregation reaches
    the target loss in less simulated wall-clock than the barrier, which
    must wait for the slowest scheduled uplink every round."""
    topo = FleetTopology(num_cells=2, clients_per_cell=16,
                         cpu_hz_range=(2e8, 8e9), max_dist_m=1500.0)
    target = 1.8
    sync = run_fleet(FleetConfig(topology=topo, rounds=12, seed=3))
    anc = run_fleet(FleetConfig(topology=topo, rounds=48, seed=3,
                                async_config=AsyncConfig(buffer_size=8,
                                                         max_staleness=12)),
                    mode="async")
    t_sync = time_to_loss(sync, target)
    t_async = time_to_loss(anc, target)
    assert np.isfinite(t_sync) and np.isfinite(t_async)
    assert t_async < t_sync
    # and not by luck of one extra event: the gap is structural
    assert t_async < 0.75 * t_sync


def test_run_alias_and_mode_validation():
    from repro.fleet import engine
    assert engine.run is engine.run_fleet
    with pytest.raises(ValueError, match="mode"):
        run_fleet(tiny(rounds=2), mode="buffered")


def test_async_control_chunk_bitwise_identical():
    """Chunking the per-event (C, I) in-flight-state rebuild is a pure
    memory-shape transform: an async run with ``control_chunk=3`` over 5
    cells (one full lax.map block + a ragged 2-cell tail) must reproduce
    the unchunked trajectory bit for bit."""
    def run(chunk):
        cfg = FleetConfig(
            topology=FleetTopology(num_cells=5, clients_per_cell=8),
            rounds=5, control_chunk=chunk,
            async_config=AsyncConfig(buffer_size=6, max_staleness=3))
        return run_fleet(cfg, mode="async")

    a, b = run(0), run(3)
    for field in ("losses", "accuracy", "latencies", "deadlines",
                  "mean_prune", "mean_per", "participants",
                  "bandwidth_util", "staleness", "wall_clock"):
        assert np.array_equal(getattr(a, field), getattr(b, field)), field
    assert jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda x, y: bool(jnp.array_equal(x, y)), a.params, b.params))
