"""Fleet engine + topology + scheduler behaviour (small shapes, CPU)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.fleet import (FleetConfig, FleetTopology, ScheduleConfig,
                         run_fleet)
from repro.fleet import scheduler as SCHED
from repro.fleet import topology as TOPO


def tiny(rounds=6, **kw):
    return FleetConfig(
        topology=FleetTopology(num_cells=3, clients_per_cell=8),
        rounds=rounds, **kw)


# ---------------------------------------------------------------------------
# topology
# ---------------------------------------------------------------------------

def test_population_shapes_and_ranges():
    topo = FleetTopology(num_cells=4, clients_per_cell=16)
    pop = TOPO.make_population(jax.random.PRNGKey(0), topo, 0.2)
    assert pop.geometry is None  # orthogonal default: no spatial state
    for leaf in jax.tree.leaves(pop):
        assert leaf.shape == (4, 16)
    assert np.all(np.asarray(pop.dist_m) >= topo.min_dist_m)
    assert np.all(np.asarray(pop.dist_m) <= topo.max_dist_m)
    k = np.asarray(pop.num_samples)
    assert np.all((k >= topo.samples_range[0]) & (k <= topo.samples_range[1]))
    assert np.all(np.asarray(pop.pathloss) > 0)
    assert np.all(np.asarray(pop.pathloss) < 1e-6)   # urban model, 50..500m


def test_pathloss_monotone_in_distance():
    d = jnp.asarray([[100.0, 200.0, 400.0]])
    pl = np.asarray(TOPO.path_loss_linear(d))[0]
    assert pl[0] > pl[1] > pl[2]


def test_fading_changes_per_round_but_is_seeded():
    topo = FleetTopology(num_cells=2, clients_per_cell=4)
    pop = TOPO.make_population(jax.random.PRNGKey(0), topo, 0.2)
    h1u, h1d = TOPO.sample_fading(jax.random.PRNGKey(1), pop.pathloss)
    h2u, _ = TOPO.sample_fading(jax.random.PRNGKey(2), pop.pathloss)
    h1u_again, _ = TOPO.sample_fading(jax.random.PRNGKey(1), pop.pathloss)
    np.testing.assert_allclose(np.asarray(h1u), np.asarray(h1u_again))
    # gains are ~1e-10: atol must be 0 or allclose trivially passes
    assert not np.allclose(np.asarray(h1u), np.asarray(h2u), rtol=1e-3,
                           atol=0.0)
    assert np.all(np.asarray(h1u) > 0) and np.all(np.asarray(h1d) > 0)


# ---------------------------------------------------------------------------
# scheduler
# ---------------------------------------------------------------------------

def test_participation_counts():
    k = jnp.ones((5, 32))
    for mode in ("uniform", "weighted"):
        sched = ScheduleConfig(participation=mode, participants_per_cell=8)
        mask = SCHED.participation_mask(jax.random.PRNGKey(0), sched, k)
        assert mask.shape == (5, 32)
        np.testing.assert_allclose(np.asarray(mask).sum(-1), 8.0)
    full = SCHED.participation_mask(
        jax.random.PRNGKey(0), ScheduleConfig(), k)
    np.testing.assert_allclose(np.asarray(full), 1.0)


def test_weighted_participation_prefers_large_k():
    c, i = 1, 64
    k = jnp.concatenate([jnp.full((c, i // 2), 1.0),
                         jnp.full((c, i // 2), 100.0)], axis=-1)
    sched = ScheduleConfig(participation="weighted", participants_per_cell=16)
    picks = np.zeros(i)
    for s in range(50):
        m = SCHED.participation_mask(jax.random.PRNGKey(s), sched, k)
        picks += np.asarray(m)[0]
    # the K=100 half should dominate the draw overwhelmingly
    assert picks[i // 2:].sum() > 5 * picks[:i // 2].sum()


def test_straggler_and_deadline_masks():
    sched = ScheduleConfig(straggler_prob=0.5, round_deadline_s=1.0)
    m = SCHED.straggler_mask(jax.random.PRNGKey(0), sched, (4, 256))
    frac = float(np.asarray(m).mean())
    assert 0.35 < frac < 0.65
    lat = jnp.asarray([0.5, 1.0, 1.5, jnp.inf])
    np.testing.assert_allclose(
        np.asarray(SCHED.on_time_mask(lat, sched)), [1, 1, 0, 0])
    # no deadline: only non-finite latencies miss
    np.testing.assert_allclose(
        np.asarray(SCHED.on_time_mask(lat, ScheduleConfig())), [1, 1, 1, 0])


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------

def test_engine_learns_and_tracks():
    res = run_fleet(tiny(rounds=8))
    r = 8
    assert res.losses.shape == (r,) and res.accuracy.shape == (r,)
    assert res.deadlines.shape == (r, 3) and res.bandwidth_util.shape == (r, 3)
    assert np.all(np.isfinite(res.losses))
    assert np.all(np.isfinite(res.latencies)) and np.all(res.latencies > 0)
    assert np.all((res.mean_prune >= 0) & (res.mean_prune <= 0.7 + 1e-6))
    assert np.all((res.mean_per >= 0) & (res.mean_per <= 1))
    assert np.all(res.bandwidth_util <= 1.0 + 1e-6)
    assert res.losses[-1] < res.losses[0]          # it actually learns
    assert np.isfinite(res.bound_final) and res.bound_final > 0


def test_engine_deterministic():
    a = run_fleet(tiny(rounds=4))
    b = run_fleet(tiny(rounds=4))
    np.testing.assert_allclose(a.losses, b.losses)
    np.testing.assert_allclose(a.latencies, b.latencies)
    c = run_fleet(tiny(rounds=4, seed=1))
    assert not np.allclose(a.losses, c.losses)


def test_engine_cell_chunking_matches_unchunked():
    """Gradient accumulation in cell chunks is algebra, not approximation."""
    a = run_fleet(tiny(rounds=3))
    b = run_fleet(tiny(rounds=3, cell_chunk=1))
    np.testing.assert_allclose(a.losses, b.losses, rtol=2e-5, atol=1e-6)


def test_engine_ragged_chunk_matches_unchunked():
    """Regression (ISSUE 3): a chunk size that does not divide the cell
    count must give identical results — the remainder now runs as one
    exact-sized call instead of zero-weight padded rows that still paid
    for batch generation and a full backward pass."""
    import jax
    with jax.enable_x64(True):
        a = run_fleet(tiny(rounds=3))                 # 3 cells, unchunked
        b = run_fleet(tiny(rounds=3, cell_chunk=2))   # 1 full chunk + 1 rem
    np.testing.assert_allclose(a.losses, b.losses, rtol=1e-6, atol=1e-9)
    np.testing.assert_allclose(a.accuracy, b.accuracy, rtol=1e-6, atol=1e-9)
    for pa, pb in zip(jax.tree.leaves(a.params), jax.tree.leaves(b.params)):
        np.testing.assert_allclose(pa, pb, rtol=1e-6, atol=1e-9)


def test_engine_partial_participation_and_deadline():
    sched = ScheduleConfig(participation="uniform", participants_per_cell=4,
                           straggler_prob=0.2, round_deadline_s=0.8)
    res = run_fleet(tiny(rounds=5, schedule=sched))
    assert np.all(res.latencies <= 0.8 + 1e-5)
    assert res.participants.sum() > 0              # someone makes it
    assert np.all(res.participants <= 3 * 4)       # never more than scheduled
    # a binding deadline must not oversubscribe the cell bandwidth budget
    assert np.all(res.bandwidth_util <= 1.0 + 1e-6)
    # deadline pressure should push pruning above the unconstrained run
    free = run_fleet(tiny(rounds=5))
    assert res.mean_prune.mean() >= free.mean_prune.mean() - 1e-6


def test_engine_with_host_mesh():
    """Sharded-inputs path: cells on the mesh "data" axis (1 device here)."""
    from repro.launch import mesh as MESH
    mesh = MESH.make_host_mesh(model=1)
    cfg = FleetConfig(topology=FleetTopology(num_cells=2, clients_per_cell=8),
                      rounds=3)
    res = run_fleet(cfg, mesh=mesh)
    assert np.all(np.isfinite(res.losses))


def test_run_any_dispatch():
    """system.run_any: small -> exact host path, large -> fleet engine."""
    from repro.federated import system as SYS
    small = SYS.FLConfig(rounds=2, eval_every=1)
    out = SYS.run_any(small, fleet_threshold=64)
    assert isinstance(out, SYS.FLResult)
    big = SYS.FLConfig(num_clients=128, samples=tuple([30, 40] * 64),
                       rounds=2)
    fleet_out = SYS.run_any(big, fleet_threshold=64)
    assert hasattr(fleet_out, "bound_final")
    assert fleet_out.losses.shape == (2,)
