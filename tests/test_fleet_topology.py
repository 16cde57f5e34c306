"""Interference-aware cell geometry substrate (ISSUE 5).

Pins the PR-5 contract:

* the default path is bit-compatible: ``FleetConfig()`` without a
  geometry equals an explicit ``OrthogonalCells()`` run exactly;
* the zero-interference limit: ``HexInterference`` with reuse factor high
  enough for zero co-channel neighbors reproduces the ``OrthogonalCells``
  trajectory to 1e-6 under x64 — sync and async, reference and fused;
* the damped interference fixed point is monotone from I = 0 and freezes
  within its iteration cap;
* interference raises PER, handover mitigates it, and the "exclude"
  handover policy shrinks participation;
* two-tier aggregation: ``cloud_period = 1`` equals the single-tier
  global rule to 1e-6 under x64, merges price the backhaul, and the mode
  composes with async and the fused kernels;
* Dirichlet non-IID batches skew per-client label histograms while the
  default (None) stays bit-identical;
* ``run_fleet_reference`` covers partial participation, deadline caps and
  interference (cross-path to 1e-5 under x64);
* the ``SolverConfig.grow_iters`` deprecation shim loads old configs.
"""

import contextlib
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.fleet import (AsyncConfig, FleetConfig, FleetTopology,
                         HexInterference, LinearRegressionTask,
                         OrthogonalCells, ScheduleConfig, SolverConfig,
                         SyntheticMLPTask, make_geometry, run_fleet)
from repro.fleet import engine as FE
from repro.fleet import solver as FS
from repro.fleet import topology as FT


@contextlib.contextmanager
def x64():
    with jax.enable_x64(True):
        yield


def small(cells=4, clients=6, **kw):
    return FleetConfig(
        topology=FleetTopology(num_cells=cells, clients_per_cell=clients),
        **kw)


# ---------------------------------------------------------------------------
# Hex layout + reuse coloring
# ---------------------------------------------------------------------------

def test_hex_positions_spacing_and_count():
    pos = FT.hex_bs_positions(19, 1000.0)
    assert pos.shape == (19, 2)
    d = np.linalg.norm(pos[:, None, :] - pos[None, :, :], axis=-1)
    d = d[~np.eye(19, dtype=bool)]
    np.testing.assert_allclose(d.min(), 1000.0, rtol=1e-9)
    assert len(np.unique(np.round(pos, 6), axis=0)) == 19


@pytest.mark.parametrize("reuse", [3, 4, 7])
def test_hex_reuse_coloring_is_proper(reuse):
    """No two adjacent cells (distance == spacing) share a reuse group."""
    pos = FT.hex_bs_positions(19, 1.0)
    groups = FT.hex_reuse_groups(19, reuse)
    d = np.linalg.norm(pos[:, None, :] - pos[None, :, :], axis=-1)
    adjacent = np.abs(d - 1.0) < 1e-9
    same = groups[:, None] == groups[None, :]
    assert not np.any(adjacent & same)


def test_hex_reuse_at_least_cells_is_orthogonal():
    groups = FT.hex_reuse_groups(7, 99)
    assert len(np.unique(groups)) == 7
    geo = HexInterference(reuse=99)
    topo = FleetTopology(num_cells=7, clients_per_cell=3)
    pop = geo.make_population(jax.random.PRNGKey(0), topo, 0.2)
    assert pop.geometry is None  # zero co-channel: no spatial state needed


def test_make_geometry_registry():
    assert isinstance(make_geometry("orthogonal"), OrthogonalCells)
    assert isinstance(make_geometry("hex", reuse=1), HexInterference)
    with pytest.raises(ValueError, match="unknown geometry"):
        make_geometry("torus")


def test_interference_psd_units_and_zero_allocation():
    """Zero allocated bandwidth -> zero interference; doubling every
    allocation doubles the PSD (the coupling is linear in B_j)."""
    geo = HexInterference(reuse=1)
    topo = FleetTopology(num_cells=4, clients_per_cell=5)
    pop = geo.make_population(jax.random.PRNGKey(1), topo, 0.2)
    chan = geo.round_channel(jax.random.PRNGKey(2), pop, topo)
    graph = chan.interference
    assert graph is not None
    bw = jnp.full(topo.shape, 1e5)
    i1 = FT.interference_psd(bw, pop.tx_power, graph, 15e6)
    i2 = FT.interference_psd(2.0 * bw, pop.tx_power, graph, 15e6)
    i0 = FT.interference_psd(jnp.zeros_like(bw), pop.tx_power, graph, 15e6)
    assert np.all(np.asarray(i0) == 0.0)
    assert np.all(np.asarray(i1) > 0.0)
    np.testing.assert_allclose(np.asarray(i2), 2.0 * np.asarray(i1),
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# Default-path bit compatibility + the orthogonal limit
# ---------------------------------------------------------------------------

def _assert_traj_equal(a, b, **tol):
    np.testing.assert_allclose(a.losses, b.losses, **tol)
    np.testing.assert_allclose(a.accuracy, b.accuracy, **tol)
    np.testing.assert_allclose(a.latencies, b.latencies, **tol)
    np.testing.assert_allclose(a.mean_per, b.mean_per, **tol)
    for la, lb in zip(jax.tree.leaves(a.params), jax.tree.leaves(b.params)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb), **tol)


def test_default_config_is_explicit_orthogonal_bitwise():
    base = run_fleet(small(rounds=3))
    geo = run_fleet(small(rounds=3, geometry=OrthogonalCells()))
    np.testing.assert_array_equal(base.losses, geo.losses)
    np.testing.assert_array_equal(base.latencies, geo.latencies)
    for a, b in zip(jax.tree.leaves(base.params), jax.tree.leaves(geo.params)):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("kernel", ["reference", "fused"])
def test_hex_zero_interference_limit_matches_orthogonal_sync(kernel):
    """Acceptance: reuse >= num_cells => the HexInterference trajectory
    equals OrthogonalCells to 1e-6 (x64), reference and fused kernels."""
    with x64():
        base = run_fleet(small(rounds=3, kernel=kernel))
        hexo = run_fleet(small(rounds=3, kernel=kernel,
                               geometry=HexInterference(reuse=64)))
    _assert_traj_equal(base, hexo, rtol=1e-6, atol=1e-12)


def test_hex_zero_interference_limit_matches_orthogonal_async():
    acfg = AsyncConfig(buffer_size=6, max_staleness=4)
    with x64():
        base = run_fleet(small(rounds=4, async_config=acfg), mode="async")
        hexo = run_fleet(small(rounds=4, async_config=acfg,
                               geometry=HexInterference(reuse=64)),
                         mode="async")
    _assert_traj_equal(base, hexo, rtol=1e-6, atol=1e-12)


# ---------------------------------------------------------------------------
# Interference physics + the fixed point
# ---------------------------------------------------------------------------

def test_interference_raises_per_and_handover_mitigates():
    base = run_fleet(small(rounds=3))
    hexi = run_fleet(small(rounds=3, geometry=HexInterference(reuse=1)))
    hex_noho = run_fleet(small(rounds=3,
                               geometry=HexInterference(reuse=1,
                                                        handover=False)))
    assert np.mean(hexi.mean_per) > np.mean(base.mean_per)
    # strongest-gain handover strictly improves the serving link
    assert np.mean(hexi.mean_per) < np.mean(hex_noho.mean_per)


def test_handover_exclude_policy_shrinks_participation():
    geo = HexInterference(reuse=1, mobility_m=30.0)
    base = run_fleet(small(rounds=3, geometry=geo))
    excl = run_fleet(small(rounds=3, geometry=geo,
                           schedule=ScheduleConfig(
                               handover_policy="exclude")))
    assert np.sum(excl.participants) < np.sum(base.participants)
    with pytest.raises(ValueError, match="handover_policy"):
        ScheduleConfig(handover_policy="drop")


def _solve_kw(cfg, pop):
    w = cfg.wireless
    return dict(bandwidth_hz=w.bandwidth_hz,
                noise_psd=w.noise_psd_w_per_hz,
                waterfall_m0=w.waterfall_m0, model_bits=w.model_bits,
                cycles_per_sample=w.cycles_per_sample, weight=cfg.weight)


def test_interference_fixed_point_monotone_and_frozen():
    """From I = 0 the damped iterate climbs monotonically (more
    interference -> more bandwidth demanded -> more interference) and the
    while_loop freezes before its cap at the default tolerance."""
    cfg = small()
    geo = HexInterference(reuse=1)
    topo = cfg.topology
    with x64():
        pop = geo.make_population(jax.random.PRNGKey(0), topo,
                                  cfg.wireless.tx_power_ue_w)
        chan = geo.round_channel(jax.random.PRNGKey(3), pop, topo)
        m = jnp.full((topo.num_cells,), 1e-3)
        kw = _solve_kw(cfg, pop)

        iterates = []
        for k in range(1, 5):
            sol = FS.solve_fleet(
                chan.h_up, pop.num_samples, pop.cpu_hz, pop.tx_power,
                pop.max_prune, m, interference=chan.interference,
                solver=SolverConfig(fp_iters=k, fp_rtol=0.0), **kw)
            iterates.append(np.asarray(sol.interference_psd))
            assert int(sol.fp_iterations) == k
        for prev, nxt in zip(iterates, iterates[1:]):
            assert np.all(nxt >= prev * (1.0 - 1e-9))
        assert np.any(iterates[-1] > 0.0)

        # default tolerance: converges strictly inside the cap
        sol = FS.solve_fleet(
            chan.h_up, pop.num_samples, pop.cpu_hz, pop.tx_power,
            pop.max_prune, m, interference=chan.interference,
            solver=SolverConfig(fp_iters=16, fp_rtol=1e-3), **kw)
        assert int(sol.fp_iterations) < 16
        # ...at a self-consistent point: F(I*) stays within tolerance of I*
        i_star = sol.interference_psd
        i_raw = FT.interference_psd(sol.bandwidth, pop.tx_power,
                                    chan.interference,
                                    cfg.wireless.bandwidth_hz)
        scale = cfg.wireless.noise_psd_w_per_hz + float(jnp.max(i_star))
        assert float(jnp.max(jnp.abs(i_raw - i_star))) <= 2e-3 * scale


def test_interference_appears_in_solution_and_uncoupled_solve_is_free():
    cfg = small(rounds=2, geometry=HexInterference(reuse=1))
    res = run_fleet(cfg)
    assert np.all(np.isfinite(res.losses))
    # the orthogonal solve reports no interference telemetry
    geo = OrthogonalCells()
    pop = geo.make_population(jax.random.PRNGKey(0), cfg.topology, 0.2)
    chan = geo.round_channel(jax.random.PRNGKey(1), pop, cfg.topology)
    assert chan.interference is None and chan.served_home is None


# ---------------------------------------------------------------------------
# Two-tier hierarchical aggregation
# ---------------------------------------------------------------------------

def test_two_tier_period_one_matches_single_tier():
    """cloud_period = 1 merges every round with the realized Eq.-(5)
    weight mass per cell — algebraically the single-tier global update."""
    with x64():
        base = run_fleet(small(rounds=4))
        tt = run_fleet(small(rounds=4, cloud_period=1))
    np.testing.assert_allclose(tt.losses, base.losses, rtol=1e-6, atol=1e-9)
    np.testing.assert_allclose(tt.accuracy, base.accuracy, rtol=1e-6,
                               atol=1e-9)
    for a, b in zip(jax.tree.leaves(tt.params), jax.tree.leaves(base.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                                   atol=1e-9)


def test_two_tier_merge_rounds_price_the_backhaul():
    base = run_fleet(small(rounds=4))
    tt = run_fleet(small(rounds=4, cloud_period=2))
    backhaul = base.cfg.wireless.backhaul_s if hasattr(base, "cfg") else None
    w = FleetConfig().wireless
    lat = tt.latencies - base.latencies
    # merge rounds (1 and 3) carry the backhaul surcharge, edge rounds none
    np.testing.assert_allclose(lat[1::2], w.backhaul_s, rtol=1e-5)
    np.testing.assert_allclose(lat[0::2], 0.0, atol=1e-7)


def test_two_tier_fused_matches_reference_block():
    with x64():
        ref = run_fleet(small(rounds=3, cloud_period=2, kernel="reference",
                              mask_kind="block"))
        fused = run_fleet(small(rounds=3, cloud_period=2, kernel="fused"))
    np.testing.assert_allclose(fused.losses, ref.losses, rtol=1e-5,
                               atol=1e-8)
    for a, b in zip(jax.tree.leaves(fused.params),
                    jax.tree.leaves(ref.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-8)


def test_two_tier_async_runs_and_tracks():
    res = run_fleet(small(rounds=5, cloud_period=2,
                          async_config=AsyncConfig(buffer_size=6,
                                                   max_staleness=4)),
                    mode="async")
    assert np.all(np.isfinite(res.losses))
    assert res.mode == "async"
    # composes with interference
    res2 = run_fleet(small(rounds=4, cloud_period=2,
                           geometry=HexInterference(reuse=1),
                           async_config=AsyncConfig(buffer_size=6)),
                     mode="async")
    assert np.all(np.isfinite(res2.losses))


def test_two_tier_validation():
    with pytest.raises(ValueError, match="cloud_period"):
        FE.build_simulation(small(rounds=2, cloud_period=-1))


# ---------------------------------------------------------------------------
# Non-IID Dirichlet batches
# ---------------------------------------------------------------------------

def test_dirichlet_skews_mlp_labels_and_default_is_bit_identical():
    task_iid = SyntheticMLPTask(local_batch=64)
    task_skew = SyntheticMLPTask(local_batch=64, dirichlet_alpha=0.05)
    kt, ke, kd = jax.random.split(jax.random.PRNGKey(0), 3)
    state = task_iid.build(kt, ke)

    def label_counts(task):
        y = jax.vmap(lambda i: task.client_batch(state, kd, i)["y"])(
            jnp.arange(16))
        return np.stack([np.bincount(np.asarray(yc), minlength=4)
                         for yc in y])

    iid = label_counts(task_iid)
    skew = label_counts(task_skew)
    # per-client max-class share: Dirichlet(0.05) concentrates hard
    assert skew.max(axis=1).mean() > iid.max(axis=1).mean() + 10
    # None alpha stays the original draw (bit-compatible default)
    base = run_fleet(small(rounds=2))
    viad = run_fleet(small(rounds=2, dirichlet_alpha=None))
    np.testing.assert_array_equal(base.losses, viad.losses)


def test_dirichlet_config_field_reaches_task_and_conflicts_raise():
    cfg = small(rounds=2, dirichlet_alpha=0.2)
    task = FE.resolve_task(cfg)
    assert task.dirichlet_alpha == 0.2
    res = run_fleet(cfg)
    assert np.all(np.isfinite(res.losses))
    with pytest.raises(ValueError, match="dirichlet_alpha"):
        FE.resolve_task(small(dirichlet_alpha=0.2,
                              task=LinearRegressionTask()))


def test_dirichlet_transformer_token_pool_skew():
    from repro.fleet import TransformerTask

    task = TransformerTask(dirichlet_alpha=0.05, local_batch=4)
    kt, ke, kd = jax.random.split(jax.random.PRNGKey(0), 3)
    state = task.build(kt, ke)
    b0 = task.client_batch(state, kd, jnp.asarray(0))
    b0_again = task.client_batch(state, kd, jnp.asarray(0))
    b1 = task.client_batch(state, kd, jnp.asarray(1))
    # fixed local datasets: same draw every round; clients differ
    np.testing.assert_array_equal(np.asarray(b0["tokens"]),
                                  np.asarray(b0_again["tokens"]))
    assert not np.array_equal(np.asarray(b0["tokens"]),
                              np.asarray(b1["tokens"]))
    assert task.cache_batches and not TransformerTask().cache_batches


# ---------------------------------------------------------------------------
# Host reference path: mask/cap port + interference fixed point
# ---------------------------------------------------------------------------

def test_run_fleet_reference_partial_participation_and_deadline():
    """Satellite: the host solver's mask/cap port — 5-UE-path and
    fleet-path trajectories agree to 1e-5 under x64 with partial
    participation and a binding round deadline."""
    from repro.federated import system as SYS

    cfg = small(cells=3, clients=5, rounds=4, task=LinearRegressionTask(),
                lr=0.05,
                schedule=ScheduleConfig(participation="uniform",
                                        participants_per_cell=3,
                                        round_deadline_s=2.0))
    with x64():
        fleet = run_fleet(cfg)
        host = SYS.run_fleet_reference(cfg)
    np.testing.assert_allclose(host.losses, fleet.losses, rtol=1e-5,
                               atol=1e-8)
    np.testing.assert_allclose(host.mean_prune, fleet.mean_prune, rtol=1e-5,
                               atol=1e-8)
    np.testing.assert_allclose(host.latencies, fleet.latencies, rtol=1e-5)


def test_run_fleet_reference_interference_fixed_point():
    """Acceptance: run_fleet_reference reproduces the fleet path with
    interference enabled to 1e-5 under x64 (fp_rtol = 0 pins both paths
    to the same iteration count)."""
    from repro.federated import system as SYS

    cfg = small(cells=3, clients=5, rounds=3, task=LinearRegressionTask(),
                lr=0.05, geometry=HexInterference(reuse=1),
                solver=SolverConfig(fp_iters=4, fp_rtol=0.0))
    with x64():
        fleet = run_fleet(cfg)
        host = SYS.run_fleet_reference(cfg)
    np.testing.assert_allclose(host.losses, fleet.losses, rtol=1e-5,
                               atol=1e-8)
    np.testing.assert_allclose(host.mean_per, fleet.mean_per, rtol=1e-5,
                               atol=1e-9)
    np.testing.assert_allclose(host.latencies, fleet.latencies, rtol=1e-5)


def test_run_fleet_reference_rejects_two_tier():
    from repro.federated import system as SYS

    with pytest.raises(NotImplementedError, match="two-tier"):
        SYS.run_fleet_reference(small(rounds=2, cloud_period=2,
                                      task=LinearRegressionTask()))


# ---------------------------------------------------------------------------
# SolverConfig.grow_iters deprecation shim
# ---------------------------------------------------------------------------

def test_grow_iters_shim_warns_and_loads():
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        cfg = SolverConfig(grow_iters=48)
    assert any(issubclass(w.category, DeprecationWarning) for w in rec)
    assert cfg == SolverConfig()  # the knob is gone from the config state
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        SolverConfig()  # the modern spelling stays silent
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        assert SolverConfig().grow_iters == 0
    assert any(issubclass(w.category, DeprecationWarning) for w in rec)
    assert "grow_iters" not in [f.name for f in
                                __import__("dataclasses").fields(
                                    SolverConfig)]


# ---------------------------------------------------------------------------
# Per-link fast fading on the interference cross paths (ISSUE 7)
# ---------------------------------------------------------------------------

def _cross_graphs(seed, topo, pop, geo):
    key = jax.random.PRNGKey(seed)
    graph = geo.round_channel(key, pop, topo).interference
    ray = jax.random.exponential(
        jax.random.fold_in(key, FT._SALT_CROSS), graph.cross_gain.shape)
    return graph, ray


def test_cross_fades_are_per_link_fast_and_seed_salted():
    """The realized cross gain is static geometry x an i.i.d. per-link
    Exp(1) fade drawn from the _SALT_CROSS fold of the round key: it
    changes every round, varies across the neighbor axis within a client
    (per-link, not a per-cell scalar), and the static factor it divides
    back out to is round-invariant."""
    geo = HexInterference(reuse=1, mobility_m=0.0)
    topo = FleetTopology(num_cells=4, clients_per_cell=6)
    pop = geo.make_population(jax.random.PRNGKey(0), topo, 0.2)

    g1, ray1 = _cross_graphs(1, topo, pop, geo)
    g2, ray2 = _cross_graphs(2, topo, pop, geo)
    m = np.asarray(g1.nbr_mask, bool)         # (C, K) valid-neighbor mask
    assert m.sum() >= 8                        # reuse=1: dense coupling

    # fast fading: realized cross gains move between rounds
    a1, a2 = np.asarray(g1.cross_gain), np.asarray(g2.cross_gain)
    assert not np.allclose(a1[m], a2[m], rtol=1e-3, atol=0.0)
    # seeded: the same round key reproduces the draw bitwise
    g1b, _ = _cross_graphs(1, topo, pop, geo)
    np.testing.assert_array_equal(a1, np.asarray(g1b.cross_gain))

    # per-link: the round-to-round fade ratio differs across the neighbor
    # axis for the same client (a per-cell or per-client scalar fade
    # would scale all of a client's links together)
    ratio = a1 / a2                            # (C, K, I)
    c = np.flatnonzero(m.sum(-1) >= 2)[0]      # a cell with >= 2 neighbors
    k0, k1 = np.flatnonzero(m[c])[:2]
    assert not np.allclose(ratio[c, k0], ratio[c, k1], rtol=1e-3, atol=0.0)

    # static factor: dividing the salted Exp(1) fade back out recovers the
    # same geometry gains from independent rounds (mobility off)
    s1 = a1[m] / np.asarray(ray1)[m]
    s2 = a2[m] / np.asarray(ray2)[m]
    np.testing.assert_allclose(s1, s2, rtol=1e-5)  # f32 mul/div round-trip


def test_cross_fades_unit_mean():
    """Mean fade 1: the fading-averaged calibration of the static gains
    survives the per-link draw (sample mean over rounds x links ~ 1)."""
    geo = HexInterference(reuse=1, mobility_m=0.0)
    topo = FleetTopology(num_cells=4, clients_per_cell=6)
    pop = geo.make_population(jax.random.PRNGKey(0), topo, 0.2)
    fades = []
    for s in range(40):
        graph, ray = _cross_graphs(s, topo, pop, geo)
        m = np.asarray(graph.nbr_mask, bool)
        fades.append(np.asarray(ray)[m].ravel())
    fades = np.concatenate(fades)
    assert fades.min() >= 0.0
    assert abs(fades.mean() - 1.0) < 0.06      # Exp(1): se ~ 1/sqrt(2880)
    assert abs(fades.std() - 1.0) < 0.10


def test_cross_fades_leave_serving_links_untouched():
    """The salted cross draw must not consume serving-link randomness:
    h_up / h_down / served_home match the pre-fade channel bit-for-bit
    (they are shared draws; only graph.cross_gain carries the new fade)."""
    topo = FleetTopology(num_cells=4, clients_per_cell=6)
    hi = HexInterference(reuse=1, mobility_m=0.0)
    pop = hi.make_population(jax.random.PRNGKey(0), topo, 0.2)
    ch = hi.round_channel(jax.random.PRNGKey(5), pop, topo)
    ch_again = hi.round_channel(jax.random.PRNGKey(5), pop, topo)
    np.testing.assert_array_equal(np.asarray(ch.h_up),
                                  np.asarray(ch_again.h_up))
    # zero-co-channel limit: no graph, hence no cross draw at all — the
    # orthogonal bit-exact equivalence (pinned above) is unaffected
    far = HexInterference(reuse=topo.num_cells)
    pop_far = far.make_population(jax.random.PRNGKey(0), topo, 0.2)
    assert far.round_channel(jax.random.PRNGKey(5), pop_far,
                             topo).interference is None
