"""The batched jax trade-off solver must agree with the host reference.

Acceptance bar (ISSUE 1): ``fleet/solver.py`` matches ``core/tradeoff.py``
closed-form outputs within 1e-6 on randomized problems.  Comparisons run
under x64 so the only differences are libm-vs-XLA ulps, not dtype loss.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import make_problem
from repro.core import closed_form as CF
from repro.core import tradeoff as T
from repro.core.convergence import ConvergenceBound
from repro.fleet import solver as FS


@pytest.fixture(autouse=True)
def _x64():
    with jax.enable_x64(True):
        yield


def _solve_jax(prob, weight, max_iters=16):
    return FS.solve_cell(
        jnp.asarray(prob.h_up), jnp.asarray(prob.num_samples),
        jnp.asarray(prob.cpu_hz), jnp.asarray(prob.tx_power),
        jnp.asarray(prob.max_prune), jnp.asarray(prob.bound.m),
        bandwidth_hz=prob.cfg.bandwidth_hz,
        noise_psd=prob.cfg.noise_psd_w_per_hz,
        waterfall_m0=prob.cfg.waterfall_m0,
        model_bits=prob.cfg.model_bits,
        cycles_per_sample=prob.cfg.cycles_per_sample,
        weight=weight, solver=FS.SolverConfig(max_iters=max_iters))


@pytest.mark.parametrize("seed", range(8))
@pytest.mark.parametrize("lam", [1e-5, 4e-4, 1e-2])
def test_solver_matches_host_reference(seed, lam):
    prob = make_problem(seed=seed, weight=lam)
    ref = T.solve_alternating(prob, max_iters=16)
    sol = _solve_jax(prob, lam)
    np.testing.assert_allclose(np.asarray(sol.prune), ref.prune,
                               rtol=1e-6, atol=1e-12)
    np.testing.assert_allclose(np.asarray(sol.bandwidth), ref.bandwidth,
                               rtol=1e-6)
    np.testing.assert_allclose(float(sol.deadline), ref.deadline, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(sol.per), ref.per, rtol=1e-5,
                               atol=1e-12)
    assert bool(sol.feasible) == ref.feasible


@pytest.mark.parametrize("seed", range(6))
def test_pruning_vertex_matches_solve_pruning(seed):
    prob = make_problem(seed=seed)
    bw = np.full(prob.num_clients, prob.cfg.bandwidth_hz / prob.num_clients)
    t_ref, rho_ref = T.solve_pruning(prob, bw)
    t_np = prob.no_prune_latency(bw)
    t_jax, rho_jax = CF.pruning_vertex(
        jnp.asarray(t_np), jnp.asarray(prob.num_samples), prob.weight,
        prob.bound.m, jnp.asarray(prob.max_prune), xp=jnp)
    np.testing.assert_allclose(float(t_jax), t_ref, rtol=1e-9)
    np.testing.assert_allclose(np.asarray(rho_jax), rho_ref, rtol=1e-9,
                               atol=1e-15)


@pytest.mark.parametrize("seed", range(6))
def test_bandwidth_bisection_matches(seed):
    prob = make_problem(seed=seed)
    rho = np.full(prob.num_clients, 0.3)
    deadline = float(np.max(prob.no_prune_latency(
        np.full(prob.num_clients, prob.cfg.bandwidth_hz / prob.num_clients)
    ))) * 0.8
    ref = T.solve_bandwidth(prob, rho, deadline)
    out = CF.bandwidth_for_deadline(
        jnp.asarray(rho), jnp.asarray(deadline),
        jnp.asarray(prob.num_samples), jnp.asarray(prob.cpu_hz),
        prob.cfg.cycles_per_sample, prob.cfg.model_bits,
        jnp.asarray(prob.tx_power), jnp.asarray(prob.h_up),
        prob.cfg.noise_psd_w_per_hz, xp=jnp)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-6)


def test_masked_solver_matches_subproblem():
    """Solving I clients with a mask == solving the masked subset alone."""
    prob = make_problem(num_clients=8, seed=3)
    keep = np.array([1, 1, 0, 1, 0, 1, 1, 0], np.float64)
    idx = np.flatnonzero(keep)

    sub = T.TradeoffProblem(
        cfg=prob.cfg,
        bound=ConvergenceBound(prob.bound.params, prob.num_samples[idx]),
        h_up=prob.h_up[idx], h_down=prob.h_down[idx],
        tx_power=prob.tx_power[idx], cpu_hz=prob.cpu_hz[idx],
        num_samples=prob.num_samples[idx], max_prune=prob.max_prune[idx],
        weight=prob.weight, num_rounds=prob.num_rounds)
    ref = T.solve_alternating(sub, max_iters=16)

    sol = FS.solve_cell(
        jnp.asarray(prob.h_up), jnp.asarray(prob.num_samples),
        jnp.asarray(prob.cpu_hz), jnp.asarray(prob.tx_power),
        jnp.asarray(prob.max_prune), jnp.asarray(sub.bound.m),
        mask=jnp.asarray(keep),
        bandwidth_hz=prob.cfg.bandwidth_hz,
        noise_psd=prob.cfg.noise_psd_w_per_hz,
        waterfall_m0=prob.cfg.waterfall_m0,
        model_bits=prob.cfg.model_bits,
        cycles_per_sample=prob.cfg.cycles_per_sample,
        weight=prob.weight, solver=FS.SolverConfig(max_iters=16))

    drop = np.flatnonzero(keep == 0)
    np.testing.assert_allclose(np.asarray(sol.prune)[drop], 0.0)
    np.testing.assert_allclose(np.asarray(sol.bandwidth)[drop], 0.0)
    np.testing.assert_allclose(np.asarray(sol.prune)[idx], ref.prune,
                               rtol=1e-6, atol=1e-12)
    np.testing.assert_allclose(np.asarray(sol.bandwidth)[idx], ref.bandwidth,
                               rtol=1e-6)
    np.testing.assert_allclose(float(sol.deadline), ref.deadline, rtol=1e-6)


def test_deadline_cap_binds_and_prunes_harder():
    prob = make_problem(seed=1)
    free = _solve_jax(prob, prob.weight)
    cap = float(free.deadline) * 0.6
    capped = FS.solve_cell(
        jnp.asarray(prob.h_up), jnp.asarray(prob.num_samples),
        jnp.asarray(prob.cpu_hz), jnp.asarray(prob.tx_power),
        jnp.asarray(prob.max_prune), jnp.asarray(prob.bound.m),
        deadline_cap=jnp.asarray(cap),
        bandwidth_hz=prob.cfg.bandwidth_hz,
        noise_psd=prob.cfg.noise_psd_w_per_hz,
        waterfall_m0=prob.cfg.waterfall_m0,
        model_bits=prob.cfg.model_bits,
        cycles_per_sample=prob.cfg.cycles_per_sample,
        weight=prob.weight)
    assert float(capped.deadline) <= cap * (1 + 1e-9)
    assert np.mean(np.asarray(capped.prune)) >= np.mean(np.asarray(free.prune))
    assert np.all(np.asarray(capped.prune) <= prob.max_prune + 1e-12)


def test_solve_fleet_vmap_shapes_and_consistency():
    """The vmapped fleet call equals per-cell calls, cell by cell."""
    cells = 3
    probs = [make_problem(seed=s) for s in range(cells)]
    stack = lambda f: jnp.stack([jnp.asarray(f(p)) for p in probs])
    sol = FS.solve_fleet(
        stack(lambda p: p.h_up), stack(lambda p: p.num_samples),
        stack(lambda p: p.cpu_hz), stack(lambda p: p.tx_power),
        stack(lambda p: p.max_prune),
        jnp.asarray([p.bound.m for p in probs]),
        bandwidth_hz=probs[0].cfg.bandwidth_hz,
        noise_psd=probs[0].cfg.noise_psd_w_per_hz,
        waterfall_m0=probs[0].cfg.waterfall_m0,
        model_bits=probs[0].cfg.model_bits,
        cycles_per_sample=probs[0].cfg.cycles_per_sample,
        weight=probs[0].weight)
    assert sol.prune.shape == (cells, probs[0].num_clients)
    assert sol.deadline.shape == (cells,)
    for c, p in enumerate(probs):
        one = _solve_jax(p, p.weight)
        np.testing.assert_allclose(np.asarray(sol.prune[c]),
                                   np.asarray(one.prune), rtol=1e-9)
        np.testing.assert_allclose(float(sol.deadline[c]),
                                   float(one.deadline), rtol=1e-9)
