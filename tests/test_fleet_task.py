"""FleetTask substrate (ISSUE 4): one task abstraction driving the engine,
the 5-UE path and the fused kernels.

Pins the PR-4 contract:

* the legacy ``FleetConfig(feature_dim=..., hidden=...)`` API warns but
  produces **bit-identical** trajectories through the SyntheticMLPTask
  shim (sync + async, reference + fused kernels);
* ``TransformerTask`` completes a >= 10-round smoke run with finite,
  decreasing loss on per-layer tile grids, and its fused/XLA path equals
  the vmap reference to 1e-5 under x64;
* ``LinearRegressionTask``'s closed-form optimum makes convergence-rate
  assertions *exact* (the GD error map is linear);
* ``run_any`` fleet-path and 5-UE-path (host reference solver)
  trajectories agree to 1e-5 under x64 on one shared task;
* per-leaf rectangular block grids in ``core.pruning`` expand exactly as
  the scalar-block reference.
"""

import contextlib
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import pruning
from repro.fleet import (AsyncConfig, FleetConfig, FleetTopology,
                         LinearRegressionTask, SyntheticMLPTask,
                         TransformerTask, run_fleet)
from repro.fleet import engine as FE
from repro.fleet.task import auto_tile_grid, make_task


@contextlib.contextmanager
def x64():
    with jax.enable_x64(True):
        yield


def tiny(clients=8, **kw):
    return FleetConfig(
        topology=FleetTopology(num_cells=1, clients_per_cell=clients), **kw)


def _assert_trees_close(a, b, **tol):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb), **tol)


# ---------------------------------------------------------------------------
# Backward-compat shim
# ---------------------------------------------------------------------------

def test_legacy_fields_warn_and_match_task_config_bitwise():
    """Old-style FleetConfig == new-style task config, bit for bit, and the
    old style emits a DeprecationWarning."""
    legacy_kw = dict(feature_dim=24, hidden=(12,), num_classes=3)
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        old = run_fleet(tiny(rounds=3, **legacy_kw))
    assert any(issubclass(w.category, DeprecationWarning) for w in rec)
    new = run_fleet(tiny(rounds=3, task=SyntheticMLPTask(**legacy_kw)))
    np.testing.assert_array_equal(old.losses, new.losses)
    np.testing.assert_array_equal(old.accuracy, new.accuracy)
    np.testing.assert_array_equal(old.latencies, new.latencies)
    for a, b in zip(jax.tree.leaves(old.params), jax.tree.leaves(new.params)):
        np.testing.assert_array_equal(a, b)


def test_legacy_shim_covers_fused_and_async():
    """The shim is path-complete: fused kernels and the async engine see
    the same task the legacy fields used to weld in."""
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        old = run_fleet(tiny(rounds=3, feature_dim=24, kernel="fused"))
        old_a = run_fleet(tiny(rounds=3, feature_dim=24,
                               async_config=AsyncConfig(buffer_size=4)),
                          mode="async")
    assert any(issubclass(w.category, DeprecationWarning) for w in rec)
    task = SyntheticMLPTask(feature_dim=24)
    new = run_fleet(tiny(rounds=3, task=task, kernel="fused"))
    new_a = run_fleet(tiny(rounds=3, task=task,
                           async_config=AsyncConfig(buffer_size=4)),
                      mode="async")
    np.testing.assert_array_equal(old.losses, new.losses)
    np.testing.assert_array_equal(old_a.losses, new_a.losses)


def test_default_config_does_not_warn():
    """FleetConfig() with untouched legacy fields stays silent (every
    existing call site would otherwise spam DeprecationWarnings)."""
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        FE.resolve_task(tiny(rounds=2))


def test_make_task_registry():
    assert isinstance(make_task("mlp"), SyntheticMLPTask)
    assert isinstance(make_task("transformer"), TransformerTask)
    assert isinstance(make_task("linreg"), LinearRegressionTask)
    with pytest.raises(ValueError, match="unknown task"):
        make_task("resnet")


# ---------------------------------------------------------------------------
# TransformerTask: production-model rounds on per-layer tile grids
# ---------------------------------------------------------------------------

def test_transformer_smoke_ten_rounds_loss_decreases():
    """Acceptance: a >= 10-round transformer run on CPU, fused/XLA path,
    finite decreasing loss, exercising per-layer tile grids."""
    task = TransformerTask()
    res = run_fleet(tiny(rounds=10, task=task, kernel="fused", lr=0.5))
    assert np.all(np.isfinite(res.losses))
    assert res.losses[-1] < res.losses[0]
    # genuinely per-layer grids: several distinct (bk, bn) tile shapes
    params = task.init_params(jax.random.PRNGKey(0))
    grids = {tuple(g) for g in task.tile_grid(params) if g is not None}
    assert len(grids) >= 2


def test_transformer_fused_matches_vmap_reference():
    """Acceptance: fused/XLA == vmap reference to 1e-5 on the transformer
    task (x64 so only the algorithm can separate the paths)."""
    task = TransformerTask()
    kw = dict(rounds=4, task=task, lr=0.5)
    with x64():
        ref = run_fleet(tiny(clients=6, kernel="reference",
                             mask_kind="block", **kw))
        fused = run_fleet(tiny(clients=6, kernel="fused", **kw))
    np.testing.assert_allclose(fused.losses, ref.losses, rtol=1e-5,
                               atol=1e-8)
    np.testing.assert_allclose(fused.accuracy, ref.accuracy, rtol=1e-5,
                               atol=1e-8)
    _assert_trees_close(fused.params, ref.params, rtol=1e-5, atol=1e-8)


def test_transformer_async_runs():
    res = run_fleet(tiny(clients=6, rounds=3, task=TransformerTask(), lr=0.5,
                         async_config=AsyncConfig(buffer_size=3,
                                                  max_staleness=4)),
                    mode="async")
    assert np.all(np.isfinite(res.losses))
    assert res.mode == "async"


def test_transformer_model_bits_override_reaches_wireless():
    """The task's physical size D_M replaces the Table-I model_bits, so
    upload latency prices the *actual* model."""
    cfg = tiny(rounds=2, task=TransformerTask())
    cfg2, task, _, params, _, _, _ = FE._build_common(cfg)
    mb = task.model_bits(params)
    assert mb is not None and mb > 0
    assert cfg2.wireless.model_bits == mb
    # the MLP default keeps the paper's Table-I constant
    cfg3, *_ = FE._build_common(tiny(rounds=2))
    assert cfg3.wireless.model_bits == cfg.wireless.model_bits


# ---------------------------------------------------------------------------
# LinearRegressionTask: exact convergence-rate assertions
# ---------------------------------------------------------------------------

def test_linreg_gd_contracts_at_exact_closed_form_rate():
    """Quadratic loss => theta_{t+1} - theta* = (I - lr H)(theta_t -
    theta*) exactly; T steps of cohort GD must land on the matrix-power
    prediction to float-64 precision."""
    with x64():
        task = LinearRegressionTask(noise=0.0)
        kt, ke, ki, kd = jax.random.split(jax.random.PRNGKey(0), 4)
        state = task.build(kt, ke)
        params = task.init_params(ki)
        clients = 6
        batch = jax.vmap(lambda i: task.client_batch(state, kd, i))(
            jnp.arange(clients))
        x = batch["x"].reshape(-1, task.feature_dim)
        y = batch["y"].reshape(-1, task.targets)
        a = jnp.concatenate([x, jnp.ones((x.shape[0], 1))], axis=-1)
        h = a.T @ a / a.shape[0]
        w_star, b_star = task.optimum(x, y)
        theta_star = jnp.concatenate([w_star, b_star[None, :]], axis=0)

        def mean_loss(p):
            return jnp.mean(jax.vmap(lambda b: task.loss(p, b))(batch))

        lr, steps = 0.05, 25
        theta0 = jnp.concatenate(
            [params["linear"]["w"], params["linear"]["b"][None, :]], axis=0)
        p = params
        for _ in range(steps):
            g = jax.grad(mean_loss)(p)
            p = jax.tree.map(lambda q, gi: q - lr * gi, p, g)
        theta_t = jnp.concatenate(
            [p["linear"]["w"], p["linear"]["b"][None, :]], axis=0)

        m = jnp.eye(h.shape[0]) - lr * h
        expect = theta_star + jnp.linalg.matrix_power(m, steps) \
            @ (theta0 - theta_star)
        np.testing.assert_allclose(np.asarray(theta_t), np.asarray(expect),
                                   rtol=1e-9, atol=1e-11)
        # noise-free data: the optimum is the generating parameters
        np.testing.assert_allclose(np.asarray(w_star),
                                   np.asarray(state["w_true"]),
                                   rtol=1e-8, atol=1e-9)


def test_linreg_engine_converges_toward_optimum():
    res = run_fleet(tiny(rounds=10, task=LinearRegressionTask(), lr=0.1))
    assert np.all(np.isfinite(res.losses))
    assert res.losses[-1] < res.losses[0]
    assert res.accuracy[-1] > res.accuracy[0]      # R^2 rises


# ---------------------------------------------------------------------------
# Cross-path equivalence: run_any 5-UE path vs fleet path on one task
# ---------------------------------------------------------------------------

def test_run_any_fleet_path_matches_5ue_path():
    """Satellite: fleet-path and 5-UE-path trajectories agree to 1e-5
    under x64 for the same FLConfig once both sit on one FleetTask (the
    5-UE side steps per round with the *host* reference solver)."""
    from repro.federated import system as SYS

    with x64():
        cfg = SYS.FLConfig(num_clients=5, rounds=6,
                           task=LinearRegressionTask(), lr=0.05)
        host = SYS.run_any(cfg, fleet_threshold=64)   # 5 <= 64: 5-UE path
        fleet = SYS.run_any(cfg, fleet_threshold=0)   # forced fleet engine
    assert host.mode == fleet.mode == "sync"
    np.testing.assert_allclose(host.losses, fleet.losses, rtol=1e-5,
                               atol=1e-8)
    np.testing.assert_allclose(host.accuracy, fleet.accuracy, rtol=1e-5,
                               atol=1e-8)
    np.testing.assert_allclose(host.latencies, fleet.latencies, rtol=1e-5)
    np.testing.assert_allclose(host.mean_prune, fleet.mean_prune, rtol=1e-5,
                               atol=1e-8)
    _assert_trees_close(host.params, fleet.params, rtol=1e-5, atol=1e-8)


def test_run_fleet_reference_supports_partial_participation():
    """PR-5: the host solver grew the mask/cap port, so the 5-UE path now
    steps partial-participation schedules instead of rejecting them (the
    tight cross-path equivalence lives in test_fleet_topology.py)."""
    from repro.federated import system as SYS
    from repro.fleet import ScheduleConfig

    cfg = tiny(rounds=2, task=LinearRegressionTask(),
               schedule=ScheduleConfig(participation="uniform",
                                       participants_per_cell=4))
    res = SYS.run_fleet_reference(cfg)
    assert np.all(np.isfinite(res.losses))
    assert np.all(res.participants <= 4 * cfg.topology.num_cells)


# ---------------------------------------------------------------------------
# Per-leaf rectangular tile grids (core.pruning)
# ---------------------------------------------------------------------------

def test_rect_block_masks_achieve_requested_rate():
    w = jax.random.normal(jax.random.PRNGKey(0), (40, 12))
    params = {"w": w}
    masks = pruning.block_masks(params, 0.5, block=(8, 4))
    rate = float(pruning.achieved_rate(params, masks))
    assert abs(rate - 0.5) < 0.1


def test_per_leaf_grid_masks_from_keep_match_block_masks():
    """masks_from_keep (the generic fused path's expansion) == block_masks
    on a mixed per-leaf grid, for every client rate."""
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    params = {"embed": jax.random.normal(ks[0], (30, 8)),
              "proj": jax.random.normal(ks[1], (8, 20)),
              "scale": jax.random.normal(ks[2], (8,))}
    leaves = jax.tree_util.tree_leaves(params)
    grid = [(6, 4) if leaf.shape == (30, 8)
            else (4, 5) if leaf.shape == (8, 20) else None
            for leaf in leaves]
    states = pruning.block_norm_state(params, grid)
    rates = jnp.asarray([0.0, 0.3, 0.7, 1.0])
    keeps = pruning.block_keep(states, rates)
    for ci in range(rates.shape[0]):
        ref = pruning.block_masks(params, rates[ci], block=grid)
        got = pruning.masks_from_keep(
            params, [None if k is None else k[ci] for k in keeps], grid)
        for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(got)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_auto_tile_grid_shapes():
    params = {"tall": jnp.zeros((256, 16)), "wide": jnp.zeros((16, 128)),
              "bias": jnp.zeros((16,))}
    leaves = jax.tree_util.tree_leaves(params)
    grid = auto_tile_grid(params, target_tiles=8, min_block=4)
    by_shape = {tuple(l.shape): g for l, g in zip(leaves, grid)}
    assert by_shape[(256, 16)] == (32, 4)
    assert by_shape[(16, 128)] == (4, 16)
    assert by_shape[(16,)] is None


# ---------------------------------------------------------------------------
# Trainer + mesh consumers of the task substrate
# ---------------------------------------------------------------------------

def test_task_train_step_multi_leaf_batch():
    """make_task_train_step handles generic batch pytrees (the P(caxes)
    prefix spec broadcasts over all leaves)."""
    from repro.federated import trainer as FT
    from repro.launch import mesh as MESH

    mesh = MESH.make_host_mesh(model=1)
    task = LinearRegressionTask()
    step = FT.make_task_train_step(task, mesh, client_axes=("data",), lr=0.1)
    n = FT.num_clients(mesh, ("data",))
    kt, ke, ki, kd = jax.random.split(jax.random.PRNGKey(0), 4)
    state = task.build(kt, ke)
    params = task.init_params(ki)
    batch = jax.vmap(lambda i: task.client_batch(state, kd, i))(
        jnp.arange(n))
    batch = jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]), batch)
    new_params, metrics = step(params, batch, jnp.zeros((n,)),
                               jnp.ones((n,)), jnp.full((n,), 40.0))
    assert bool(jnp.isfinite(metrics["loss"]))
    delta = sum(float(jnp.sum(jnp.abs(a - b)))
                for a, b in zip(jax.tree.leaves(new_params),
                                jax.tree.leaves(params)))
    assert delta > 0.0


def test_engine_task_with_mesh_client_sharding():
    """The gradient batch's client axis constrains to the mesh "data" axis
    (single-device here; pins the code path the multi-device run uses)."""
    from repro.launch import mesh as MESH

    mesh = MESH.make_host_mesh(model=1)
    res = run_fleet(tiny(rounds=3, task=LinearRegressionTask(), lr=0.05),
                    mesh=mesh)
    assert np.all(np.isfinite(res.losses))
