"""Cohort-gather equivalence matrix (ISSUE 7).

The cohort path (``FleetConfig.cohort_gather``) gathers each round's
scheduled clients into a dense (C, m) batch before the gradient pass and
— interference-free — routes the per-cell solver over the gathered
cohort, scattering the solution back.  The contract this file pins:

* cohort-on equals cohort-off across the full mode matrix
  {sync, async} x {reference, fused_xla} x {orthogonal, hex} x
  {cloud_period 1, 2} — to 1e-6 under x64 (the gathered gradient sum may
  reassociate float addition; in practice the tiny configs here agree
  bitwise, but the tolerance is the contract);
* the schedule draw is shared: ``scheduler.participation_cohort`` ranks
  the same single Gumbel tensor as ``participation_mask``, so the mask is
  bit-identical and the cohort lists exactly the masked clients;
* edge cases: cohort == fleet (forced identity gather) is *bitwise*;
  cohort of 1; a ragged final block under ``cell_chunk`` /
  ``control_chunk``; a deadline that excludes every client;
* chunked control (``control_chunk``) is bit-identical to the global
  solve, gathered or not;
* telemetry on/off leaves the cohort path's trajectories bit-identical
  (control draws are shared with the telemetry-off build);
* a two-axis ("cells", "data") fleet mesh reproduces the meshless run.
"""

import contextlib
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.fleet import (AsyncConfig, FleetConfig, FleetTopology,
                         HexInterference, ScheduleConfig, run_fleet)
from repro.fleet import engine as FE
from repro.fleet import scheduler as SCHED
from repro.fleet import telemetry as TEL
from repro.launch import mesh as MESH


@contextlib.contextmanager
def x64():
    """Equivalence under float64: the tolerance tests the algorithm, not
    fp32 reduction-order noise."""
    with jax.enable_x64(True):
        yield


def tiny_cfg(cohort, m=4, rounds=3, cells=3, clients=8, geometry=None,
             participation="uniform", **kw):
    sched = kw.pop("schedule", None) or ScheduleConfig(
        participation=participation, participants_per_cell=m)
    return FleetConfig(
        topology=FleetTopology(num_cells=cells, clients_per_cell=clients),
        schedule=sched, geometry=geometry, rounds=rounds,
        cohort_gather=cohort, **kw)


def traj(res):
    """The numeric trajectory leaves an equivalence assertion compares."""
    out = dict(losses=res.losses, accuracy=res.accuracy,
               latencies=res.latencies, deadlines=res.deadlines,
               mean_prune=res.mean_prune, mean_per=res.mean_per,
               participants=res.participants,
               bandwidth_util=res.bandwidth_util,
               learning_cost=res.learning_cost,
               wall_clock=res.wall_clock, staleness=res.staleness)
    for i, leaf in enumerate(jax.tree.leaves(res.params)):
        out[f"param_{i}"] = leaf
    return {k: np.asarray(v) for k, v in out.items() if v is not None}


def assert_traj_close(a, b, rtol=0.0, atol=0.0):
    ta, tb = traj(a), traj(b)
    assert ta.keys() == tb.keys()
    for k in ta:
        # inf == inf must pass (excluded-client latencies); nan must not
        np.testing.assert_allclose(ta[k], tb[k], rtol=rtol, atol=atol,
                                   err_msg=k)


# ---------------------------------------------------------------------------
# the matrix: {sync, async} x {reference, fused_xla} x {ortho, hex}
#             x {cloud_period 1, 2}
# ---------------------------------------------------------------------------

MATRIX = [
    (mode, kernel, geom, period)
    for mode in ("sync", "async")
    for kernel in ("reference", "fused_xla")
    for geom in ("orthogonal", "hex")
    for period in (1, 2)
]


@pytest.mark.parametrize("mode,kernel,geom,period", MATRIX)
def test_cohort_matches_fleet_matrix(mode, kernel, geom, period):
    geometry = (None if geom == "orthogonal"
                else HexInterference(reuse=1, max_neighbors=2))
    kw = dict(kernel=kernel, cloud_period=period, geometry=geometry)
    if mode == "async":
        kw["async_config"] = AsyncConfig(buffer_size=6, max_staleness=3)
    with x64():
        off = run_fleet(tiny_cfg(False, **kw), mode=mode)
        on = run_fleet(tiny_cfg(True, **kw), mode=mode)
    assert_traj_close(on, off, rtol=1e-6, atol=1e-9)


# ---------------------------------------------------------------------------
# edge cases
# ---------------------------------------------------------------------------

def test_cohort_equals_fleet_is_bitwise():
    """Full participation forces the identity cohort: the gather reorders
    nothing and must be bit-exact, not just close."""
    with x64():
        off = run_fleet(tiny_cfg(False, participation="full", m=0))
        on = run_fleet(tiny_cfg(True, participation="full", m=0))
    assert_traj_close(on, off)  # exact


def test_cohort_of_one():
    with x64():
        off = run_fleet(tiny_cfg(False, m=1))
        on = run_fleet(tiny_cfg(True, m=1))
    assert_traj_close(on, off, rtol=1e-6, atol=1e-9)


def test_cohort_ragged_final_cell_chunk():
    """cell_chunk=2 over 3 cells: one full block + a ragged tail on the
    gathered gradient axis.  Chunked accumulation reassociates the
    cross-cell gradient sum, so the contract is the 1e-6 tolerance."""
    with x64():
        base = run_fleet(tiny_cfg(True))
        ragged = run_fleet(tiny_cfg(True, cell_chunk=2))
    assert_traj_close(ragged, base, rtol=1e-6, atol=1e-9)


@pytest.mark.parametrize("cohort", [False, True])
def test_control_chunk_bitwise(cohort):
    """Chunked control (one full block + a ragged tail over 3 cells):
    frozen Algorithm-1 lanes are idempotent, so blocking the solver vmap
    over cells is exact — on both the gathered and the full-fleet path."""
    with x64():
        base = run_fleet(tiny_cfg(cohort))
        chunked = run_fleet(tiny_cfg(cohort, control_chunk=2))
    assert_traj_close(chunked, base)


def test_deadline_excludes_every_client():
    """A 1 ns round deadline schedules nobody; the gathered solve still
    runs (all-zero mask in the cohort) and both paths agree."""
    sched = ScheduleConfig(participation="uniform", participants_per_cell=4,
                           round_deadline_s=1e-9)
    with x64():
        off = run_fleet(tiny_cfg(False, schedule=sched))
        on = run_fleet(tiny_cfg(True, schedule=sched))
    assert_traj_close(on, off, rtol=1e-6, atol=1e-9)
    assert np.all(traj(on)["participants"] == 0)


# ---------------------------------------------------------------------------
# schedule draw sharing
# ---------------------------------------------------------------------------

def test_participation_cohort_matches_mask():
    k = jnp.arange(1.0, 33.0).reshape(4, 8) * jnp.ones((4, 8))
    for mode in ("uniform", "weighted"):
        sched = ScheduleConfig(participation=mode, participants_per_cell=3)
        key = jax.random.PRNGKey(7)
        mask = SCHED.participation_mask(key, sched, k)
        mask2, cohort = SCHED.participation_cohort(key, sched, k)
        np.testing.assert_array_equal(np.asarray(mask), np.asarray(mask2))
        m, ch = np.asarray(mask), np.asarray(cohort)
        assert ch.shape == (4, 3)
        for c in range(4):
            np.testing.assert_array_equal(ch[c], np.flatnonzero(m[c]))
        assert np.all(np.diff(ch, axis=-1) > 0)   # sorted, no duplicates


def test_participation_cohort_full_is_identity():
    k = jnp.ones((2, 5))
    mask, cohort = SCHED.participation_cohort(
        jax.random.PRNGKey(0), ScheduleConfig(), k)
    np.testing.assert_array_equal(np.asarray(mask), 1.0)
    np.testing.assert_array_equal(np.asarray(cohort),
                                  np.tile(np.arange(5), (2, 1)))


def test_cohort_size_resolution():
    assert SCHED.cohort_size(ScheduleConfig(), 8) == 8
    assert SCHED.cohort_size(
        ScheduleConfig(participation="uniform", participants_per_cell=3), 8) == 3
    assert SCHED.cohort_size(
        ScheduleConfig(participation="uniform", participants_per_cell=99), 8) == 8
    assert SCHED.cohort_size(
        ScheduleConfig(participation="full", participants_per_cell=3), 8) == 8


def test_cohort_auto_enables_on_partial_schedule():
    assert not FE._cohort_enabled(tiny_cfg(None, participation="full", m=0))
    assert FE._cohort_enabled(tiny_cfg(None))
    assert not FE._cohort_enabled(tiny_cfg(False))
    assert FE._cohort_enabled(tiny_cfg(True, participation="full", m=0))


# ---------------------------------------------------------------------------
# telemetry must not perturb the cohort path
# ---------------------------------------------------------------------------

def test_telemetry_off_bitwise_on_cohort_path():
    with x64():
        plain = run_fleet(tiny_cfg(True))
        telled = run_fleet(tiny_cfg(True, telemetry=TEL.TelemetryConfig()))
    assert telled.telemetry is not None and plain.telemetry is None
    assert_traj_close(telled, plain)  # control draws shared: exact


# ---------------------------------------------------------------------------
# two-axis mesh
# ---------------------------------------------------------------------------

def test_fleet_mesh_run_matches_meshless():
    mesh = MESH.make_fleet_mesh(cells=1, data=1)
    assert mesh.axis_names == ("cells", "data")
    with x64():
        base = run_fleet(tiny_cfg(True))
        meshed = run_fleet(tiny_cfg(True), mesh=mesh)
    assert_traj_close(meshed, base)


def test_fleet_mesh_factorization():
    mesh = MESH.make_fleet_mesh()
    n = jax.device_count()
    assert mesh.shape["cells"] * mesh.shape["data"] == n
    assert mesh.shape["cells"] <= mesh.shape["data"]


def test_control_chunk_negative_raises():
    with pytest.raises(ValueError, match="control_chunk"):
        FE.build_simulation(tiny_cfg(True, control_chunk=-1))
