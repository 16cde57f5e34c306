"""The readers of the round's nested scopes, of the unscoped device time
and of set-up's stages, on synthetic reductions and a synthetic recorder."""

import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import run  # noqa: E402
from yardstick import setup_spans as SS  # noqa: E402
from yardstick import trace as TR  # noqa: E402

from repro.fleet import telemetry as TEL  # noqa: E402

CELLS = ("smollm-135m.fleet-train", "fleet-1m-mlp.sync-cohort")
S = 1_000_000_000                                    # ns per second
SETUP = ("setup_phase_s.build", "setup_phase_s.trace",
         "setup_phase_s.compile", "setup_cache_misses")


def _ctx(units=2, window_s=1.0):
    return {"host_window_s": window_s, "device_kind": "TPU v5 lite",
            "work": {"units": units, "flops": 0.0}}


def _reduce(cell, ops, window):
    scopes = [cell.reader(m).get("scope") for m in cell.per_layer]
    return TR.reduce(ops, [], window, [s for s in scopes if s])


def _read(cell, name, red, ctx):
    m = next(m for m in cell.per_layer if m["name"] == name)
    from yardstick import readers
    return readers.read(cell.reader(m), red, ctx)


def test_nested_scopes_count_for_parent_and_child():
    cell = run.Cell("fleet-1m-mlp.sync-cohort")
    g = "jit(simulate)/while/body/closed_call/fleet.gradient"
    s = "jit(simulate)/while/body/closed_call/fleet.solve/vmap()/while/body"
    ops = [
        TR.Op(0.0, 0.1, "fusion.1", f"{s}/fleet.solve.vertex/mul"),
        TR.Op(0.1, 0.3, "fusion.2", f"{s}/fleet.solve.bandwidth/div"),
        TR.Op(0.3, 0.4, "fusion.3", f"{s}/fleet.solve.cost/select_n"),
        TR.Op(0.4, 0.5, "sort.1", "jit(simulate)/fleet.solve/gather"),
        TR.Op(0.5, 0.6, "fusion.4", f"{g}/fleet.data/jit(_normal)/erf"),
        TR.Op(0.6, 0.7, "fusion.5",
              f"{g}/vmap(fleet.client)/fleet.mask/jit(quantile)/sort"),
        TR.Op(0.7, 0.9, "fusion.6", f"{g}/vmap(fleet.client)/fleet.backprop/"
              "transpose(jvp())/dot_general"),
        TR.Op(0.9, 1.0, "fusion.7", "jit(simulate)/fleet.schedule/sort"),
    ]
    red = _reduce(cell, ops, (0.0, 2.0))
    ctx = _ctx(units=2)
    got = run.layer_metrics(cell, red, ctx)
    ms = lambda s: pytest.approx(1e3 * s / 2)
    assert got["fleet_phase_ms.solve"]["value"] == ms(0.5)
    assert got["fleet_phase_ms.solve.vertex"]["value"] == ms(0.1)
    assert got["fleet_phase_ms.solve.bandwidth"]["value"] == ms(0.2)
    assert got["fleet_phase_ms.solve.cost"]["value"] == ms(0.1)
    assert got["fleet_phase_ms.gradient"]["value"] == ms(0.4)
    assert got["fleet_phase_ms.data"]["value"] == ms(0.1)
    assert got["fleet_phase_ms.mask"]["value"] == ms(0.1)
    assert got["fleet_phase_ms.backprop"]["value"] == ms(0.2)
    assert got["fleet_phase_ms.schedule"]["value"] == ms(0.1)
    assert got["fleet_phase_ms.unscoped"]["value"] == pytest.approx(0.0)
    assert "fleet_phase_ms.merge" not in got            # nothing to read


def test_unscoped_is_busy_time_outside_the_top_level_scopes():
    cell = run.Cell("fleet-1m-mlp.sync-cohort")
    ops = [
        # a loop op spans its body: its gaps are busy, and scoped by no op
        TR.Op(0.0, 1.0, "while.1", "jit(simulate)/while"),
        TR.Op(0.1, 0.4, "fusion.1", "jit(simulate)/fleet.solve/mul"),
        TR.Op(0.5, 0.6, "fusion.2",
              "jit(simulate)/fleet.gradient/fleet.mask/sort"),
        TR.Op(1.0, 1.25, "sort.1", "jit(simulate)/argsort"),
    ]
    red = _reduce(cell, ops, (0.0, 2.0))
    got = _read(cell, "fleet_phase_ms.unscoped", red, _ctx(units=5))
    # busy 1.25 s, scoped 0.4 s, over 5 rounds
    assert got == pytest.approx(1e3 * 0.85 / 5)
    empty = TR.reduce([], [], (0.0, 2.0), [])
    assert _read(cell, "fleet_phase_ms.unscoped", empty, _ctx()) is None


def _recorder():
    """Set-up (build, an outer trace holding a nested one, lowerings,
    compiles, one cache write), a 20 s window with no span, then the
    round lowered again and a reference program, as a run records them."""
    rec = TEL.SpanRecorder()
    rec.record("fleet.build", 0, 10 * S, mode="sync", clients=16)
    rec.record("fleet.build.population", 1 * S, 4 * S)
    rec.record("jax.trace", 2 * S, 3 * S, program="_normal")
    rec.record("jax.lower", 3 * S, 4 * S, program="jit(_normal)")
    rec.record("jax.compile", 4 * S, 6 * S, program="jit(_normal)")
    rec.record("jax.cache_miss", 5 * S, 5 * S)
    rec.record("jax.trace", 11 * S, 14 * S, program="simulate")
    rec.record("jax.trace", 12 * S, 13 * S, program="inner")
    rec.record("jax.lower", 14 * S, 15 * S, program="jit(simulate)")
    rec.record("jax.compile", 15 * S, 25 * S, program="jit(simulate)")
    rec.record("jax.cache_hit", 24 * S, 24 * S)
    # the window: 25 s .. 45 s; then the round again and the reference
    rec.record("jax.trace", 45 * S, 48 * S, program="simulate")
    rec.record("jax.lower", 48 * S, 49 * S, program="jit(simulate)")
    rec.record("jax.compile", 49 * S, 50 * S, program="jit(simulate)")
    rec.record("jax.trace", 51 * S, 52 * S, program="round")
    rec.record("jax.compile", 52 * S, 60 * S, program="jit(round)")
    rec.record("jax.cache_miss", 59 * S, 59 * S)
    return rec


@pytest.mark.parametrize("cell_name", CELLS)
def test_setup_stages_count_set_up_once(cell_name, monkeypatch):
    rec = _recorder()
    monkeypatch.setattr(SS, "recorder", lambda: rec)
    cell = run.Cell(cell_name)
    red = TR.reduce([], [], (0.0, 5.0), [])
    got = {n: _read(cell, n, red, _ctx(window_s=5.0)) for n in SETUP}
    # build 10 s, of which 2..6 s under JAX spans
    assert got["setup_phase_s.build"] == pytest.approx(6.0)
    # traces and lowerings 2..4 s and 11..15 s: the nested trace once,
    # the round's second lowering after the window not at all
    assert got["setup_phase_s.trace"] == pytest.approx(6.0)
    assert got["setup_phase_s.compile"] == pytest.approx(12.0)
    assert got["setup_cache_misses"] == 1
    assert sum(got[n] for n in SETUP[:3]) <= 25.0       # set-up's length


def test_setup_starts_at_the_last_build(monkeypatch):
    rec = _recorder()
    rec.record("fleet.build", 70 * S, 71 * S)
    rec.record("jax.compile", 72 * S, 73 * S, program="jit(simulate)")
    rec.record("jax.compile", 100 * S, 101 * S, program="jit(round)")
    monkeypatch.setattr(SS, "recorder", lambda: rec)
    spans = SS.setup(_ctx(window_s=5.0))
    assert [s.start_ns // S for s in spans] == [70, 72]
    assert SS.build_s(spans) == pytest.approx(1.0)
    assert SS.cache_misses(spans) == 0


def test_setup_readers_read_nothing_without_a_recorder(monkeypatch):
    cell = run.Cell(CELLS[1])
    red = TR.reduce([], [], (0.0, 5.0), [])
    monkeypatch.setattr(SS, "recorder", lambda: None)
    assert all(_read(cell, n, red, _ctx(window_s=5.0)) is None
               for n in SETUP)
    # a recorder with no build in it
    rec = TEL.SpanRecorder()
    rec.record("jax.compile", 0, S, program="jit(f)")
    monkeypatch.setattr(SS, "recorder", lambda: rec)
    assert all(_read(cell, n, red, _ctx(window_s=5.0)) is None
               for n in SETUP)


def test_setup_is_unread_where_no_stretch_can_hold_the_window(monkeypatch):
    rec = _recorder()
    monkeypatch.setattr(SS, "recorder", lambda: rec)
    assert SS.setup(_ctx(window_s=5.0)) is not None
    # the longest stretch with no span open is 20 s
    assert SS.setup(_ctx(window_s=30.0)) is None
