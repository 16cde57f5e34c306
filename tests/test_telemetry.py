"""Telemetry primitives: histograms, metric splitting, sinks, trace
spans, record emission — plus the solver-residual surfacing in
``core/tradeoff.py``.  Engine-level contracts (bit-identity, key-set
stability) live in ``test_metrics_contract.py``."""

import csv
import json
import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import tradeoff
from repro.fleet import telemetry as TEL

from conftest import make_problem


# ---------------------------------------------------------------------------
# histogram
# ---------------------------------------------------------------------------

def test_histogram_mass_equals_element_count():
    x = jax.random.uniform(jax.random.PRNGKey(0), (3, 40))
    h = TEL.histogram(x, 0.0, 1.0, 16)
    assert h.shape == (3, 16)
    np.testing.assert_allclose(np.asarray(h).sum(axis=-1), 40.0, rtol=1e-6)


def test_histogram_matches_numpy_on_interior_values():
    x = jnp.asarray([0.05, 0.05, 0.51, 0.97])
    h = np.asarray(TEL.histogram(x, 0.0, 1.0, 10))
    ref, _ = np.histogram(np.asarray(x), bins=10, range=(0.0, 1.0))
    np.testing.assert_array_equal(h, ref)


def test_histogram_clips_out_of_range_into_edge_bins():
    x = jnp.asarray([-5.0, -0.001, 1.001, 42.0])
    h = np.asarray(TEL.histogram(x, 0.0, 1.0, 4))
    np.testing.assert_array_equal(h, [2.0, 0.0, 0.0, 2.0])


def test_histogram_sanitizes_nan_and_inf():
    x = jnp.asarray([jnp.nan, jnp.inf, -jnp.inf, 0.5])
    h = np.asarray(TEL.histogram(x, 0.0, 1.0, 2))
    # nan -> bottom, -inf -> bottom, +inf -> top, 0.5 -> top half
    np.testing.assert_array_equal(h, [2.0, 2.0])
    assert h.sum() == x.size  # mass invariant survives non-finite input


def test_histogram_weighted_mass():
    x = jnp.asarray([0.1, 0.9])
    w = jnp.asarray([0.25, 0.5])
    h = np.asarray(TEL.histogram(x, 0.0, 1.0, 2, weights=w))
    np.testing.assert_allclose(h, [0.25, 0.5])


def test_bin_edges_span_range():
    e = np.asarray(TEL.bin_edges(-2.0, 2.0, 8))
    assert e.shape == (9,)
    np.testing.assert_allclose([e[0], e[-1]], [-2.0, 2.0])


# ---------------------------------------------------------------------------
# config validation / split_metrics
# ---------------------------------------------------------------------------

def test_telemetry_config_validates():
    with pytest.raises(ValueError):
        TEL.TelemetryConfig(bins=0)
    with pytest.raises(ValueError):
        TEL.TelemetryConfig(per_range=(1.0, 0.0))


def test_split_metrics_strips_prefix_and_preserves_core():
    metrics = {"loss": 1.0, "tel_per_hist": 2.0, "eval_accuracy": 3.0}
    core, tel = TEL.split_metrics(metrics)
    assert core == {"loss": 1.0, "eval_accuracy": 3.0}
    assert tel == {"per_hist": 2.0}


def test_split_metrics_none_when_no_telemetry():
    core, tel = TEL.split_metrics({"loss": 1.0})
    assert tel is None and core == {"loss": 1.0}


# ---------------------------------------------------------------------------
# sinks
# ---------------------------------------------------------------------------

def _fake_records():
    return [{"kind": "run", "mode": "sync", "rounds": 2},
            {"kind": "round", "round": 0, "loss": 1.5},
            {"kind": "round", "round": 1, "loss": 1.2}]


def test_memory_sink_protocol():
    sink = TEL.MemorySink()
    assert isinstance(sink, TEL.TelemetrySink)
    for r in _fake_records():
        sink.emit(r)
    sink.close()
    assert len(sink.records) == 3 and sink.closed


def test_jsonl_sink_round_trip(tmp_path):
    path = os.path.join(tmp_path, "tel.jsonl")
    sink = TEL.JSONLSink(path)
    for r in _fake_records():
        sink.emit(r)
    sink.close()
    with open(path) as fh:
        back = [json.loads(line) for line in fh]
    assert back == _fake_records()


def test_csv_sink_writes_header_union(tmp_path):
    path = os.path.join(tmp_path, "tel.csv")
    sink = TEL.CSVSink(path)
    for r in _fake_records():
        sink.emit(r)
    sink.close()
    with open(path) as fh:
        rows = list(csv.DictReader(fh))
    assert len(rows) == 3
    assert rows[1]["kind"] == "round" and float(rows[1]["loss"]) == 1.5


def test_sink_for_path_dispatches_on_extension(tmp_path):
    assert isinstance(TEL.sink_for_path(os.path.join(tmp_path, "a.csv")),
                      TEL.CSVSink)
    assert isinstance(TEL.sink_for_path(os.path.join(tmp_path, "a.jsonl")),
                      TEL.JSONLSink)


# ---------------------------------------------------------------------------
# span recorder
# ---------------------------------------------------------------------------

def test_span_recorder_chrome_trace(tmp_path):
    rec = TEL.SpanRecorder()
    with rec.span("outer", clients=8):
        with rec.span("inner"):
            pass
    assert [e["name"] for e in rec.events] == ["inner", "outer"]
    doc = rec.chrome_trace()
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    assert all(e["ph"] == "X" and e["dur"] >= 0 for e in evs)
    outer = next(e for e in evs if e["name"] == "outer")
    inner = next(e for e in evs if e["name"] == "inner")
    assert outer["args"] == {"clients": 8}
    assert outer["ts"] <= inner["ts"]
    assert outer["ts"] + outer["dur"] >= inner["ts"] + inner["dur"]
    path = os.path.join(tmp_path, "trace.json")
    rec.write(path)
    with open(path) as fh:
        assert json.load(fh)["traceEvents"]


def test_span_recorder_stamps_the_wall_clock():
    rec = TEL.SpanRecorder()
    before = time.time_ns()
    with rec.span("outer"):
        rec.count("hit")
    after = time.time_ns()
    outer, = rec.spans("outer")
    hit, = rec.spans("hit")
    assert before <= outer.start_ns <= hit.start_ns == hit.end_ns \
        <= outer.end_ns <= after
    assert rec.totals()["hit"] == (1, 0.0)
    instant = next(e for e in rec.events if e["name"] == "hit")
    assert instant["ph"] == "i" and instant["ts"] == hit.start_ns / 1e3


def test_span_recorder_buffer_stays_bounded():
    rec = TEL.SpanRecorder()
    n = TEL.SpanRecorder.CAPACITY + 100
    for i in range(n):
        rec.record("x", i, i + 10, program=f"p{i % 3}")
    kept = rec.spans()
    assert len(kept) == TEL.SpanRecorder.CAPACITY
    assert kept[0].start_ns == 100 and kept[-1].start_ns == n - 1
    # totals still cover every span recorded
    assert rec.totals()["x"] == pytest.approx((n, n * 10e-9))
    per = rec.program_totals("x")
    assert sum(v["count"] for v in per.values()) == n
    assert per["p0"]["first_s"] == pytest.approx(10e-9)


def test_compile_report_counts_a_recompile():
    rec = TEL.process_recorder()
    assert TEL.process_recorder() is rec          # one per process

    @jax.jit
    def recompiled_in_this_test(x):
        return x * 3.0

    recompiled_in_this_test(jnp.ones(3))
    recompiled_in_this_test(jnp.ones(3))          # cached: no compile
    recompiled_in_this_test(jnp.ones(5))          # a new shape compiles
    got = TEL.compile_report()["jit(recompiled_in_this_test)"]
    assert got["count"] == 2
    assert 0.0 < got["first_s"] < got["total_s"]


def test_process_recorder_holds_build_and_jax_spans_on_one_clock():
    from repro.fleet import FleetConfig, FleetTopology, build_simulation
    rec = TEL.process_recorder()
    before = time.time_ns()
    sim = build_simulation(FleetConfig(
        topology=FleetTopology(num_cells=2, clients_per_cell=4), rounds=1,
        seed=9173))
    sim.simulate(sim.params, sim.round_keys)
    after = time.time_ns()
    build = [s for s in rec.spans("fleet.build") if s.start_ns >= before]
    top = [s for s in build if s.name == "fleet.build"]
    assert len(top) == 1
    b = top[0]
    assert b.args == {"mode": "sync", "clients": 8}
    assert {s.name for s in build} == {
        "fleet.build", "fleet.build.population", "fleet.build.task",
        "fleet.build.init", "fleet.build.round"}
    assert all(b.start_ns <= s.start_ns <= s.end_ns <= b.end_ns
               for s in build)
    jax_spans = [s for s in rec.spans("jax.") if s.start_ns >= before
                 and s.end_ns > s.start_ns]
    assert {s.name for s in jax_spans} >= {"jax.trace", "jax.lower",
                                           "jax.compile"}
    assert all(s.end_ns <= after for s in jax_spans)
    assert all(s.args["program"] for s in jax_spans)
    # the round compiles on the first call, after the build
    rnd = [s for s in jax_spans if s.args["program"] == "jit(simulate)"
           and s.name == "jax.compile"]
    assert rnd and rnd[-1].start_ns >= b.end_ns
    # no trace is kept inside another trace
    traces = sorted((s.start_ns, s.end_ns) for s in jax_spans
                    if s.name == "jax.trace")
    assert all(b0 <= a1 for (a0, b0), (a1, b1) in zip(traces, traces[1:]))


def test_profiler_host_events_share_the_recorder_clock(tmp_path):
    from jax.profiler import ProfileData
    rec = TEL.SpanRecorder()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with rec.span("telemetry.clock_probe"):
            time.sleep(0.02)
    finally:
        jax.profiler.stop_trace()
    path = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    pd = ProfileData.from_file(str(path))
    # host events are stored relative to the capture's start time
    start = next(dict(p.stats)["profile_start_time"] for p in pd.planes
                 if p.name == "Task Environment")
    ev = next(e for p in pd.planes if p.name.startswith("/host:")
              for line in p.lines for e in line.events
              if e.name == "telemetry.clock_probe")
    ours, = rec.spans("telemetry.clock_probe")
    assert abs(start + ev.start_ns - ours.start_ns) < 5e6        # 5 ms
    assert abs(ev.duration_ns - (ours.end_ns - ours.start_ns)) < 5e6


# ---------------------------------------------------------------------------
# solver residual surfacing (core/tradeoff.py)
# ---------------------------------------------------------------------------

def test_solve_alternating_reports_residual():
    sol = tradeoff.solve_alternating(make_problem(num_clients=3), rtol=1e-8)
    assert isinstance(sol.residual, float)
    assert 0.0 <= sol.residual <= 1e-8  # converged: residual under rtol


def test_solve_alternating_warns_when_iteration_capped():
    with pytest.warns(tradeoff.SolverConvergenceWarning):
        sol = tradeoff.solve_alternating(make_problem(num_clients=3),
                                         max_iters=1, rtol=1e-30)
    assert sol.iterations == 1
    assert sol.residual > 1e-30


def test_solve_alternating_converged_run_does_not_warn():
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error", tradeoff.SolverConvergenceWarning)
        tradeoff.solve_alternating(make_problem(num_clients=3), max_iters=200)
