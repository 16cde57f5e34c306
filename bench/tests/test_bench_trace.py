"""The trace reduction: busy/idle union, per-scope device time, top ops and
idle gaps named by the host span open during them -- on synthetic events
and on a small trace recorded on a TPU v5e (``fixtures/``)."""

import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from yardstick import trace as TR  # noqa: E402

FIXTURES = pathlib.Path(__file__).resolve().parent / "fixtures"


def _ops():
    return [
        TR.Op(0.0, 1.0, "fusion.1", "jit(f)/while/body/fleet.solve/add"),
        TR.Op(0.5, 2.0, "fusion.2", "jit(f)/fleet.gradient/dot_general"),
        TR.Op(4.0, 5.0, "convolution.3", "jit(f)/fleet.gradient/conv"),
        TR.Op(9.0, 12.0, "copy.4", ""),
    ]


def _spans():
    return [TR.Span(-1.0, 11.0, "bench.window"),
            TR.Span(2.0, 3.5, "bench.call"),
            TR.Span(5.0, 9.0, "bench.sync")]


def test_union_merges_overlaps():
    assert TR.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == \
        [(0, 2.5), (3, 4)]


def test_busy_and_scopes_inside_window():
    red = TR.reduce(_ops(), _spans(), (0.0, 10.0),
                    ["fleet.solve", "fleet.gradient", "fleet.merge"])
    # busy: [0, 2] + [4, 5] + [9, 10] (clipped at the window's end)
    assert red.busy_s == pytest.approx(4.0)
    assert red.window_s == pytest.approx(10.0)
    assert red.idle_share == pytest.approx(0.6)
    # overlapping ops each count their own time to their scope
    assert red.scope_s == pytest.approx(
        {"fleet.solve": 1.0, "fleet.gradient": 2.5, "fleet.merge": 0.0})


def test_gaps_named_by_innermost_open_span():
    red = TR.reduce(_ops(), _spans(), (0.0, 10.0))
    # gaps [2, 4] (mid 3, inside bench.call) and [5, 9] (bench.sync)
    assert red.gaps == [("bench.sync", pytest.approx(4.0)),
                        ("bench.call", pytest.approx(2.0))]


def test_top_ops_by_stable_label():
    red = TR.reduce(_ops(), _spans(), (0.0, 10.0))
    bd = TR.breakdown(red)
    labels = dict(bd["device_ops"])
    assert labels["fleet.gradient:fusion"] == pytest.approx(1.5)
    assert labels["fleet.gradient:convolution"] == pytest.approx(1.0)
    assert labels["fleet.solve:fusion"] == pytest.approx(1.0)
    assert labels["copy"] == pytest.approx(1.0)
    assert [k for k, _ in bd["device_ops"]][0] == "fleet.gradient:fusion"


def test_loop_ops_are_not_counted_twice():
    """A ``while`` op spans its body's ops: only the body counts."""
    ops = [TR.Op(0.0, 4.0, "while.1", "jit(f)/fleet.solve/while"),
           TR.Op(0.0, 1.0, "fusion.2", "jit(f)/fleet.solve/while/body/add"),
           TR.Op(2.0, 3.0, "fusion.3", "jit(f)/fleet.solve/while/body/mul"),
           TR.Op(5.0, 6.0, "fusion.4", "jit(f)/fleet.merge/sub")]
    red = TR.reduce(ops, [], (0.0, 8.0), ["fleet.solve", "fleet.merge"])
    assert red.busy_s == pytest.approx(5.0)
    assert red.scope_s == pytest.approx({"fleet.solve": 2.0,
                                         "fleet.merge": 1.0})
    assert "fleet.solve:while" not in red.op_s


def test_busy_is_averaged_over_devices():
    ops = [TR.Op(0.0, 1.0, "a", device=0), TR.Op(0.0, 3.0, "b", device=1)]
    red = TR.reduce(ops, [], (0.0, 4.0))
    assert red.devices == 2
    assert red.busy_s == pytest.approx(2.0)


def test_scope_map_reads_op_name_metadata():
    module, ops = TR.scope_map((FIXTURES / "v5e_tiny.hlo.txt").read_text())
    assert module == "jit_f"
    assert ops["convolution_tanh_fusion"] == "jit(f)/fleet.solve/dot_general"
    assert ops["fusion"] == "jit(f)/fleet.gradient/dot_general"


def test_recorded_chip_trace():
    """A jitted step with two scopes (``fleet.solve``: tanh(x @ x);
    ``fleet.gradient``: sin(y) @ y, 512 x 512), called three times under
    ``bench.window``, each call followed by ``bench.sync`` and a 3 ms host
    sleep (``bench.host``); recorded on one TPU v5e.  The scope of each
    op comes from the same function compiled for a described v5e (the
    op names match the chip's)."""
    maps = dict([TR.scope_map((FIXTURES / "v5e_tiny.hlo.txt").read_text())])
    ops, spans = TR.read(FIXTURES / "v5e_tiny.xplane.pb", maps)
    assert ops, "no device ops read from the fixture"
    win = TR.window_of(spans, "bench.window")
    assert win is not None and win[1] > win[0]
    red = TR.reduce(ops, spans, win, ["fleet.solve", "fleet.gradient"])
    assert 0.0 < red.busy_s < red.window_s
    assert red.scope_s["fleet.solve"] > 0.0
    assert red.scope_s["fleet.gradient"] > 0.0
    assert sum(red.scope_s.values()) <= red.busy_s * (1 + 1e-9)
    # the three sleeps are the longest gaps, and are named as such
    names = [n for n, _ in red.gaps[:3]]
    assert names == ["bench.host"] * 3
    assert all(s > 2.5e-3 for _, s in red.gaps[:3])
