"""The harness on the CPU: cells assembled from their files by name, a new
mix or metric added as files only, and no result without a TPU."""

import json
import os
import pathlib
import re
import subprocess
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH / "tests"))

import run  # noqa: E402
import tiny  # noqa: E402
from yardstick import readers  # noqa: E402
from yardstick import trace as TR  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = str(ROOT / "src")
    return env


def test_benchmark_json_names_and_bounds():
    assert set(BENCHMARK) == {"command", "paths", "run_seconds", "configs",
                              "workloads", "end_to_end", "per_layer"}
    e2e = {m["name"] for m in BENCHMARK["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCHMARK["end_to_end"]:
        assert NAME.match(m["name"]) and 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCHMARK["per_layer"]:
        assert NAME.match(m["name"]) and m["moves"] in e2e
        assert (BENCH / "metrics" / f"{m['name']}.json").exists()
    for c in BENCHMARK["configs"]:
        assert (ROOT / c["file"]).exists()


@pytest.mark.parametrize("name", [w["name"] for w in BENCHMARK["workloads"]])
def test_cell_assembled_by_name(name):
    cell = run.Cell(name)
    config, mix = name.split(".")
    assert cell.config["name"] == config
    assert (BENCH / "traffic" / f"{mix}.json").exists()
    assert cell.driver_module.Driver.__name__ == "Driver"
    assert "setup_s" in [m["name"] for m in cell.end_to_end]
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.per_layer:
        assert cell.reader(m)["kind"] in readers.KINDS
        # the end-to-end metric a per-layer metric moves is in the cell
        assert m["moves"] in [e["name"] for e in cell.end_to_end]
    assert cell.limits and set(cell.limits) <= set(cell.driver_module.NUMBERS)


def test_new_mix_is_a_new_cell(tmp_path):
    """A mix written as a new JSON file that names an existing driver runs
    as a cell with no code edited."""
    bench, benchmark = tiny.make_bench(tmp_path)
    mix = json.loads((bench / "traffic" / "tiny-cohort.json").read_text())
    mix["fleet"]["schedule"]["participants_per_cell"] = 10
    mix["fleet"]["lr"] = 0.05
    (bench / "traffic" / "tiny-wide.json").write_text(json.dumps(mix))
    (bench / "limits" / "tiny-fleet.tiny-wide.json").write_text(
        (bench / "limits" / "tiny-fleet.tiny-cohort.json").read_text())
    benchmark["workloads"].append(
        {"name": "tiny-fleet.tiny-wide", "config": "tiny-fleet",
         "traffic": "tiny-wide", "chips": 1, "why": "a mix of data only"})
    for m in benchmark["end_to_end"] + benchmark["per_layer"]:
        if "tiny-fleet.tiny-cohort" in m.get("workloads", []):
            m["workloads"].append("tiny-fleet.tiny-wide")
    cell = run.Cell("tiny-fleet.tiny-wide", bench, benchmark)
    import jax
    result = run.run_cell(cell, 2**31 + 7, 0.5, False, jax.devices()[:1])
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["metrics"]["fleet_rounds_per_s"]["value"] > 0
    assert set(result["metrics"]) == {"setup_s", "fleet_rounds_per_s"}
    assert list(result)[-1] == "checks"


def test_new_metric_file_of_an_existing_kind(tmp_path):
    """A per-layer metric added as one JSON file is read with no code
    edited; a reader with nothing to read leaves the metric out."""
    bench, benchmark = tiny.make_bench(tmp_path)
    (bench / "metrics" / "fleet_phase_ms.cloud_merge.json").write_text(
        json.dumps({"kind": "scope_ms_per_unit",
                    "scope": "fleet.cloud_merge"}))
    benchmark["per_layer"].append(
        {"name": "fleet_phase_ms.cloud_merge", "unit": "ms", "better": "lower",
         "source": "device_trace", "layer": "merge and eval",
         "moves": "fleet_rounds_per_s"})
    cell = run.Cell("tiny-fleet.tiny-cohort", bench, benchmark)
    ops = [TR.Op(0.0, 0.5, "fusion.1", "jit(f)/fleet.cloud_merge/add"),
           TR.Op(0.5, 1.0, "fusion.2", "jit(f)/fleet.solve/mul")]
    red = TR.reduce(ops, [], (0.0, 2.0),
                    ["fleet.cloud_merge", "fleet.solve", "fleet.merge"])
    ctx = {"host_window_s": 2.0, "device_kind": "TPU v5 lite",
           "work": {"units": 2, "flops": 2e9}}
    got = run.layer_metrics(cell, red, ctx)
    assert got["fleet_phase_ms.cloud_merge"]["value"] == pytest.approx(250.0)
    assert got["fleet_phase_ms.solve"]["value"] == pytest.approx(250.0)
    assert "fleet_phase_ms.merge" not in got          # nothing to read
    assert got["idle_share.fleet"]["value"] == pytest.approx(50.0)
    assert got["train_mfu"]["value"] == pytest.approx(
        100 * 1e9 / 197e12)


def test_new_metric_as_a_reader_of_its_own(tmp_path):
    """A per-layer metric of a kind no file has yet comes as one module,
    found by its name, with no code edited."""
    bench, benchmark = tiny.make_bench(tmp_path)
    (bench / "metrics" / "solve_share.py").write_text(
        "SCOPE = 'fleet.solve'\n"
        "def read(red, ctx):\n"
        "    s = red.scope_s.get(SCOPE, 0.0)\n"
        "    return 100.0 * s / red.busy_s if s > 0 else None\n")
    benchmark["per_layer"].append(
        {"name": "solve_share", "unit": "%", "better": "lower",
         "source": "device_trace", "layer": "fleet control",
         "moves": "fleet_rounds_per_s"})
    cell = run.Cell("tiny-fleet.tiny-cohort", bench, benchmark)
    assert cell.reader(benchmark["per_layer"][-1])["scope"] == "fleet.solve"
    ops = [TR.Op(0.0, 0.5, "fusion.1", "jit(f)/fleet.solve/mul"),
           TR.Op(0.5, 2.0, "fusion.2", "jit(f)/fleet.gradient/add")]
    red = TR.reduce(ops, [], (0.0, 2.0), ["fleet.solve", "fleet.gradient"])
    ctx = {"host_window_s": 2.0, "device_kind": "TPU v5 lite",
           "work": {"units": 2, "flops": 2e9}}
    got = run.layer_metrics(cell, red, ctx)
    assert got["solve_share"]["value"] == pytest.approx(25.0)
    red = TR.reduce(ops[1:], [], (0.0, 2.0), ["fleet.solve"])
    assert "solve_share" not in run.layer_metrics(cell, red, ctx)


def test_refuses_without_tpu():
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         BENCHMARK["workloads"][0]["name"], "--seed", "1", "--seconds", "1"],
        cwd=ROOT, env=_env(), capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_compile_cache_key_holds_scope_names():
    import jax
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes",
             "jax_compilation_cache_include_metadata_in_key")
    saved = {n: getattr(jax.config, n) for n in names}
    try:
        run.enable_compile_cache()
        assert jax.config.jax_compilation_cache_include_metadata_in_key
    finally:
        for n, v in saved.items():
            jax.config.update(n, v)
