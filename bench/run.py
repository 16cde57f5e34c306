"""Run one benchmark cell on the accelerator and print its result line.

  python bench/run.py --workload <config>.<mix> --seed <n> --seconds <s> \
      --trace <0|1>

A cell is an entry of ``BENCHMARK.json``'s ``workloads``.  Its configuration
is ``bench/configs/<config>.json``, its traffic mix
``bench/traffic/<mix>.json`` (which names a driver in ``bench/drivers/``),
the limits of its correctness
check ``bench/limits/<cell>.json``, and each per-layer metric a reader
description ``bench/metrics/<metric>.json`` or a reader of its own,
``bench/metrics/<metric>.py``.  A model family (the configuration's
``model_type``) is its program mapping ``bench/arch/<model_type>.py`` and
its plain reference ``bench/reference/<model_type>.py`` (``families.py``).
Adding any of them takes new files only.

One run is one process:

1. refuse anything but a TPU with as many chips as the cell asks for;
2. set-up (timed as ``setup_s`` from process start): the driver builds the
   system, makes the weights on the device from ``--seed``, and warms up the
   cell's own shapes from the persistent compile cache (fixed at
   ``<checkout>/.jax_cache`` unless ``JAX_COMPILATION_CACHE_DIR`` is set);
3. the window: ``--seconds`` of timed calls, ended by ``block_until_ready``;
   with ``--trace 1`` its last ``TRACE_SECONDS`` run under the profiler
   (after ``SETTLE_SECONDS`` of calls that wait out the profiler's start)
   and the per-layer metrics are read from that trace;
4. ``memory_peak_bytes`` is read, the system's state freed, and the plain
   reference checks what the timed path produced;
5. the last lines of standard error give each number compared beside its
   limit, and the last line of standard output is one JSON object.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

TRACE_SECONDS = 5.0
SETTLE_SECONDS = 1.0
TRACE_DIR = ROOT / ".bench_trace"
CACHE_DIR = ROOT / ".jax_cache"

from families import load_module  # noqa: E402


class NoChip(RuntimeError):
    pass


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


class Cell:
    """A workload of ``BENCHMARK.json`` assembled from its files by name."""

    def __init__(self, name: str, bench_dir: pathlib.Path = BENCH,
                 benchmark: dict | None = None):
        self.dir = bench_dir
        if benchmark is None:
            benchmark = load_json(bench_dir.parent / "BENCHMARK.json")
        entry = next((w for w in benchmark["workloads"] if w["name"] == name),
                     None)
        if entry is None:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        self.name, self.chips = name, entry["chips"]
        self.config = load_json(bench_dir / "configs"
                                / f"{entry['config']}.json")
        self.traffic = load_json(bench_dir / "traffic"
                                 / f"{entry['traffic']}.json")
        self.limits = load_json(bench_dir / "limits" / f"{name}.json")
        self.driver_module = load_module(
            bench_dir / "drivers" / f"{self.traffic['driver']}.py")
        self.end_to_end = [m for m in benchmark["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in benchmark["per_layer"]
                          if name in m.get("workloads", [name])]

    def driver(self, seed: int):
        return self.driver_module.Driver(self.config, self.traffic, seed)

    def reader(self, metric: dict) -> dict:
        module = self.dir / "metrics" / f"{metric['name']}.py"
        if module.exists():
            mod = load_module(module)
            return {"kind": "python", "read": mod.read,
                    "scope": getattr(mod, "SCOPE", None)}
        return load_json(self.dir / "metrics" / f"{metric['name']}.json")


def check_devices(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU found (JAX platform is {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def enable_compile_cache() -> None:
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # the key then holds each op's scope name (op_name): a program cached
    # before a scope was renamed is compiled again, not served with the
    # old names, which the per-layer scope metrics would not find
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)


def window(driver, seconds: float, annotate) -> tuple[int, float, float]:
    """Timed calls for ``seconds``; returns (units, t0, t1), host clock."""
    units = 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        with annotate("bench.call"):
            units += driver.step()
    with annotate("bench.sync"):
        driver.sync()
    return units, t0, time.perf_counter()


def peak_bytes(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def layer_metrics(cell: Cell, red, context: dict) -> dict:
    from yardstick import readers
    out = {}
    for m in cell.per_layer:
        value = readers.read(cell.reader(m), red, context)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             devices=None, stand_in=None) -> dict:
    """Set-up, window and check of one run; returns the result object."""
    import jax
    from yardstick import trace as TR
    driver = cell.driver(seed)
    driver.stand_in = stand_in
    driver.setup()
    setup_s = time.perf_counter() - T_START
    annotate = jax.profiler.TraceAnnotation

    if trace:
        window(driver, max(seconds - TRACE_SECONDS - SETTLE_SECONDS, 0.0),
               annotate)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR))
        try:
            # the profiler's start holds up the first calls under it: they
            # run before the window that is read
            window(driver, SETTLE_SECONDS, annotate)
            with annotate("bench.window"):
                units, t0, t1 = window(driver, min(seconds, TRACE_SECONDS),
                                       annotate)
        finally:
            jax.profiler.stop_trace()
    else:
        units, t0, t1 = window(driver, seconds, annotate)

    devices = devices or jax.devices()[:cell.chips]
    memory = peak_bytes(devices)
    failed = driver.failed()
    # the compiled programs' text names the scope of every traced op
    scope_maps = dict(TR.scope_map(t) for t in driver.programs()) \
        if trace else {}
    driver.release()
    ref = driver.reference()
    readings = driver.readings(ref)

    checks = {k: [readings[k], cell.limits[k]] for k in cell.limits}
    # a reading that is not a number (NaN) fails its limit
    correct = all(v <= lim for v, lim in checks.values())
    dev = devices[0]
    result = {"correct": bool(correct), "attempted": units, "failed": failed,
              "metrics": {}, "device": {
                  "platform": dev.platform, "kind": dev.device_kind,
                  "count": len(devices), "memory_peak_bytes": memory}}
    if trace:
        ops, spans = TR.read(TR.find_xplane(TRACE_DIR), scope_maps)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        win = TR.window_of(spans, "bench.window")
        scopes = [cell.reader(m).get("scope") for m in cell.per_layer]
        red = TR.reduce(ops, spans, win, [s for s in scopes if s])
        context = {"host_window_s": t1 - t0, "device_kind": dev.device_kind,
                   "work": driver.work(units)}
        result["metrics"] = layer_metrics(cell, red, context)
        result["device"].update(busy_s=red.busy_s, window_s=red.window_s)
        result["breakdown"] = TR.breakdown(red)
    else:
        named = {m["name"] for m in cell.end_to_end}
        values = {"setup_s": (setup_s, "s"),
                  **driver.end_to_end(units, t1 - t0)}
        result["metrics"] = {k: {"value": v, "unit": u}
                             for k, (v, u) in values.items() if k in named}
    result["checks"] = {k: {"value": v if math.isfinite(v) else str(v),
                            "limit": lim} for k, (v, lim) in checks.items()}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = Cell(args.workload)
    try:
        devices = check_devices(cell.chips)
    except NoChip as e:
        print(f"bench: {e}; nothing was run", file=sys.stderr)
        return 2
    enable_compile_cache()
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices)
    for k, c in result["checks"].items():
        print(f"check {k} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
