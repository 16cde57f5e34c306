"""Fleet engine throughput: rounds/sec vs client count, sync vs async,
reference vs fused client-gradient kernels.

Measures the scan-compiled round loop end-to-end (channel sample ->
closed-form solver -> masked-gradient FedSGD -> packet-error aggregation
-> tracking) with compile time reported separately, sweeping the fleet
from the paper's 5 UEs up to 100k clients.  The solver runs *inside* the
scan — zero per-round host work — so rounds/sec is the compiled-program
number the ROADMAP north star cares about.

``--kernel`` picks the client-gradient hot path (``FleetConfig.kernel``):
``reference`` is the PR-2 vmap + AD batch, ``fused`` streams client tiles
through ``kernels/fleet_fused.py``; ``both`` runs the two arms on
identical configs/draws and prints the speedup.

``--compare`` benchmarks the synchronous barrier against FedBuff-style
buffered aggregation on a straggler-heavy fleet: same client count, same
seed, reporting both engine throughput (rounds/s or events/s of host time)
and *simulated* wall-clock to a target training loss — the async path's
whole point is buying back the straggler tail on that second axis.
``--compare --buffer 0,1`` adds a FedAsync arm (buffer = 1: every arrival
is its own server event) with the event count scaled so it merges about
as many client updates as the default buffered arm — the ROADMAP's
FedAsync latency study.

``--json`` additionally writes ``BENCH_fleet.json`` — the machine-readable
perf trajectory (every arm's rounds/sec plus fused-over-reference
speedups), so regressions are diffable from this PR onward.

  PYTHONPATH=src python -m benchmarks.fleet_bench            # default sweep
  PYTHONPATH=src python -m benchmarks.fleet_bench --clients 5,1000,100000 \
      --kernel both --json
  PYTHONPATH=src python -m benchmarks.fleet_bench --compare  # sync vs async
  PYTHONPATH=src python -m benchmarks.fleet_bench --smoke --json   # CI-sized

Writes ``fleet_bench.csv`` (sweep) / ``fleet_async_bench.csv`` (compare)
via the shared benchmark plumbing, and ``BENCH_fleet.json`` with --json.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import platform
import time

import jax
import numpy as np

from benchmarks import common
from repro.fleet import (AsyncConfig, FleetConfig, FleetTopology,
                         ScheduleConfig, SpanRecorder, TelemetryConfig)
from repro.fleet.engine import build_simulation, time_to_loss
from repro.fleet.topology import GEOMETRIES, make_geometry

JSON_NAME = "BENCH_fleet.json"
TOPOLOGY_JSON_NAME = "BENCH_fleet_topology.json"


def _fleet_shape(clients: int) -> tuple[int, int]:
    """Factor a client count into (cells, clients_per_cell), near-square
    but capping cell size at 256 so the per-cell solver stays cache-sized."""
    if clients <= 8:
        return 1, clients
    per_cell = min(256, int(math.sqrt(clients)))
    while clients % per_cell:
        per_cell -= 1
    return clients // per_cell, per_cell


def _span(recorder: SpanRecorder | None, name: str, **args):
    """A recorder span, or a no-op when tracing is off (no --trace)."""
    if recorder is None:
        return contextlib.nullcontext()
    return recorder.span(name, **args)


def _time_simulation(sim, repeats: int,
                     recorder: SpanRecorder | None = None
                     ) -> tuple[float, float, tuple]:
    """(compile seconds, best-of-``repeats`` warm seconds, last scan
    output — for ``finalize``)."""
    with _span(recorder, "compile+run"):
        t0 = time.perf_counter()
        out = sim.simulate(sim.params, sim.round_keys)   # compile + run
        jax.block_until_ready(out)
        cold = time.perf_counter() - t0
    warm = math.inf
    for _ in range(max(repeats, 1)):
        with _span(recorder, "warm_run"):
            t0 = time.perf_counter()
            out = sim.simulate(sim.params, sim.round_keys)
            jax.block_until_ready(out)
            warm = min(warm, time.perf_counter() - t0)
    return cold - warm, warm, out


def bench_one(clients: int, rounds: int, kernel: str = "reference",
              seed: int = 0, repeats: int = 2, telemetry: bool = False,
              recorder: SpanRecorder | None = None) -> dict:
    cells, per_cell = _fleet_shape(clients)
    cfg = FleetConfig(
        topology=FleetTopology(num_cells=cells, clients_per_cell=per_cell),
        rounds=rounds, seed=seed, kernel=kernel,
        cell_chunk=max(1, min(cells, 4096 // max(per_cell, 1))),
        telemetry=TelemetryConfig() if telemetry else None)

    with _span(recorder, "bench_one", clients=clients, kernel=kernel,
               telemetry=telemetry):
        with _span(recorder, "build"):
            sim = build_simulation(cfg)
        compile_s, warm, out = _time_simulation(sim, repeats,
                                                recorder=recorder)
        with _span(recorder, "finalize"):
            res = sim.finalize(*out)

    assert np.all(np.isfinite(res.losses)), "non-finite losses at scale"
    return {
        "mode": "sync",
        "kernel": kernel,
        "clients": clients,
        "cells": cells,
        "rounds": rounds,
        "telemetry": telemetry,
        "compile_s": compile_s,
        "run_s": warm,
        "rounds_per_s": rounds / warm,
        "client_rounds_per_s": clients * rounds / warm,
        "final_loss": float(res.losses[-1]),
    }


def bench_cohort(clients: int, rounds: int, cohort: bool,
                 participation: float = 0.1, kernel: str = "reference",
                 seed: int = 0, repeats: int = 2,
                 control_chunk: int | None = None,
                 recorder: SpanRecorder | None = None) -> dict:
    """One cohort-compute arm: a partial schedule (``participation`` of
    each cell) with the cohort gather on or off, same seed and draws.

    ``cohort=True`` is the dense (C, m) compute path — gradient batch and
    gathered per-cell solve scale with the scheduled cohort;
    ``cohort=False`` pins the legacy full-fleet masked scan on the
    identical schedule.  The rounds/s ratio of the two arms is the
    cohort-sharding payoff the acceptance gate cares about (>= 3x at 10k
    clients, participation 0.1).  ``control_chunk`` defaults to blocks of
    512 cells once the fleet is larger than that (the Algorithm-1
    working-set bound that keeps the 1M-client control pass in budget).
    """
    cells, per_cell = _fleet_shape(clients)
    m = max(1, int(round(per_cell * participation)))
    if control_chunk is None:
        control_chunk = 512 if cells > 512 else 0
    batch_cols = m if cohort else per_cell
    cfg = FleetConfig(
        topology=FleetTopology(num_cells=cells, clients_per_cell=per_cell),
        schedule=ScheduleConfig(participation="uniform",
                                participants_per_cell=m),
        rounds=rounds, seed=seed, kernel=kernel, cohort_gather=cohort,
        cell_chunk=max(1, min(cells, 4096 // max(batch_cols, 1))),
        control_chunk=control_chunk)

    with _span(recorder, "bench_cohort", clients=clients, cohort=cohort,
               kernel=kernel):
        with _span(recorder, "build"):
            sim = build_simulation(cfg)
        compile_s, warm, out = _time_simulation(sim, repeats,
                                                recorder=recorder)
        with _span(recorder, "finalize"):
            res = sim.finalize(*out)

    assert np.all(np.isfinite(res.losses)), "non-finite losses (cohort)"
    return {
        "mode": "sync",
        "kernel": kernel,
        "clients": clients,
        "cells": cells,
        "rounds": rounds,
        "cohort": bool(cohort),
        "participation": participation,
        "cohort_m": m,
        "control_chunk": control_chunk,
        "compile_s": compile_s,
        "run_s": warm,
        "rounds_per_s": rounds / warm,
        "client_rounds_per_s": clients * rounds / warm,
        "cohort_client_rounds_per_s": cells * m * rounds / warm,
        "final_loss": float(res.losses[-1]),
    }


# above this, the full-fleet masked-scan arm is skipped: a 1M-client
# dense scan on one host exists only to be slower than the cohort path,
# and the equivalence suite already pins the two paths' trajectories
_MAX_FLEET_SCAN_CLIENTS = 100_000


def run_cohort(counts: list[int], rounds: int, kernel: str,
               participation: float, repeats: int,
               recorder: SpanRecorder | None = None) -> list[dict]:
    """The --cohort table: cohort-gather vs full-fleet scan on the same
    partial schedule, plus cohort-only points past the scan ceiling."""
    header = ["mode", "kernel", "clients", "cells", "rounds", "cohort",
              "participation", "cohort_m", "control_chunk", "compile_s",
              "run_s", "rounds_per_s", "client_rounds_per_s",
              "cohort_client_rounds_per_s", "final_loss"]
    rows, records = [], []
    for clients in counts:
        arms = {}
        variants = ([False, True] if clients <= _MAX_FLEET_SCAN_CLIENTS
                    else [True])
        for cohort in variants:
            r = bench_cohort(clients, rounds, cohort, kernel=kernel,
                             participation=participation, repeats=repeats,
                             recorder=recorder)
            arms[cohort] = r
            records.append(r)
            rows.append([r[h] for h in header])
            tag = "cohort" if cohort else "fleet-scan"
            print(f"{tag:>11s} clients={clients:>8d} cells={r['cells']:>5d} "
                  f"m={r['cohort_m']:>4d} compile={r['compile_s']:6.1f}s "
                  f"run={r['run_s']:8.2f}s {r['rounds_per_s']:8.2f} rounds/s")
        if False in arms and True in arms:
            ratio = (arms[True]["rounds_per_s"]
                     / arms[False]["rounds_per_s"])
            print(f"      cohort/fleet-scan @ {clients} clients "
                  f"(participation {participation}): {ratio:.2f}x")
    path = common.write_csv("fleet_cohort_bench.csv", header, rows)
    print(f"wrote {path}")
    return records


def bench_telemetry_overhead(clients: int, rounds: int, seed: int = 0,
                             repeats: int = 2,
                             recorder: SpanRecorder | None = None) -> dict:
    """rounds/s with ``FleetConfig.telemetry`` off vs on (default
    ``TelemetryConfig()``), same shape and seed — the observability tax.
    The stanza rides ``BENCH_fleet.json`` so the regression check can pin
    it (the acceptance target is <= 10% at the 1024-client shape).

    The two arms are timed *interleaved* (off, on, off, on, ...) with the
    per-arm best kept: back-to-back sequential timing lets machine-level
    throughput drift between the windows masquerade as overhead, which at
    this shape (~10ms/round) is larger than the effect being measured."""
    repeats = max(repeats, 5)
    cells, per_cell = _fleet_shape(clients)
    base_kw = dict(
        topology=FleetTopology(num_cells=cells, clients_per_cell=per_cell),
        rounds=rounds, seed=seed,
        cell_chunk=max(1, min(cells, 4096 // max(per_cell, 1))))
    sims = [build_simulation(FleetConfig(**base_kw, telemetry=tel))
            for tel in (None, TelemetryConfig())]
    best = [math.inf, math.inf]
    with _span(recorder, "telemetry_overhead", clients=clients):
        for sim in sims:                                 # compile both
            jax.block_until_ready(sim.simulate(sim.params, sim.round_keys))
        for _ in range(repeats):
            for i, sim in enumerate(sims):
                t0 = time.perf_counter()
                jax.block_until_ready(
                    sim.simulate(sim.params, sim.round_keys))
                best[i] = min(best[i], time.perf_counter() - t0)
    off, on = rounds / best[0], rounds / best[1]
    return {
        "clients": clients,
        "rounds": rounds,
        "rounds_per_s_off": off,
        "rounds_per_s_on": on,
        "overhead_frac": 1.0 - on / off,
    }


def bench_mode(clients: int, rounds: int, mode: str, seed: int = 0,
               kernel: str = "reference", buffer_frac: float = 0.25,
               target_loss: float = 1.8, deadline_s: float = 8.0,
               repeats: int = 2, buffer_size: int | None = None,
               events: int | None = None,
               recorder: SpanRecorder | None = None) -> dict:
    """Time one engine mode on a straggler-heavy fleet (wide CPU + distance
    spread, so the sync barrier pays a long latency tail every round).

    Both arms run time-triggered (same round deadline, same solver cap):
    without it one deeply-faded client would stall the unbounded sync
    barrier forever, which is the failure mode — not a benchmark.  Sync
    drops late clients at the barrier; async never waits on them (staleness
    weighting retires their updates instead).

    ``buffer_size`` overrides the frac-derived async buffer (1 = FedAsync:
    every arrival is its own server event); ``events`` overrides the async
    event count so small-buffer arms can merge a comparable number of
    client updates.
    """
    from repro.fleet import ScheduleConfig

    cells, per_cell = _fleet_shape(clients)
    n = cells * per_cell
    if mode == "async":
        buffer = buffer_size if buffer_size else max(1, int(n * buffer_frac))
    else:
        buffer = 0
    steps = events if (mode == "async" and events) else rounds
    cfg = FleetConfig(
        topology=FleetTopology(num_cells=cells, clients_per_cell=per_cell,
                               cpu_hz_range=(2e8, 8e9), max_dist_m=1500.0),
        schedule=ScheduleConfig(round_deadline_s=deadline_s),
        async_config=AsyncConfig(buffer_size=buffer, max_staleness=20),
        rounds=steps, seed=seed, kernel=kernel,
        cell_chunk=max(1, min(cells, 4096 // max(per_cell, 1))))

    with _span(recorder, "bench_mode", clients=clients, mode=mode,
               kernel=kernel):
        with _span(recorder, "build"):
            sim = build_simulation(cfg, mode=mode)
        compile_s, warm, out = _time_simulation(sim, repeats,
                                                recorder=recorder)
        with _span(recorder, "finalize"):
            res = sim.finalize(*out)

    assert np.all(np.isfinite(res.losses)), f"non-finite losses ({mode})"
    return {
        "mode": mode,
        "kernel": kernel,
        "clients": clients,
        "rounds": steps,
        "buffer": buffer,
        "compile_s": compile_s,
        "run_s": warm,
        "rounds_per_s": steps / warm,
        "sim_wall_s": float(res.wall_clock[-1]),
        "sim_s_to_loss": time_to_loss(res, target_loss),
        "final_loss": float(res.losses[-1]),
        "mean_staleness": float(np.mean(res.staleness)),
    }


def _speedups(records: list[dict]) -> list[dict]:
    """fused-over-reference rounds/sec ratio per (mode, clients)."""
    by_key = {}
    for r in records:
        if r.get("cohort") is not None:
            continue  # cohort arms run one kernel on a partial schedule —
            # pairing them with the full-participation sweep would corrupt
            # the fused/reference ratio at the same client count
        by_key.setdefault((r["mode"], r["clients"]), {})[r["kernel"]] = r
    out = []
    for (mode, clients), arms in sorted(by_key.items()):
        if "reference" in arms and "fused" in arms:
            out.append({
                "mode": mode,
                "clients": clients,
                "speedup": arms["fused"]["rounds_per_s"]
                / arms["reference"]["rounds_per_s"],
            })
    return out


def env_metadata() -> dict:
    """The environment stamp of a bench artifact: enough to tell hardware
    / toolchain drift from code drift when two BENCH JSONs disagree."""
    devices = jax.devices()
    return {
        "jax_version": jax.__version__,
        "backend": jax.default_backend(),
        "platform": platform.platform(),
        "python": platform.python_version(),
        "device_count": len(devices),
        "device_kind": devices[0].device_kind if devices else "none",
        "x64": bool(jax.config.jax_enable_x64),
        "cpu_count": os.cpu_count(),
    }


# mirror of check_regression.ARM_KEYS: what identifies "the same arm"
# (batch/rho/impl are serve_bench keys — always None on fleet records)
_ARM_KEYS = ("mode", "kernel", "clients", "buffer", "cohort",
             "batch", "rho", "impl")


def write_json(records: list[dict], path: str | None = None,
               extra: dict | None = None, merge: bool = False) -> str:
    os.makedirs(common.RESULTS_DIR, exist_ok=True)
    path = path or os.path.join(common.RESULTS_DIR, JSON_NAME)
    if merge and os.path.exists(path):
        # fold the fresh arms into the existing document: same-arm records
        # are replaced, everything else is preserved (the committed bench
        # trajectory grows, it doesn't reset)
        with open(path) as f:
            old = json.load(f)
        fresh = {tuple(r.get(k) for k in _ARM_KEYS) for r in records}
        kept = [r for r in old.get("results", [])
                if tuple(r.get(k) for k in _ARM_KEYS) not in fresh]
        records = kept + records
        if extra is None and "telemetry_overhead" in old:
            extra = {"telemetry_overhead": old["telemetry_overhead"]}
    doc = {
        "schema": "fleet_bench/v1",
        "created_unix": time.time(),
        "backend": jax.default_backend(),
        "cpu_count": os.cpu_count(),
        "env": env_metadata(),
        "results": records,
        "speedups": _speedups(records),
    }
    if extra:
        doc.update(extra)
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
    return path


def bench_geometry(clients: int, rounds: int, geometry: str, reuse: int,
                   target_loss: float = 1.9, seed: int = 0,
                   repeats: int = 2) -> dict:
    """Time one cell-geometry arm: the orthogonal baseline or hex cells at
    a given frequency-reuse factor (smaller reuse = more co-channel
    interference = more fixed-point work per round *and* worse PER, so
    both rounds/s and simulated time-to-loss move)."""
    cells, per_cell = _fleet_shape(clients)
    geo = None if geometry == "orthogonal" else make_geometry(geometry,
                                                              reuse=reuse)
    cfg = FleetConfig(
        topology=FleetTopology(num_cells=cells, clients_per_cell=per_cell),
        geometry=geo, rounds=rounds, seed=seed,
        cell_chunk=max(1, min(cells, 4096 // max(per_cell, 1))))

    sim = build_simulation(cfg)
    compile_s, warm, out = _time_simulation(sim, repeats)
    res = sim.finalize(*out)

    assert np.all(np.isfinite(res.losses)), f"non-finite losses ({geometry})"
    return {
        "geometry": geometry,
        "reuse": reuse if geometry == "hex" else 0,
        "clients": clients,
        "cells": cells,
        "rounds": rounds,
        "compile_s": compile_s,
        "run_s": warm,
        "rounds_per_s": rounds / warm,
        "sim_s_to_loss": time_to_loss(res, target_loss),
        "mean_per": float(np.mean(res.mean_per)),
        "mean_prune": float(np.mean(res.mean_prune)),
        "final_loss": float(res.losses[-1]),
    }


def run_geometry(clients: int, rounds: int, geometries: list[str],
                 reuse_factors: list[int], target_loss: float,
                 repeats: int) -> list[dict]:
    """The --geometry table: rounds/s + simulated time-to-loss vs reuse
    factor, orthogonal cells as the uncoupled baseline.  Writes
    ``fleet_topology_bench.csv`` + ``BENCH_fleet_topology.json``."""
    header = ["geometry", "reuse", "clients", "cells", "rounds", "compile_s",
              "run_s", "rounds_per_s", "sim_s_to_loss", "mean_per",
              "mean_prune", "final_loss"]
    rows, records = [], []
    for geometry in geometries:
        if geometry not in GEOMETRIES:
            raise ValueError(
                f"unknown geometry {geometry!r}; one of {sorted(GEOMETRIES)}")
        sweeps = reuse_factors if geometry == "hex" else [0]
        for reuse in sweeps:
            r = bench_geometry(clients, rounds, geometry, reuse,
                               target_loss=target_loss, repeats=repeats)
            records.append(r)
            rows.append([r[h] for h in header])
            tag = f"hex reuse={reuse}" if geometry == "hex" else "orthogonal"
            print(f"{tag:>14s} clients={r['clients']:>7d} "
                  f"compile={r['compile_s']:6.1f}s run={r['run_s']:7.2f}s "
                  f"{r['rounds_per_s']:8.2f} rounds/s "
                  f"per={r['mean_per']:.4f} "
                  f"to_loss<{target_loss}: {r['sim_s_to_loss']:8.1f}s")
    path = common.write_csv("fleet_topology_bench.csv", header, rows)
    print(f"wrote {path}")
    os.makedirs(common.RESULTS_DIR, exist_ok=True)
    jpath = os.path.join(common.RESULTS_DIR, TOPOLOGY_JSON_NAME)
    with open(jpath, "w") as f:
        json.dump({
            "schema": "fleet_topology_bench/v1",
            "created_unix": time.time(),
            "backend": jax.default_backend(),
            "cpu_count": os.cpu_count(),
            "target_loss": target_loss,
            "results": records,
        }, f, indent=1)
    print(f"wrote {jpath}")
    return records


_MAX_COMPARE_EVENTS = 4000


def run_compare(counts: list[int], rounds: int, target_loss: float,
                kernels: list[str], repeats: int,
                buffers: list[int] | None = None,
                buffer_frac: float = 0.25) -> list[dict]:
    """Sync-vs-async table: host throughput + simulated time-to-target.

    ``buffers`` lists the async buffer sizes to benchmark against the one
    sync arm; 0 means the frac-derived default (buffer = 0.25 n).  Small
    explicit buffers (1 = FedAsync) get their event count scaled up so
    every async arm merges about the same number of client updates as the
    default arm — otherwise a buffer-1 run of ``rounds`` events would
    train on ``rounds`` updates total and the latency comparison would be
    meaningless.  Events are capped at ``_MAX_COMPARE_EVENTS`` (4000);
    the cap is printed when it binds, and a capped arm merges fewer
    updates than the default arm (compare its row accordingly).
    """
    header = ["mode", "kernel", "clients", "rounds", "buffer", "compile_s",
              "run_s", "rounds_per_s", "sim_wall_s", "sim_s_to_loss",
              "final_loss", "mean_staleness"]
    buffers = buffers or [0]
    rows, records = [], []

    def emit(r):
        records.append(r)
        rows.append([r[h] for h in header])
        print(f"{r['mode']:>5s} {r['kernel']:>9s} "
              f"clients={r['clients']:>7d} buf={r['buffer']:>6d} "
              f"compile={r['compile_s']:6.1f}s run={r['run_s']:7.2f}s "
              f"{r['rounds_per_s']:8.2f} rounds/s "
              f"sim_wall={r['sim_wall_s']:8.1f}s "
              f"to_loss<{target_loss}: {r['sim_s_to_loss']:8.1f}s "
              f"stale={r['mean_staleness']:4.1f}")

    for clients in counts:
        cells, per_cell = _fleet_shape(clients)
        n = cells * per_cell
        buf_default = max(1, int(n * buffer_frac))
        for kernel in kernels:
            sync = bench_mode(clients, rounds, "sync", kernel=kernel,
                              target_loss=target_loss, repeats=repeats)
            emit(sync)
            for b in buffers:
                buf = buf_default if b == 0 else b
                events = max(1, round(rounds * buf_default / buf))
                if events > _MAX_COMPARE_EVENTS:
                    print(f"      buffer={buf}: capping events "
                          f"{events} -> {_MAX_COMPARE_EVENTS}")
                    events = _MAX_COMPARE_EVENTS
                r = bench_mode(clients, rounds, "async", kernel=kernel,
                               target_loss=target_loss, repeats=repeats,
                               buffer_size=buf, events=events)
                emit(r)
                s, a = sync["sim_s_to_loss"], r["sim_s_to_loss"]
                if np.isfinite(s) and np.isfinite(a) and a > 0 and s > 0:
                    word = "sooner" if s >= a else "LATER"
                    ratio = s / a if s >= a else a / s
                    print(f"      clients={clients:>7d} async(buf={buf}) "
                          f"reaches loss<{target_loss} {ratio:.2f}x {word} "
                          f"(simulated)")
    path = common.write_csv("fleet_async_bench.csv", header, rows)
    print(f"wrote {path}")
    return records


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--clients", default="5,100,1000,10000",
                    help="comma-separated client counts (try up to 100000)")
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--kernel", default=None,
                    choices=["reference", "fused", "both"],
                    help="client-gradient hot path (default: reference; "
                         "--json defaults to both)")
    ap.add_argument("--compare", action="store_true",
                    help="sync vs async buffered aggregation comparison")
    ap.add_argument("--cohort", action="store_true",
                    help="cohort-gather vs full-fleet masked scan on a "
                         "partial schedule (default 10000 clients; counts "
                         f"above {_MAX_FLEET_SCAN_CLIENTS} run the cohort "
                         "arm only); --json merges the arms into "
                         f"{JSON_NAME} instead of overwriting it")
    ap.add_argument("--participation", type=float, default=0.1,
                    help="--cohort: scheduled fraction of each cell")
    ap.add_argument("--geometry", default=None, metavar="GEOMS",
                    help="comma-separated cell geometries to benchmark "
                         "(e.g. 'orthogonal,hex'): rounds/s + simulated "
                         f"time-to-loss vs reuse factor, written to "
                         f"{TOPOLOGY_JSON_NAME}")
    ap.add_argument("--reuse", default="1,3,7",
                    help="--geometry: comma-separated hex reuse factors")
    ap.add_argument("--buffer", default="0",
                    help="--compare: comma-separated async buffer sizes "
                         "(0 = the 0.25n default; 1 = FedAsync — every "
                         "arrival is its own server event, with the event "
                         "count scaled to match total merged updates)")
    ap.add_argument("--target-loss", type=float, default=1.8,
                    help="--compare: simulated-time-to-loss threshold")
    ap.add_argument("--json", nargs="?", const="", default=None,
                    metavar="PATH",
                    help=f"write {JSON_NAME} (default under "
                         "benchmarks/results/)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record build/compile/run wall-clock spans and "
                         "write them as Chrome-trace JSON "
                         "(chrome://tracing / Perfetto)")
    ap.add_argument("--repeats", type=int, default=2,
                    help="warm runs per point; best is reported")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run: 2 tiny fleets, 3 rounds")
    args = ap.parse_args()

    emit_json = args.json is not None
    json_path = args.json or None
    recorder = SpanRecorder() if args.trace else None
    kernel = args.kernel or ("both" if emit_json else "reference")
    kernels = ["reference", "fused"] if kernel == "both" else [kernel]

    if args.geometry:
        if args.smoke:
            clients, rounds = 24, 3
        else:
            clients = (1024 if args.clients == "5,100,1000,10000"
                       else int(args.clients.split(",")[0]))
            rounds = args.rounds
        run_geometry(clients, rounds, args.geometry.split(","),
                     [int(r) for r in args.reuse.split(",")],
                     args.target_loss, args.repeats)
        if recorder is not None:
            print(f"wrote {recorder.write(args.trace)}")
        return

    if args.cohort:
        if args.smoke:
            counts, rounds = [256], 3
        else:
            counts = ([10000] if args.clients == "5,100,1000,10000"
                      else [int(c) for c in args.clients.split(",")])
            rounds = args.rounds
        records = run_cohort(counts, rounds, kernels[0], args.participation,
                             args.repeats, recorder=recorder)
        if emit_json:
            print(f"wrote {write_json(records, json_path, merge=True)}")
        if recorder is not None:
            print(f"wrote {recorder.write(args.trace)}")
        return

    if args.compare:
        if args.smoke:
            counts, rounds = [64], 5
        else:
            counts = ([10000] if args.clients == "5,100,1000,10000"
                      else [int(c) for c in args.clients.split(",")])
            rounds = 50 if args.rounds == 20 else args.rounds
        buffers = [int(b) for b in args.buffer.split(",")]
        records = run_compare(counts, rounds, args.target_loss, kernels,
                              args.repeats, buffers=buffers)
        if emit_json:
            print(f"wrote {write_json(records, json_path)}")
        if recorder is not None:
            print(f"wrote {recorder.write(args.trace)}")
        return

    if args.smoke:
        counts, rounds = [16, 64], 3
    else:
        counts = [int(c) for c in args.clients.split(",")]
        rounds = args.rounds

    header = ["mode", "kernel", "clients", "cells", "rounds", "compile_s",
              "run_s", "rounds_per_s", "client_rounds_per_s", "final_loss"]
    rows, records = [], []
    for clients in counts:
        for k in kernels:
            r = bench_one(clients, rounds, kernel=k, repeats=args.repeats,
                          recorder=recorder)
            records.append(r)
            rows.append([r[h] for h in header])
            print(f"{k:>9s} clients={clients:>7d} cells={r['cells']:>4d} "
                  f"compile={r['compile_s']:6.1f}s run={r['run_s']:7.2f}s "
                  f"{r['rounds_per_s']:8.2f} rounds/s "
                  f"{r['client_rounds_per_s']:12.0f} client-rounds/s")
    overhead = None
    if emit_json:
        # one async point per kernel so the artifact covers both modes
        async_clients = 64 if args.smoke else min(10000, max(counts))
        async_rounds = 5 if args.smoke else rounds
        for k in kernels:
            r = bench_mode(async_clients, async_rounds, "async", kernel=k,
                           repeats=args.repeats, recorder=recorder)
            records.append(r)
            print(f"{k:>9s} async clients={async_clients:>7d} "
                  f"run={r['run_s']:7.2f}s {r['rounds_per_s']:8.2f} events/s")
        # the observability tax at the acceptance shape (64 under --smoke)
        overhead = bench_telemetry_overhead(
            64 if args.smoke else 1024, 5 if args.smoke else max(rounds, 30),
            repeats=args.repeats, recorder=recorder)
        print(f"telemetry overhead @ {overhead['clients']} clients: "
              f"{overhead['rounds_per_s_off']:.2f} -> "
              f"{overhead['rounds_per_s_on']:.2f} rounds/s "
              f"({100 * overhead['overhead_frac']:+.1f}%)")
    for s in _speedups(records):
        print(f"  fused/reference @ {s['clients']:>7d} clients "
              f"({s['mode']}): {s['speedup']:.2f}x")
    path = common.write_csv("fleet_bench.csv", header, rows)
    print(f"wrote {path}")
    if emit_json:
        print(f"wrote {write_json(records, json_path, extra={'telemetry_overhead': overhead})}")
    if recorder is not None:
        print(f"wrote {recorder.write(args.trace)}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
