"""Fleet telemetry substrate: in-scan summaries, trace spans, sinks.

Three observability layers.  The in-scan summaries are opt-in
(``FleetConfig(telemetry=...)`` is ``None`` by default and the default
path is bit-identical to a build without this module); scopes cost
nothing on the device, and host spans a few microseconds each:

* **In-scan deep telemetry** — ``TelemetryConfig`` selects fixed-size
  per-round summaries that ride the engine's ``lax.scan`` as extra
  metric outputs with zero host round-trips: per-cell static-bin
  histograms of PER / SINR / latency / rho / bandwidth share
  (``histogram``), the async staleness distribution, gradient-norm and
  mask-density drift, and solver diagnostics (Algorithm-1 alternation
  counts, interference fixed-point residual trajectories) surfaced out
  of ``fleet/solver.py``'s ``while_loop``s.  Everything is shape-static
  — bin edges are config constants, so a million-client round emits the
  same few-KB summary as a 5-UE round.

* **Trace spans** — inside the compiled program the engine's phases
  carry ``jax.named_scope`` labels, metadata only (the optimized program
  is the same with or without them); a device profile maps each op to
  its scope through the compiled text's ``op_name``.  Top-level phases
  and the scopes nested in them:

  ============================  ===========================================
  scope                         what runs under it
  ============================  ===========================================
  ``fleet.channel``             fading draw (``CellGeometry.round_channel``)
  ``fleet.schedule``            participation draw / cohort, handover,
                                Eq.-(11) ``surrogate_m``, downlink time,
                                deadline cap
  ``fleet.solve``               Algorithm 1 (gathers, scatters, final PER)
  ``  fleet.solve.vertex``      Prop.-1 vertex walk, cap re-derivation
  ``  fleet.solve.bandwidth``   Eq.-(21) inversion, cap admission
  ``  fleet.solve.cost``        Eq.-(14a) cost, freeze test, state selects
  ``fleet.gradient``            client gradients and the Eq.-(5) sum
  ``  fleet.data``              client batches: regenerated or gathered
  ``  fleet.mask``              masks built and applied (fused:
                                ``kernel_prepare``)
  ``  fleet.backprop``          forward and backward (fused:
                                ``kernel_grads``)
  ``fleet.merge``               the server step
  ``fleet.cloud_merge``         two-tier edge/cloud merge
  ``fleet.eval``                the task's eval metrics
  ============================  ===========================================

  On the host, ``SpanRecorder`` records wall-clock spans (also entered
  as ``jax.profiler.TraceAnnotation``) and counts, exportable as
  Chrome-trace JSON (``chrome://tracing`` / Perfetto).
  ``process_recorder()`` is the process-wide one: ``build_simulation``
  records ``fleet.build`` and its stages into it, and on first use it
  listens to ``jax.monitoring`` for every trace, lowering and compile
  (``jax.trace`` / ``jax.lower`` / ``jax.compile``, with the program's
  name) and every compile-cache hit and write.  ``compile_report()``
  says which program compiled how often and for how long.

* **Sinks** — the tiny ``TelemetrySink`` protocol (``emit(record)`` /
  ``close()``) with in-memory, JSONL and CSV implementations; the
  engine, the 5-UE reference path, the benchmarks and the examples all
  emit per-round records through ``emit_result``.

See ``docs/observability.md`` for semantics and usage.
"""

from __future__ import annotations

import collections
import contextlib
import csv
import dataclasses
import json
import os
import threading
import time
from typing import Any, NamedTuple, Optional, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

PREFIX = "tel_"


# ---------------------------------------------------------------------------
# Configuration
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TelemetryConfig:
    """Static knobs of the in-scan telemetry (hashable: safe to close over).

    Every histogram uses ``bins`` fixed equal-width bins over a static
    ``*_range``; values outside the range clip into the edge bins, so
    each histogram's total mass is exactly the number of counted clients
    (the smoke-testable invariant).  Ranges are physical:

    * ``per_range`` / ``rho_range`` / ``bw_share_range`` — probabilities
      and fractions in [0, 1].
    * ``sinr_db_range`` — per-client uplink SINR in dB (clients with no
      allocation clip into the top bin: zero-bandwidth PSD SINR is +inf).
    * ``latency_range_s`` — realized per-client round latency in seconds
      (download + compute + upload); unschedulable clients (infinite
      latency) clip into the top bin.

    ``staleness_bins`` buckets the async merge age tau in server versions
    over [0, max_staleness + 1).  ``solver`` adds Algorithm-1 alternation
    counts and — under an interference geometry — the damped fixed
    point's residual trajectory / iteration count.  ``gradients`` adds
    the aggregated-gradient L2 norm and the solver-implied mask density
    (scheduled-mean 1 - rho) per round.
    """

    bins: int = 16
    per_range: tuple[float, float] = (0.0, 1.0)
    rho_range: tuple[float, float] = (0.0, 1.0)
    bw_share_range: tuple[float, float] = (0.0, 1.0)
    sinr_db_range: tuple[float, float] = (-20.0, 60.0)
    latency_range_s: tuple[float, float] = (0.0, 10.0)
    staleness_bins: int = 8
    solver: bool = True
    gradients: bool = True

    def __post_init__(self):
        if self.bins < 1:
            raise ValueError(f"bins must be >= 1, got {self.bins}")
        if self.staleness_bins < 1:
            raise ValueError(
                f"staleness_bins must be >= 1, got {self.staleness_bins}")
        for name in ("per_range", "rho_range", "bw_share_range",
                     "sinr_db_range", "latency_range_s"):
            lo, hi = getattr(self, name)
            if not hi > lo:
                raise ValueError(f"{name} must satisfy hi > lo, got "
                                 f"({lo}, {hi})")


def bin_edges(lo: float, hi: float, bins: int) -> np.ndarray:
    """The ``bins + 1`` static bin edges of a telemetry histogram."""
    return np.linspace(lo, hi, bins + 1)


# ---------------------------------------------------------------------------
# In-scan summaries (pure jnp — jit/vmap/scan safe, shape static)
# ---------------------------------------------------------------------------

def histogram(x: jnp.ndarray, lo: float, hi: float, bins: int,
              weights: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Static-bin histogram over the last axis: (..., I) -> (..., bins).

    Values clip into [lo, hi] first (out-of-range mass lands in the edge
    bins; +/-inf included), and NaNs count in the bottom bin — so the
    unweighted total mass is always exactly the number of elements
    reduced, which is what lets a smoke test assert
    ``hist.sum() == num_clients``.  ``weights`` (same shape as ``x``)
    turns counts into weighted mass.
    """
    dtype = jnp.result_type(float)
    x = jnp.nan_to_num(jnp.asarray(x, dtype), nan=lo, posinf=hi, neginf=lo)
    x = jnp.clip(x, lo, hi)
    idx = jnp.minimum(
        jnp.floor((x - lo) * (bins / (hi - lo))).astype(jnp.int32), bins - 1)
    w = jnp.ones_like(x) if weights is None else jnp.asarray(weights, dtype)
    # scatter-add into per-row offset bins: O(N) instead of the O(N*bins)
    # one-hot matmul — the histograms are the bulk of the in-scan
    # telemetry cost, and this keeps the overhead within budget
    lead, n = x.shape[:-1], x.shape[-1] if x.ndim else 1
    rows = int(np.prod(lead)) if lead else 1
    flat = (idx.reshape(rows, n)
            + (jnp.arange(rows, dtype=jnp.int32) * bins)[:, None])
    out = jnp.zeros(rows * bins, dtype).at[flat.reshape(-1)].add(
        w.reshape(-1))
    return out.reshape(*lead, bins)


def control_summaries(tcfg: TelemetryConfig, sol, t_client: jnp.ndarray,
                      sinr_db: Optional[jnp.ndarray],
                      bandwidth_hz: float) -> dict[str, jnp.ndarray]:
    """Per-cell histograms + solver diagnostics of one control pass.

    ``sol`` is a ``fleet.solver.CellSolution`` (duck-typed: ``prune`` /
    ``bandwidth`` / ``per`` / ``iterations`` and the optional ``fp_*``
    interference diagnostics); ``t_client`` the realized (C, I) latency;
    ``sinr_db`` the realized per-client uplink SINR in dB (None skips the
    SINR histogram — the host reference solver path does not expose it).
    All histograms count *every* client (mass per cell = I), so
    distribution mass is invariant across schedules.
    """
    b = tcfg.bins
    out = {
        PREFIX + "per_hist": histogram(sol.per, *tcfg.per_range, b),
        PREFIX + "rho_hist": histogram(sol.prune, *tcfg.rho_range, b),
        PREFIX + "bw_hist": histogram(sol.bandwidth / bandwidth_hz,
                                      *tcfg.bw_share_range, b),
        PREFIX + "latency_hist": histogram(t_client, *tcfg.latency_range_s,
                                           b),
    }
    if sinr_db is not None:
        out[PREFIX + "sinr_hist"] = histogram(sinr_db, *tcfg.sinr_db_range, b)
    if tcfg.solver:
        out[PREFIX + "solver_iters"] = sol.iterations
        if sol.fp_iterations is not None:
            out[PREFIX + "fp_iterations"] = sol.fp_iterations
        if sol.fp_residual is not None:
            out[PREFIX + "fp_residual"] = sol.fp_residual
        if sol.fp_residuals is not None:
            out[PREFIX + "fp_residuals"] = sol.fp_residuals
    return out


def grad_summaries(tcfg: TelemetryConfig, grad_sq_sum: jnp.ndarray,
                   mask_density: jnp.ndarray) -> dict[str, jnp.ndarray]:
    """Gradient-norm / mask-density drift entries (``tcfg.gradients``)."""
    if not tcfg.gradients:
        return {}
    return {PREFIX + "grad_norm": jnp.sqrt(grad_sq_sum),
            PREFIX + "mask_density": mask_density}


def tree_sq_norm(tree) -> jnp.ndarray:
    """Sum of squares over every leaf of a pytree (scalar)."""
    leaves = jax.tree_util.tree_leaves(tree)
    dtype = jnp.result_type(float)
    total = jnp.zeros((), dtype)
    for leaf in leaves:
        total = total + jnp.sum(jnp.square(leaf.astype(dtype)))
    return total


def staleness_summary(tcfg: TelemetryConfig, tau: jnp.ndarray,
                      max_staleness: int) -> dict[str, jnp.ndarray]:
    """Histogram of the merged cohort's staleness (server versions)."""
    hist = histogram(tau, 0.0, float(max_staleness + 1), tcfg.staleness_bins)
    return {PREFIX + "staleness_hist": hist}


def split_metrics(metrics: dict) -> tuple[dict, Optional[dict]]:
    """Split a metrics dict into (core metrics, telemetry dict or None).

    Telemetry keys carry the ``tel_`` prefix inside the scan; the
    returned telemetry dict is keyed without it (``per_hist``, ...).
    """
    core = {k: v for k, v in metrics.items() if not k.startswith(PREFIX)}
    tel = {k[len(PREFIX):]: v for k, v in metrics.items()
           if k.startswith(PREFIX)}
    return core, (tel or None)


# ---------------------------------------------------------------------------
# Trace spans and counts (host wall clock; Chrome-trace JSON export)
# ---------------------------------------------------------------------------

class Span(NamedTuple):
    """One recorded span: wall-clock nanoseconds (``time.time_ns``), the
    thread that recorded it, and its arguments.  A count is a span with
    ``end_ns == start_ns``."""

    name: str
    start_ns: int
    end_ns: int
    tid: int
    args: Optional[dict] = None


class SpanRecorder:
    """Record named wall-clock spans and counts; export as Chrome-trace JSON.

    Stamps are ``time.time_ns()``: the clock of JAX's own compile events
    and of a ``jax.profiler`` capture's host events (which the capture
    stores relative to its ``profile_start_time``), so the three lie
    side by side.  The newest ``CAPACITY`` spans are kept; per-name
    totals (count, seconds) and per-program totals of spans that carry a
    ``program`` argument cover every span ever recorded.

    Each ``span`` also enters a ``jax.profiler.TraceAnnotation`` so the
    phase shows up in a ``jax.profiler.trace`` capture when one is
    active.  Spans may nest; events carry the thread id, so the Chrome
    trace viewer (``chrome://tracing`` or Perfetto) renders nesting
    correctly.
    """

    CAPACITY = 4096

    def __init__(self):
        self._lock = threading.Lock()
        self._spans: collections.deque = collections.deque(
            maxlen=self.CAPACITY)
        self._totals: dict[str, list] = {}
        self._programs: dict[tuple[str, str], list] = {}

    @contextlib.contextmanager
    def span(self, name: str, **args):
        start = time.time_ns()
        with jax.profiler.TraceAnnotation(name):
            try:
                yield self
            finally:
                self.record(name, start, time.time_ns(), **args)

    def record(self, name: str, start_ns: int, end_ns: int, **args) -> None:
        """Add a finished span (or, with ``end_ns == start_ns``, a count)."""
        sp = Span(name, int(start_ns), int(end_ns), threading.get_ident(),
                  args or None)
        seconds = (sp.end_ns - sp.start_ns) * 1e-9
        with self._lock:
            self._spans.append(sp)
            tot = self._totals.setdefault(name, [0, 0.0])
            tot[0] += 1
            tot[1] += seconds
            program = args.get("program")
            if program is not None:
                per = self._programs.setdefault((name, program),
                                                [0, seconds, 0.0])
                per[0] += 1
                per[2] += seconds

    def count(self, name: str) -> None:
        """Record one occurrence of ``name`` at this instant."""
        now = time.time_ns()
        self.record(name, now, now)

    def spans(self, prefix: str = "") -> list[Span]:
        """The kept spans whose name starts with ``prefix``, oldest first."""
        with self._lock:
            return [s for s in self._spans if s.name.startswith(prefix)]

    def totals(self) -> dict[str, tuple[int, float]]:
        """name -> (count, seconds) over every span recorded."""
        with self._lock:
            return {k: (v[0], v[1]) for k, v in self._totals.items()}

    def program_totals(self, name: str) -> dict[str, dict]:
        """program -> {count, first_s, total_s} of the ``name`` spans that
        carry a ``program`` argument."""
        with self._lock:
            return {prog: {"count": v[0], "first_s": v[1], "total_s": v[2]}
                    for (n, prog), v in self._programs.items() if n == name}

    @property
    def events(self) -> list[dict]:
        """The kept spans as Chrome-trace events (microseconds since the
        Unix epoch; a count is an instant event)."""
        pid = os.getpid()
        out = []
        for s in self.spans():
            ev = {"name": s.name, "cat": s.name.split(".")[0],
                  "ts": s.start_ns / 1e3, "pid": pid, "tid": s.tid}
            if s.end_ns > s.start_ns:
                ev.update(ph="X", dur=(s.end_ns - s.start_ns) / 1e3)
            else:
                ev.update(ph="i", s="t")
            if s.args:
                ev["args"] = s.args
            out.append(ev)
        return out

    def chrome_trace(self) -> dict:
        """The ``chrome://tracing`` / Perfetto JSON document."""
        return {"traceEvents": sorted(self.events, key=lambda e: e["ts"]),
                "displayTimeUnit": "ms"}

    def write(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f, indent=1)
        return path


# JAX's compile pipeline, as ``jax.monitoring`` reports it: time spans
# (``time.time()`` seconds, the recorder's clock) named by program, and
# the persistent compilation cache's hits and writes.
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
NESTING = (TRACE_EVENT, LOWER_EVENT)
JAX_SPANS = {
    TRACE_EVENT: "jax.trace",
    LOWER_EVENT: "jax.lower",
    "/jax/core/compile/backend_compile_duration": "jax.compile",
}
JAX_COUNTS = {
    "/jax/compilation_cache/cache_hits": "jax.cache_hit",
    "/jax/compilation_cache/cache_misses": "jax.cache_miss",
}

_process_lock = threading.Lock()
_process: Optional[SpanRecorder] = None


def process_recorder(create: bool = True) -> Optional[SpanRecorder]:
    """The process-wide ``SpanRecorder``: the default recorder of
    ``build_simulation`` and ``run_fleet``.

    The first call makes it and registers ``jax.monitoring`` listeners
    that record every later trace, lowering and backend compile (or
    compile-cache fetch) as ``jax.trace`` / ``jax.lower`` /
    ``jax.compile`` spans with the program's name, and every persistent
    cache hit and write as a ``jax.cache_hit`` / ``jax.cache_miss``
    count.  Importing this module registers nothing.  ``create=False``
    returns None until some caller has made it.  A trace nested in
    another trace or in a lowering is part of it and is not recorded on
    its own.
    """
    global _process
    with _process_lock:
        if _process is None and create:
            rec = SpanRecorder()
            # JAX reports each span's start as a scalar when it opens; a
            # trace opened inside another trace or a lowering (every jitted
            # helper a program calls, every lowering rule that traces) is
            # part of the outer span and is not kept
            depth = threading.local()

            def on_open(event, value, **kw):
                if event in NESTING:
                    depth.n = getattr(depth, "n", 0) + 1

            def on_span(event, start, end, **kw):
                name = JAX_SPANS.get(event)
                if name is None:
                    return
                if event in NESTING:
                    depth.n = max(getattr(depth, "n", 1) - 1, 0)
                    if event == TRACE_EVENT and depth.n:
                        return
                rec.record(name, int(start * 1e9), int(end * 1e9),
                           program=str(kw.get("fun_name", "")))

            def on_event(event, **kw):
                name = JAX_COUNTS.get(event)
                if name is not None:
                    rec.count(name)

            jax.monitoring.register_scalar_listener(on_open)
            jax.monitoring.register_event_time_span_listener(on_span)
            jax.monitoring.register_event_listener(on_event)
            _process = rec
        return _process


def compile_report() -> dict:
    """program -> {count, first_s, total_s} of the backend compiles (or
    cache fetches) the process recorder holds: a program compiled more
    than once shows a count above 1."""
    return process_recorder().program_totals("jax.compile")


# ---------------------------------------------------------------------------
# Sinks
# ---------------------------------------------------------------------------

@runtime_checkable
class TelemetrySink(Protocol):
    """Anything that accepts flat telemetry records.

    ``emit`` receives one JSON-serializable dict per call (run header,
    then one record per round/event); ``close`` flushes and releases the
    underlying resource.  Implementations must tolerate heterogeneous
    key sets across records.
    """

    def emit(self, record: dict) -> None: ...

    def close(self) -> None: ...


class MemorySink:
    """Collect records in a list (tests, notebooks)."""

    def __init__(self):
        self.records: list[dict] = []
        self.closed = False

    def emit(self, record: dict) -> None:
        self.records.append(record)

    def close(self) -> None:
        self.closed = True


class JSONLSink:
    """One JSON object per line — the append-friendly default on disk."""

    def __init__(self, path: str):
        self.path = path
        self._f = open(path, "w")

    def emit(self, record: dict) -> None:
        self._f.write(json.dumps(record) + "\n")

    def close(self) -> None:
        if not self._f.closed:
            self._f.close()


class CSVSink:
    """Flat CSV: one row per record, header = union of all record keys.

    Rows are buffered and written on ``close()`` so the header can cover
    every key seen (the run-header record and the round records carry
    different key sets).  Array-valued fields (histograms, per-cell
    vectors) are JSON-encoded into their cell — CSV stays a
    scalar-friendly summary format; use JSONL for faithful nesting.
    """

    def __init__(self, path: str):
        self.path = path
        self._rows: list[dict] = []
        self._fields: list[str] = []
        self._closed = False

    def emit(self, record: dict) -> None:
        flat = {k: (json.dumps(v) if isinstance(v, (list, dict)) else v)
                for k, v in record.items()}
        for k in flat:
            if k not in self._fields:
                self._fields.append(k)
        self._rows.append(flat)

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        with open(self.path, "w", newline="") as f:
            writer = csv.DictWriter(f, fieldnames=self._fields, restval="")
            writer.writeheader()
            for row in self._rows:
                writer.writerow(row)


def sink_for_path(path: str) -> TelemetrySink:
    """Pick a file sink by extension: ``.csv`` -> CSV, else JSONL."""
    return CSVSink(path) if path.endswith(".csv") else JSONLSink(path)


# ---------------------------------------------------------------------------
# Emission: FleetResult -> per-round records
# ---------------------------------------------------------------------------

def _jsonable(v: Any):
    a = np.asarray(v)
    if a.ndim == 0:
        return a.item()
    return a.tolist()


def round_records(result, meta: Optional[dict] = None):
    """Yield the run header then one record per round/event of a
    ``fleet.FleetResult`` (sinks consume these verbatim).

    The header (``kind: "run"``) carries the mode, round count and any
    caller ``meta`` (config digest, bench arm, git ref...).  Round
    records (``kind: "round"``) carry the scalar trajectories plus —
    when the run had telemetry enabled — that round's histogram /
    diagnostic summaries as nested lists.
    """
    header = {"kind": "run", "mode": result.mode,
              "rounds": int(np.asarray(result.losses).shape[0]),
              "bound_final": float(result.bound_final)}
    if meta:
        header.update(meta)
    yield header

    scalars = {
        "loss": result.losses, "accuracy": result.accuracy,
        "round_latency": result.latencies, "mean_prune": result.mean_prune,
        "mean_per": result.mean_per, "participants": result.participants,
        "wall_clock": result.wall_clock, "staleness": result.staleness,
    }
    tel = getattr(result, "telemetry", None) or {}
    n = int(np.asarray(result.losses).shape[0])
    for rnd in range(n):
        rec = {"kind": "round", "round": rnd}
        for k, v in scalars.items():
            if v is not None:
                rec[k] = _jsonable(np.asarray(v)[rnd])
        for k, v in tel.items():
            arr = np.asarray(v)
            # fixed-point diagnostics of an interference solve are per
            # round when the scan stacked them, scalar otherwise
            rec[k] = _jsonable(arr[rnd]) if arr.ndim and arr.shape[0] == n \
                else _jsonable(arr)
        yield rec


def emit_result(result, sink: TelemetrySink, meta: Optional[dict] = None,
                close: bool = False) -> int:
    """Emit a run's records through ``sink``; returns the record count."""
    n = 0
    for rec in round_records(result, meta=meta):
        sink.emit(rec)
        n += 1
    if close:
        sink.close()
    return n
