"""Set-up's stages, read from the spans the program records.

The fleet program records into one process-wide recorder
(``repro.fleet.telemetry.process_recorder``): the ``fleet.build`` span of
``build_simulation`` and its stages, JAX's own ``jax.trace`` /
``jax.lower`` / ``jax.compile`` spans (tracing, lowering, and the backend
compile or compile-cache fetch of each program) and a ``jax.cache_miss``
count for each program written to the persistent compile cache, all on
the wall clock.  A program without that recorder gives nothing to read.

Set-up is what was recorded from the last ``fleet.build`` on (the
recorder exists from there) to the window.  Nothing compiles in the
window (set-up warms up every shape first), so the window is the longest
stretch between two recorded spans in which none is open; what follows
it, such as the harness lowering the round again for its scope map and
the reference's own programs, is not set-up.  A longest stretch shorter
than the traced window cannot hold the window, and then nothing is read.

Stages are disjoint, so they add up to no more than ``setup_s``: build is
the ``fleet.build`` span's time under no JAX span, trace its time under
``jax.trace`` or ``jax.lower``, compile the time under ``jax.compile``
and under neither of those (an eager compile inside a trace counts as
trace).  Times are in seconds.
"""

from __future__ import annotations

from typing import Optional

TRACE = ("jax.trace", "jax.lower")
COMPILE = ("jax.compile",)


def recorder():
    """The program's process recorder, or None where the program has none
    or has not made it."""
    from repro.fleet import telemetry
    get = getattr(telemetry, "process_recorder", None)
    return get(create=False) if get is not None else None


def setup(ctx: dict) -> Optional[list]:
    """The spans recorded before the window, oldest first, or None."""
    rec = recorder()
    if rec is None:
        return None
    spans = sorted(rec.spans(), key=lambda s: s.start_ns)
    builds = [s.start_ns for s in spans if s.name == "fleet.build"]
    if not builds:
        return None
    spans = [s for s in spans if s.start_ns >= builds[-1]]
    longest, cut, reach = 0, None, None
    for s in spans:
        if reach is not None and s.start_ns - reach > longest:
            longest, cut = s.start_ns - reach, s.start_ns
        reach = s.end_ns if reach is None else max(reach, s.end_ns)
    if cut is None or longest < ctx["host_window_s"] * 1e9:
        return None
    return [s for s in spans if s.start_ns < cut]


def _covered_ns(intervals) -> int:
    """Length of the union of [start, end) intervals."""
    total, reach = 0, None
    for a, b in sorted(intervals):
        if reach is None or a > reach:
            total += b - a
            reach = b
        elif b > reach:
            total += b - reach
            reach = b
    return total


def _of(spans, names, lo=None, hi=None):
    out = []
    for s in spans:
        if s.name in names:
            a = s.start_ns if lo is None else max(s.start_ns, lo)
            b = s.end_ns if hi is None else min(s.end_ns, hi)
            if b > a:
                out.append((a, b))
    return out


def build_s(spans: list) -> Optional[float]:
    """Seconds of the last ``fleet.build`` under no JAX span."""
    builds = [s for s in spans if s.name == "fleet.build"]
    if not builds:
        return None
    b = builds[-1]
    inside = _covered_ns(_of(spans, TRACE + COMPILE, b.start_ns, b.end_ns))
    return (b.end_ns - b.start_ns - inside) * 1e-9


def trace_s(spans: list) -> Optional[float]:
    """Seconds under ``jax.trace`` or ``jax.lower``."""
    ns = _covered_ns(_of(spans, TRACE))
    return ns * 1e-9 if ns > 0 else None


def compile_s(spans: list) -> Optional[float]:
    """Seconds under ``jax.compile`` and under no trace or lowering."""
    if not _of(spans, COMPILE):
        return None
    return (_covered_ns(_of(spans, TRACE + COMPILE))
            - _covered_ns(_of(spans, TRACE))) * 1e-9


def cache_misses(spans: list) -> Optional[int]:
    """Programs written to the persistent compile cache."""
    if not _of(spans, TRACE + COMPILE):
        return None
    return sum(1 for s in spans if s.name == "jax.cache_miss")
