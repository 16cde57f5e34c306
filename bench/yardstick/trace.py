"""Reduce a profiler trace to device busy time, per-scope and per-op device
time, and the longest idle gaps with the host span open during each.

The trace is the ``.xplane.pb`` that ``jax.profiler.trace`` writes.  Device
operations are the events of the ``XLA Ops`` line of each ``/device:TPU:n``
plane, named by their HLO instruction only: each op's name scope is looked
up in the compiled program's text (``scope_map``), where every instruction
carries its ``op_name`` metadata.  Host spans are the events whose name
starts with ``bench.`` (the harness's own ``TraceAnnotation``s).  Device
times are moved onto the host clock (``_clock_offset``); all times are in
seconds.

``reduce(ops, spans, window)`` is pure arithmetic over event tuples, so it is
tested on synthetic events as well as on a recorded chip trace.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import math
import pathlib
import re
from typing import Iterable, Optional

DEVICE_PLANE = "/device:TPU:"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
SPAN_PREFIX = "bench."
# host events of one program launch: issued, and seen complete
HOST_ISSUE, HOST_DONE = "tpu::System::Execute", "tpu::System::Execute=>Done"
_HLO_OP = re.compile(r'^\s*(?:ROOT\s+)?%([\w.\-]+) = .*?'
                     r'metadata=\{[^}]*?op_name="([^"]*)"')


@dataclasses.dataclass(frozen=True)
class Op:
    start: float
    end: float
    name: str                # the HLO op's name, e.g. "fusion.12"
    scope: str = ""          # its name-scope path (metadata op_name)
    device: int = 0


@dataclasses.dataclass(frozen=True)
class Span:
    start: float
    end: float
    name: str


def find_xplane(directory) -> pathlib.Path:
    found = sorted(pathlib.Path(directory).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def scope_map(hlo_text: str) -> tuple[str, dict]:
    """(module name, {HLO op name: metadata op_name}) of one compiled
    program's text (``compiled.as_text()``): the device trace names ops by
    their HLO names only, and their scopes are found here."""
    first = hlo_text.split("\n", 1)[0].split()
    module = first[1].rstrip(",") if len(first) > 1 else ""
    ops = {}
    for line in hlo_text.splitlines():
        m = _HLO_OP.match(line)
        if m:
            ops[m.group(1)] = m.group(2)
    return module, ops


def _op_name(event_name: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``."""
    head = event_name.split(" = ", 1)[0].strip()
    return head[len("ROOT "):].lstrip("%") if head.startswith("ROOT ") \
        else head.lstrip("%")


def _clock_offset(modules: list, issues: list, dones: list) -> float:
    """Seconds to add to device times to put them on the host clock.

    Matches program launches from the end of the trace (nothing is in
    flight once the window has synced): a program starts on the device
    after the host issued it and ends before the host sees it done.  The
    offset is the middle of the interval those bounds leave, or the lower
    bound where they cross."""
    n = min(len(modules), len(issues), len(dones))
    if n == 0:
        return 0.0
    pairs = zip(modules[-n:], issues[-n:], dones[-n:])
    lo, hi = -math.inf, math.inf
    for (d0, d1), issue, done in pairs:
        lo, hi = max(lo, issue - d0), min(hi, done - d1)
    return (lo + hi) / 2.0 if lo <= hi else lo


def read(path, scope_maps: Optional[dict] = None
         ) -> tuple[list[Op], list[Span]]:
    """Device ops and ``bench.`` host spans of one ``.xplane.pb`` file, on
    the host clock.  ``scope_maps`` maps module names to ``scope_map``'s
    op tables; an op of another module gets no scope."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    scope_maps = scope_maps or {}
    raw, spans, modules, issues, dones = [], [], [], [], []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE):
            dev = int(plane.name[len(DEVICE_PLANE):].split()[0] or 0)
            lines = {line.name: line for line in plane.lines}
            mods = sorted((ev.start_ns * 1e-9,
                           (ev.start_ns + ev.duration_ns) * 1e-9,
                           ev.name.split("(")[0])
                          for ev in lines[MODULES_LINE].events) \
                if MODULES_LINE in lines else []
            if dev == 0:
                modules = [(a, b) for a, b, _ in mods]
            starts = [a for a, _, _ in mods]
            for ev in (lines[OPS_LINE].events if OPS_LINE in lines else ()):
                t0 = ev.start_ns * 1e-9
                i = bisect.bisect_right(starts, t0) - 1
                module = mods[i][2] if i >= 0 else ""
                name = _op_name(ev.name)
                scope = scope_maps.get(module, {}).get(name, "")
                raw.append((t0, t0 + ev.duration_ns * 1e-9, name, scope,
                            dev))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    t0, t1 = ev.start_ns * 1e-9, \
                        (ev.start_ns + ev.duration_ns) * 1e-9
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append(Span(t0, t1, ev.name))
                    elif ev.name == HOST_ISSUE:
                        issues.append(t0)
                    elif ev.name == HOST_DONE:
                        dones.append(t0)
    off = _clock_offset(modules, sorted(issues), sorted(dones))
    ops = [Op(a + off, b + off, n, sc, d) for a, b, n, sc, d in raw]
    return ops, spans


def union(intervals: Iterable[tuple[float, float]]
          ) -> list[tuple[float, float]]:
    """Merge overlapping [start, end) intervals, sorted."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(a, b, lo, hi):
    return max(a, lo), min(b, hi)


def _innermost(spans: list[Span], t: float) -> str:
    best = None
    for s in spans:
        inside = s.start <= t < s.end
        if inside and (best is None
                       or s.end - s.start < best.end - best.start):
            best = s
    return best.name if best is not None else "(no bench span)"


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                    # mean over devices of the busy union
    scope_s: dict                    # scope token -> device seconds
    op_s: dict                       # op label -> device seconds
    gaps: list                       # [(span name, seconds)], longest first
    devices: int

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def op_label(op: Op) -> str:
    """A label stable across compiles: the innermost named scope of the
    op's path (an element such as ``fleet.solve``: dotted, no call
    parentheses) and the HLO op's kind (its name without the number)."""
    kind = op.name.split(".")[0].rstrip("0123456789-_")
    named = [p for p in op.scope.split("/") if "." in p and "(" not in p]
    return f"{named[-1]}:{kind}" if named else kind


def leaves(timed: list) -> list:
    """The (start, end, op) entries that hold no other entry: a ``while``
    or ``conditional`` op spans the ops of its body on the same line, and
    only those are counted, once."""
    timed = sorted(timed, key=lambda t: (t[0], -t[1]))
    return [t for t, nxt in zip(timed, timed[1:] + [None])
            if nxt is None or not (nxt[0] < t[1] and nxt[1] <= t[1])]


def reduce(ops: list[Op], spans: list[Span], window: tuple[float, float],
           scopes: Iterable[str] = (), top: int = 10) -> Reduction:
    """Busy union, per-scope time and idle gaps inside ``window``.

    ``scopes`` are name-scope tokens (``fleet.solve``); an op counts for a
    scope when the token is one element of its scope path.  Per-scope and
    per-op times count the innermost ops only (``leaves``).  Busy and idle
    are per device and averaged; gaps are those of device 0.
    """
    lo, hi = window
    by_dev = collections.defaultdict(list)
    for op in ops:
        a, b = _clip(op.start, op.end, lo, hi)
        if b > a:
            by_dev[op.device].append((a, b, op))
    devices = sorted(by_dev) or [0]
    busy = [sum(b - a for a, b in union((a, b) for a, b, _ in by_dev[d]))
            for d in devices]
    scope_s = {s: 0.0 for s in scopes}
    op_s: dict = collections.defaultdict(float)
    for d in devices:
        for a, b, op in leaves(by_dev[d]):
            elems = op.scope.split("/")
            for s in scope_s:
                if s in elems:
                    scope_s[s] += b - a
            op_s[op_label(op)] += b - a
    n = len(devices)
    scope_s = {k: v / n for k, v in scope_s.items()}
    op_s = {k: v / n for k, v in op_s.items()}
    merged = union((a, b) for a, b, _ in by_dev[devices[0]])
    edges = [lo] + [x for ab in merged for x in ab] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    gaps = [(_innermost(spans, (a + b) / 2.0), b - a) for a, b in gaps[:top]]
    return Reduction(window_s=hi - lo, busy_s=sum(busy) / n, scope_s=scope_s,
                     op_s=dict(op_s), gaps=gaps, devices=n)


def window_of(spans: list[Span], name: str) -> Optional[tuple[float, float]]:
    hits = [s for s in spans if s.name == name]
    if not hits:
        return None
    return min(s.start for s in hits), max(s.end for s in hits)


def breakdown(red: Reduction, top: int = 10) -> dict:
    ops = sorted(red.op_s.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in red.gaps[:top]]}
