"""Kinds of per-layer metric, each read from a trace reduction.

A metric file ``bench/metrics/<name>.json`` names its ``kind`` and that
kind's parameters.  A metric of a kind of its own is a module
``bench/metrics/<name>.py`` with ``read(red, ctx)`` (and, where it reads a
name scope, ``SCOPE``): its kind here is ``python``.  A reader that finds
nothing to read returns ``None``,
and the harness then leaves the metric out of the result line; a share of a
peak is never reported as 0 for want of a reading.

``ctx`` carries what the harness knows of the traced window:
``host_window_s`` (its host-clock length), ``device_kind``, and the work
the driver did in it (``work``): ``units`` (rounds completed) and the
``flops`` that the work needs (from ``flops``).
"""

from __future__ import annotations

from yardstick import peaks as PEAKS


def scope_ms_per_unit(spec: dict, red, ctx: dict):
    """Device milliseconds of the ops under one name scope, per unit."""
    seconds = red.scope_s.get(spec["scope"], 0.0)
    units = ctx["work"]["units"]
    if seconds <= 0.0 or units <= 0:
        return None
    return 1e3 * seconds / units


def idle_share(spec: dict, red, ctx: dict):
    """Per cent of the traced window in which no op ran on the device."""
    if red.window_s <= 0.0 or red.busy_s <= 0.0:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)


def mfu(spec: dict, red, ctx: dict):
    """Per cent of the chip's bf16 peak that the work's own FLOPs reach
    over the traced window's host-clock time."""
    flops = ctx["work"].get("flops", 0.0)
    if flops <= 0.0 or ctx["host_window_s"] <= 0.0:
        return None
    peak = PEAKS.peaks(ctx["device_kind"]).flops
    return 100.0 * flops / ctx["host_window_s"] / peak


def python(spec: dict, red, ctx: dict):
    """A metric's own module."""
    return spec["read"](red, ctx)


KINDS = {"scope_ms_per_unit": scope_ms_per_unit, "idle_share": idle_share,
         "mfu": mfu, "python": python}


def read(spec: dict, red, ctx: dict):
    return KINDS[spec["kind"]](spec, red, ctx)
