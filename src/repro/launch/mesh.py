"""Production mesh builders.

Functions, not module-level constants: importing this module never touches
jax device state (the dry-run must set XLA_FLAGS before first jax init).
"""

from __future__ import annotations

import jax

SINGLE_POD = (16, 16)                     # 256 chips (TPU v5e pod)
MULTI_POD = (2, 16, 16)                   # 2 pods = 512 chips


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis of type Auto."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = MULTI_POD if multi_pod else SINGLE_POD
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int | None = None, model: int = 1):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = jax.device_count()
    data = data or (n // model)
    return make_mesh((data, model), ("data", "model"))


def make_fleet_mesh(cells: int | None = None, data: int | None = None):
    """Two-axis fleet mesh: ("cells", "data").

    The fleet engine places the leading cell axis of population/control
    tensors (and the solver's per-cell batch) on "cells" and the flat
    client axis of the gradient batch on "data" — see
    ``repro.fleet.engine``'s sharding notes.  With neither size given the
    available devices split as near-square as possible (cells gets the
    smaller factor: per-cell client counts usually exceed the cell count's
    parallel grain).
    """
    n = jax.device_count()
    if cells is None and data is None:
        cells = 1
        for f in range(int(n ** 0.5), 0, -1):
            if n % f == 0:
                cells = f
                break
        data = n // cells
    elif cells is None:
        cells = n // data
    elif data is None:
        data = n // cells
    return make_mesh((cells, data), ("cells", "data"))


def required_devices(multi_pod: bool) -> int:
    shape = MULTI_POD if multi_pod else SINGLE_POD
    n = 1
    for s in shape:
        n *= s
    return n
