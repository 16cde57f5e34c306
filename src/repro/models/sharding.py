"""Logical-axis sharding constraints for model internals.

The model code annotates activations with *logical* axis names
("batch", "seq", "embed", "heads", ...); the launcher installs a rule set
mapping logical names to mesh axes.  On a single device (or with no rules
installed) everything is a no-op, so smoke tests never touch device state.
"""

from __future__ import annotations

import contextlib
import threading

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

_state = threading.local()

# Default production rules (see DESIGN.md §6).  "data_axes" covers both the
# single-pod ("data",) and multi-pod ("pod","data") meshes.
DEFAULT_RULES = {
    "batch": ("pod", "data"),
    "seq": None,
    "embed": None,
    "heads": "model",
    "kv": None,
    "mlp": "model",
    "vocab": "model",
    "experts": "model",   # expert parallelism (when E divides the axis)
    "cache_seq": "data",
    # context parallelism: flash-attention query stripes over "model" —
    # engages the tensor axis for attention even when head counts don't
    # divide it (see attention.flash_attention)
    "q_stripes": "model",
}


def set_rules(rules: dict | None, mesh=None) -> None:
    _state.rules = rules
    _state.mesh = mesh


def get_rules() -> dict | None:
    return getattr(_state, "rules", None)


def get_mesh():
    return getattr(_state, "mesh", None)


@contextlib.contextmanager
def use_rules(rules: dict | None, mesh=None):
    """Install logical-axis rules (+ the mesh constraints bind to).

    NOTE: the mesh must be passed explicitly — ``with mesh:`` does NOT
    populate ``jax.sharding.get_abstract_mesh()`` during jit tracing, so
    relying on the ambient context silently disables every constraint."""
    prev, prev_mesh = get_rules(), get_mesh()
    set_rules(rules, mesh)
    try:
        yield
    finally:
        set_rules(prev, prev_mesh)


def _mesh_axes(mesh, names) -> tuple | None:
    """Filter a logical rule down to axes present in the mesh."""
    if names is None:
        return None
    if isinstance(names, str):
        names = (names,)
    present = tuple(n for n in names if n in mesh.axis_names)
    return present or None


def axis_size(logical_name: str) -> int:
    """Product of mesh-axis sizes a logical axis maps to (1 if rules or
    mesh are absent) — lets model code pick parallel-friendly factorings."""
    rules = get_rules()
    mesh = get_mesh()
    if rules is None or mesh is None:
        return 1
    axes = _mesh_axes(mesh, rules.get(logical_name))
    if axes is None:
        return 1
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def constrain(x, *logical_axes):
    """with_sharding_constraint by logical axis names; no-op without rules
    or without an active mesh.  Axes that do not evenly divide the
    corresponding dim are dropped (uneven GSPMD sharding costs more in
    padding/halo traffic than it saves)."""
    rules = get_rules()
    if rules is None:
        return x
    # Inside shard_map the manual axes are already per-shard; constraints
    # may only name the remaining Auto axes (hybrid shard_map).  Fully
    # manual context -> no-op.
    manual: set = set()
    ctx = jax.sharding.get_abstract_mesh()
    if not ctx.empty:
        manual = {name for name, t in zip(ctx.axis_names, ctx.axis_types)
                  if t == jax.sharding.AxisType.Manual}
        if manual:
            if len(manual) == len(ctx.axis_names):
                return x
            mesh = ctx     # hybrid: bind constraints to the context mesh
        else:
            mesh = get_mesh() or ctx
    else:
        mesh = get_mesh()
        if mesh is None:
            return x
    spec = []
    for dim, name in enumerate(logical_axes):
        if name is None:
            spec.append(None)
            continue
        axes = _mesh_axes(mesh, rules.get(name))
        if axes is not None and manual:
            axes = tuple(a for a in axes if a not in manual) or None
        if axes is not None:
            size = 1
            for a in axes:
                size *= mesh.shape[a]
            if dim >= x.ndim or x.shape[dim] % size or x.shape[dim] < size:
                axes = None
        spec.append(axes if axes is None or len(axes) > 1 else axes[0])
    if all(s is None for s in spec):
        # nothing survived the guards: an empty constraint would FORCE
        # replication — leave the tensor unconstrained instead
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*spec)))
