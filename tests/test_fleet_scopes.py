"""The fleet round's named scopes reach the compiled program.

A device profile names ops by their HLO instruction only; the scope of
each op is read from the compiled text's ``op_name`` metadata.  Every
phase scope and every scope nested in one must appear there as an element
of the path of its own (JAX writes the first scope under a ``vmap`` as
``vmap(<scope>)``, which a reader of elements would miss).
"""

import re

import jax
import pytest

from repro.fleet import (FleetConfig, FleetTopology, ScheduleConfig,
                         SyntheticMLPTask, build_simulation)

PHASES = ("fleet.channel", "fleet.solve", "fleet.gradient", "fleet.merge",
          "fleet.eval")
NESTED = ("fleet.schedule", "fleet.solve.vertex", "fleet.solve.bandwidth",
          "fleet.solve.cost", "fleet.mask", "fleet.data", "fleet.backprop")

TOPO = FleetTopology(num_cells=4, clients_per_cell=8)
TASK = SyntheticMLPTask(feature_dim=8, hidden=(8,), num_classes=4,
                        local_batch=4)
PATHS = {
    "reference_full": FleetConfig(topology=TOPO, task=TASK, rounds=2),
    "cohort_chunked_streamed": FleetConfig(
        topology=TOPO, task=TASK, rounds=2,
        schedule=ScheduleConfig(participation="uniform",
                                participants_per_cell=3),
        cohort_gather=True, control_chunk=3, cell_chunk=3,
        cache_data=False),
    "fused_block": FleetConfig(topology=TOPO, task=TASK, rounds=2,
                               kernel="fused_xla", mask_kind="block"),
}


def _op_paths(cfg) -> list:
    sim = build_simulation(cfg)
    text = sim.simulate.lower(sim.params, sim.round_keys).compile().as_text()
    return [m.group(1).split("/")
            for m in re.finditer(r'op_name="([^"]*)"', text)]


@pytest.mark.parametrize("path", sorted(PATHS))
def test_every_scope_is_an_element_of_compiled_op_names(path):
    paths = _op_paths(PATHS[path])
    elems = {e for p in paths for e in p}
    missing = [s for s in PHASES + NESTED if s not in elems]
    assert not missing, f"{path}: no op carries {missing}"
    # no op has a scope only under a transform's name, as vmap(fleet.mask)
    hidden = [(s, "/".join(p)) for p in paths for s in PHASES + NESTED
              if any(f"({s})" in e for e in p) and s not in p]
    assert not hidden, f"{path}: {hidden[:3]}"


def test_selection_scope_only_where_selection_runs(monkeypatch):
    """``fleet.mask.select`` marks the threshold selection: absent from a
    model under the size cut-off, an element of the op paths above it."""
    from repro.core import pruning
    cfg = PATHS["reference_full"]
    assert not any("fleet.mask.select" in p for p in _op_paths(cfg))
    monkeypatch.setattr(pruning, "SELECT_MIN_ELEMENTS", 1)
    paths = _op_paths(cfg)
    assert any("fleet.mask.select" in p for p in paths)
    assert not any("sort" in p[-1] for p in paths if "fleet.mask" in p)


@pytest.mark.parametrize("path", ["reference_full", "cohort_chunked_streamed"])
def test_selected_thresholds_give_the_sorts_rounds(path, monkeypatch):
    """Rounds with magnitude masks give the same losses and parameters, bit
    for bit, whether the thresholds come from the sort of |w| or from the
    selection (forced on the tiny MLP by lowering the size cut-off)."""
    import numpy as np

    from repro.core import pruning
    from repro.fleet import run_fleet
    cfg = PATHS[path]
    assert cfg.mask_kind == "magnitude"
    base = run_fleet(cfg)
    monkeypatch.setattr(pruning, "SELECT_MIN_ELEMENTS", 1)
    calls = []
    select = pruning.magnitude_thresholds
    monkeypatch.setattr(pruning, "magnitude_thresholds",
                        lambda *a: calls.append(1) or select(*a))
    sel = run_fleet(cfg)
    assert calls, "the rounds did not take the selection path"
    np.testing.assert_array_equal(sel.losses, base.losses)
    for a, b in zip(jax.tree.leaves(sel.params), jax.tree.leaves(base.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
