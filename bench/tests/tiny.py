"""Tiny stand-ins of the benchmark's cells, for runs on the CPU.

``make_bench(tmp)`` copies ``bench/`` into ``tmp`` and adds, as new files
only, a tiny llama configuration, a tiny fleet configuration and a mix of
its own, with limits that hold for sound runs at these sizes.  It returns
the copied directory and a ``BENCHMARK.json``-shaped dict naming the tiny
cells.  ``add_fixture_cells(tmp)`` makes such a copy and adds to it, as new
files and new entries only, two fixture families (``fixtures/family/``)
with a tiny cell each: ``llama_untied``, whose output head is its own
matrix, and ``llama_recount``, whose work count is not the llama count.
"""

from __future__ import annotations

import copy
import json
import pathlib
import shutil

BENCH = pathlib.Path(__file__).resolve().parents[1]

TINY_LLAMA = {
    "architectures": ["LlamaForCausalLM"], "hidden_size": 64,
    "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 256,
    "hidden_act": "silu", "max_position_embeddings": 64,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": True, "torch_dtype": "bfloat16"}

TINY_TOPOLOGY = {"num_cells": 8, "clients_per_cell": 20, "min_dist_m": 50.0,
                 "max_dist_m": 500.0, "cpu_hz_range": [2e9, 8e9],
                 "samples_range": [16, 64], "max_prune": 0.7}

SEED = 2**31 + 101
TINY_CELLS = ("tiny-llama.tiny-train", "tiny-fleet.tiny-cohort")
LIMITS = {"tiny-llama.tiny-train": "smollm-135m.fleet-train",
          "tiny-fleet.tiny-cohort": "fleet-1m-mlp.sync-cohort"}


def _write(path: pathlib.Path, obj) -> None:
    path.write_text(json.dumps(obj, indent=1))


def make_bench(tmp) -> tuple[pathlib.Path, dict]:
    tmp = pathlib.Path(tmp)
    bench = tmp / "bench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "fixtures"))
    llama = json.loads((bench / "configs" / "smollm-135m.json").read_text())
    llama.update(name="tiny-llama", model=TINY_LLAMA)
    _write(bench / "configs" / "tiny-llama.json", llama)
    fleet = json.loads((bench / "configs" / "fleet-1m-mlp.json").read_text())
    fleet["name"] = "tiny-fleet"
    fleet["fleet"]["topology"] = TINY_TOPOLOGY
    _write(bench / "configs" / "tiny-fleet.json", fleet)

    train = json.loads((bench / "traffic" / "fleet-train.json").read_text())
    train["fleet"]["topology"].update(num_cells=2, clients_per_cell=3)
    train["fleet"]["task"].update(seq_len=16, pool_clients=8)
    _write(bench / "traffic" / "tiny-train.json", train)
    cohort = json.loads((bench / "traffic" / "sync-cohort.json").read_text())
    cohort["fleet"]["schedule"]["participants_per_cell"] = 5
    cohort["fleet"]["engine"].update(control_chunk=3, cell_chunk=3)
    _write(bench / "traffic" / "tiny-cohort.json", cohort)

    for cell, real in LIMITS.items():
        limits = json.loads((bench / "limits" / f"{real}.json").read_text())
        _write(bench / "limits" / f"{cell}.json", limits)

    benchmark = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    benchmark["workloads"] = [
        {"name": c, "config": c.split(".")[0], "traffic": c.split(".")[1],
         "chips": 1, "why": "tiny CPU stand-in"} for c in TINY_CELLS]
    tiny_of = {real: t for t, real in LIMITS.items()}
    for m in benchmark["end_to_end"] + benchmark["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [tiny_of[w] for w in m["workloads"]
                              if w in tiny_of]
    return bench, benchmark


FAMILY = "llama_untied"
FAMILY_CELL = "tiny-untied.tiny-train"
# The fixture cell's limits, from CPU readings of its driver (12 sound
# seeds; 3 seeds each of the control and of the faults): lower x
# (upper / lower) ** 0.6 as the cells' own limits are set.  Its loss reads
# further from the float32 reference than tiny-llama's: the untied head
# (scale hidden ** -0.5, where the tied embedding's is 0.02) gives logits
# of order one, on which the program's bfloat16 hidden state shows.
# deadline_gap read 0 on every sound seed and keeps tiny-llama's limit.
FAMILY_LIMITS = {"init_gap": 0.0, "loss_gap": 0.0215, "grad_gap": 0.0717,
                 "change_gap": 0.178, "rho_gap": 7.0e-05, "per_gap": 7.3e-05,
                 "deadline_gap": 4.53e-05, "bandwidth_gap": 1.64e-03}


RECOUNT = "llama_recount"
RECOUNT_CELL = "tiny-recount.tiny-recount-train"
FIXTURE_CELLS = (FAMILY_CELL, RECOUNT_CELL)


def _add_cell(bench: pathlib.Path, benchmark: dict, family: str, cell: str,
              model: dict, limits: dict, why: str,
              mix: dict | None = None) -> list[pathlib.Path]:
    """A fixture family's two files, a tiny configuration of it, its mix
    where one is given and its cell's limits, written into ``bench`` as new
    files, and the cell as new entries of ``benchmark``: its ``workloads``
    entry and its name appended to each metric's list that holds
    tiny-llama.tiny-train.  Returns the files."""
    added = []
    for kind in ("arch", "reference"):
        added.append(bench / kind / f"{family}.py")
        shutil.copyfile(BENCH / "tests" / "fixtures" / "family" / kind
                        / f"{family}.py", added[-1])
    config_name, mix_name = cell.split(".")
    config = json.loads((bench / "configs" / "tiny-llama.json").read_text())
    config.update(name=config_name,
                  model={**TINY_LLAMA, "model_type": family, **model})
    added.append(bench / "configs" / f"{config_name}.json")
    _write(added[-1], config)
    if mix is not None:
        added.append(bench / "traffic" / f"{mix_name}.json")
        _write(added[-1], mix)
    added.append(bench / "limits" / f"{cell}.json")
    _write(added[-1], limits)
    benchmark["workloads"].append(
        {"name": cell, "config": config_name, "traffic": mix_name,
         "chips": 1, "why": why})
    for m in benchmark["end_to_end"] + benchmark["per_layer"]:
        if "tiny-llama.tiny-train" in m.get("workloads", []):
            m["workloads"].append(cell)
    return added


def add_fixture_cells(tmp) -> tuple[pathlib.Path, dict, dict, list]:
    """``make_bench(tmp)`` with both fixture families' cells added as new
    files and entries: the copy, its ``BENCHMARK.json``-shaped dict before
    and after the additions, and the files added."""
    bench, benchmark = make_bench(tmp)
    before = copy.deepcopy(benchmark)
    added = _add_cell(bench, benchmark, FAMILY, FAMILY_CELL,
                      {"tie_word_embeddings": False}, FAMILY_LIMITS,
                      "tiny CPU stand-in of a family added as files")
    # llama_recount brings a mix of its own, as a model_config change does:
    # tiny-train's under its own name.  Its model and mix are those of
    # tiny-llama.tiny-train, whose limits therefore hold for it.
    read = lambda *p: json.loads(bench.joinpath(*p).read_text())
    added += _add_cell(
        bench, benchmark, RECOUNT, RECOUNT_CELL, {},
        read("limits", "tiny-llama.tiny-train.json"),
        "tiny CPU stand-in of a family that counts its own work",
        mix=read("traffic", "tiny-train.json"))
    return bench, before, benchmark, added


def cell_site(name: str, fixture_copy: tuple) -> tuple[pathlib.Path, dict]:
    """(benchmark directory, ``BENCHMARK.json``-shaped dict) that hold the
    cell ``name``: the repository's for a cell of ``BENCHMARK.json``, the
    copy of ``add_fixture_cells`` for a fixture cell."""
    if name in FIXTURE_CELLS:
        bench, _, benchmark, _ = fixture_copy
        return bench, benchmark
    return BENCH, json.loads((BENCH.parent / "BENCHMARK.json").read_text())


def run_tiny(bench: tuple, name: str, stand_in=None) -> dict:
    """One run of a tiny cell on the CPU, past the harness's look for a
    chip; ``stand_in`` is None (the program), "control" (the reference one
    precision below the configuration's, in the program's place) or a
    ``(precision, fault)`` pair of the cell's driver."""
    import jax
    import calibrate
    import run
    cell = run.Cell(name, *bench)
    if stand_in == "control":
        stand_in = (calibrate.control_precision(cell.config), None)
    return run.run_cell(cell, SEED, 0.3, False, jax.devices()[:1],
                        stand_in=stand_in)
