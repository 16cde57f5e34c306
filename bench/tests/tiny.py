"""Tiny stand-ins of the benchmark's cells, for runs on the CPU.

``make_bench(tmp)`` copies ``bench/`` into ``tmp`` and adds, as new files
only, a tiny llama configuration, a tiny fleet configuration and a mix of
its own, with limits that hold for sound runs at these sizes.  It returns
the copied directory and a ``BENCHMARK.json``-shaped dict naming the tiny
cells.
"""

from __future__ import annotations

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
