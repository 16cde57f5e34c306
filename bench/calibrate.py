"""Readings from which a cell's correctness limits are set.

  python bench/calibrate.py --workload <cell> --seeds 12 --controls 3

One process, one built system.  For each of ``--seeds`` seeds it drives the
timed path as far as the check reaches and prints the readings of every
number compared (the lower readings).  Then, on ``--controls`` seeds each,
it puts the reference in the system's place computed one precision below
the configuration's (``control_precision``), and plants each
fault the cell's driver knows (``FAULTS``): the upper readings.  Each run is
one JSON line on standard output; the last line sums them up per number
(largest of the sound runs, least of each control or fault).  The
benchmark's own runs never run any of this.
"""

from __future__ import annotations

import argparse
import json
import math
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import run  # noqa: E402

def control_precision(config: dict) -> str:
    """The precision one below the configuration's: ``high`` for float32
    products stated at ``HIGHEST``, bfloat16 for other float32, fp8 for
    bfloat16."""
    if "model" in config:
        dtype = config["model"]["torch_dtype"]
    else:
        dtype = config["task"]["dtype"]
    if dtype == "bfloat16":
        return "fp8"
    if config.get("task", {}).get("matmul_precision") == "highest":
        return "high"
    return "bf16"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--base", type=int, default=3_000_000_000)
    ap.add_argument("--seed-list", default="",
                    help="comma-separated seeds of the sound runs, in place "
                         "of --seeds from --base")
    ap.add_argument("--look", type=int, default=0,
                    help="1: print where each sound run's bandwidth_gap "
                         "came from")
    args = ap.parse_args(argv)

    cell = run.Cell(args.workload)
    run.check_devices(cell.chips)
    run.enable_compile_cache()

    drv = cell.driver(args.base)
    drv.setup()
    ctl = control_precision(cell.config)
    seeds = [int(x) for x in args.seed_list.split(",")] if args.seed_list \
        else [args.base + i for i in range(args.seeds)]
    runs = [("program", None, seed) for seed in seeds]
    for j in range(args.controls):
        seed = args.base + 1000 + j
        runs += [("control", (ctl, None), seed)]
        runs += [(f, ("f32", f), seed) for f in cell.driver_module.FAULTS]
    summary: dict = {}
    for kind, stand_in, seed in runs:
        drv.seed = seed
        readings = drv.probe(stand_in)
        look = drv.look() if kind == "program" and args.look else {}
        print(json.dumps({"kind": kind, "seed": seed, **readings,
                          **({"look": look} if look else {})}), flush=True)
        agg = max if kind == "program" else min
        for k, v in readings.items():
            v = math.inf if math.isnan(v) else v
            prev = summary.setdefault(kind, {}).get(k)
            summary[kind][k] = v if prev is None else agg(prev, v)
    print(json.dumps({"summary": summary, "control": ctl,
                      "limits": cell.limits}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
