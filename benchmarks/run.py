"""Benchmark driver: one harness per paper figure/table + kernel micro-
benchmarks + the roofline aggregation.

  PYTHONPATH=src python -m benchmarks.run            # full pass
  PYTHONPATH=src python -m benchmarks.run --quick    # reduced sweep
  PYTHONPATH=src python -m benchmarks.run --only fig2,fig5
"""

from __future__ import annotations

import argparse
import sys
import time
import traceback

from benchmarks import (ablation_noniid, ablation_retx, fig2_cost_vs_power,
                        fig3_cost_vs_modelsize, fig4_lambda_sweep,
                        fig5_accuracy_shallow, fig6_accuracy_dnn,
                        thm1_bound_terms, kernel_bench, roofline_table)

BENCHES = {
    "fig2": fig2_cost_vs_power.run,
    "fig3": fig3_cost_vs_modelsize.run,
    "fig4": fig4_lambda_sweep.run,
    "fig5": fig5_accuracy_shallow.run,
    "fig6": fig6_accuracy_dnn.run,
    "thm1": thm1_bound_terms.run,
    "retx": ablation_retx.run,
    "noniid": ablation_noniid.run,
    "kernels": kernel_bench.run,
    "roofline": roofline_table.run,
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of " + ",".join(BENCHES))
    args = ap.parse_args(argv)

    names = args.only.split(",") if args.only else list(BENCHES)
    failures = []
    for name in names:
        t0 = time.time()
        print(f"\n{'='*72}\nRUN {name}\n{'='*72}")
        try:
            BENCHES[name](quick=args.quick)
            print(f"[{name}] ok in {time.time()-t0:.1f}s")
        except Exception as e:
            traceback.print_exc()
            failures.append((name, repr(e)))
            print(f"[{name}] FAILED: {e}")
    print(f"\n{len(names)-len(failures)}/{len(names)} benchmarks ok")
    for name, err in failures:
        print(f"  FAILED {name}: {err}")
    return 1 if failures else 0


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
