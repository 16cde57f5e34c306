"""Theorem 1 — decompose the convergence upper bound into its three terms
for the realized schedules of each scheme, and check the orderings the
theorem predicts (ideal <= proposed <= fpr0.7 on every term)."""

from __future__ import annotations

import numpy as np

from repro.federated import system
from benchmarks import common

SCHEMES = ["ideal", "proposed", "gba", "fpr:0.35", "fpr:0.7"]


def run(rounds: int = 40, quick: bool = False):
    rounds = 15 if quick else rounds
    rows = []
    for scheme in SCHEMES:
        res = system.run(system.FLConfig(rounds=rounds, scheme=scheme,
                                         eval_every=rounds, seed=0))
        from repro.core.convergence import ConvergenceBound, SmoothnessParams
        bound = ConvergenceBound(SmoothnessParams(),
                                 np.asarray([30, 40, 50, 30, 40], np.float64))
        avg_per = res.per_rates.mean(axis=0)
        avg_rho = res.prune_rates.mean(axis=0)
        rows.append([
            scheme,
            bound.initial_term(rounds),
            bound.packet_error_term(avg_per),
            bound.pruning_term(avg_rho),
            res.bound_final,
            float(np.mean(res.latencies)),
        ])
    header = ["scheme", "initial_term", "per_term", "prune_term",
              "total_bound", "mean_latency_s"]
    common.print_table(header, rows, "Theorem 1: realized bound terms")
    common.write_csv("thm1_bound_terms.csv", header, rows)

    by = {r[0]: r for r in rows}
    assert by["ideal"][4] <= by["proposed"][4] <= by["fpr:0.7"][4]
    assert by["ideal"][2] == 0.0 and by["ideal"][3] == 0.0
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
