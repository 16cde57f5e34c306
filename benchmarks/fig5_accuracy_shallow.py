"""Fig. 5 — test accuracy of the shallow net (one 60-neuron hidden layer)
under pruned wireless FL, per scheme.

Paper ordering: ideal >= fpr0.0 >= proposed > fpr0.7 (high pruning hurts).
MNIST is replaced by the seeded synthetic dataset (offline container);
orderings reproduce, absolute accuracies differ — recorded in DESIGN.md §5.
"""

from __future__ import annotations

import numpy as np

from repro.federated import system
from repro.models import mlp
from benchmarks import common

SCHEMES = ["ideal", "fpr:0.0", "proposed", "fpr:0.35", "fpr:0.7"]


def run(rounds: int = 200, quick: bool = False, lr: float = 5e-3,
        hidden=mlp.SHALLOW_HIDDEN, csv_name: str = "fig5_accuracy_shallow.csv",
        title: str = "Fig. 5: accuracy, shallow net"):
    rounds = 60 if quick else rounds
    schemes = SCHEMES[:3] + SCHEMES[4:] if quick else SCHEMES
    curves = {}
    for scheme in schemes:
        res = system.run(system.FLConfig(
            rounds=rounds, scheme=scheme, hidden=hidden, lr=lr,
            eval_every=max(rounds // 10, 1), seed=1))
        curves[scheme] = res.accuracy
    # rows: one per eval round
    evals = [r for r, _ in curves[schemes[0]]]
    rows = []
    for i, rnd in enumerate(evals):
        rows.append([rnd] + [curves[s][i][1] for s in schemes])
    header = ["round"] + list(schemes)
    common.print_table(header, rows, title)
    common.write_csv(csv_name, header, rows)

    final = {s: curves[s][-1][1] for s in schemes}
    assert final["ideal"] >= final["fpr:0.7"] - 0.02, \
        "ideal FL must match/beat heavy pruning"
    assert final["proposed"] >= final["fpr:0.7"] - 0.02
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
