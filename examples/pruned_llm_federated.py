"""The paper's technique on a transformer: federated pruned training of a
(reduced) assigned architecture through the fleet engine's task protocol.

``TransformerTask`` plugs the causal-LM model into ``run_fleet``, so
every round couples the full stack exactly as a production deployment
would: channel draw -> Algorithm 1 (per-cell closed-form solve, inside
the scan) -> per-client TPU block pruning masks -> masked local grads ->
packet-error-weighted aggregation -> SGD.  Compare
``examples/serve_pruned.py``, which continues this path into
block-sparse serving.

  PYTHONPATH=src python examples/pruned_llm_federated.py --arch smollm-135m
  PYTHONPATH=src python examples/pruned_llm_federated.py \
      --arch olmoe-1b-7b --rounds 20 --dirichlet 0.3
"""

import argparse

import numpy as np

from repro.fleet import FleetConfig, FleetTopology, run_fleet
from repro.fleet.task import TransformerTask


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="smollm-135m",
                    help="assigned architecture (reduced smoke variant)")
    ap.add_argument("--rounds", type=int, default=30)
    ap.add_argument("--cells", type=int, default=2)
    ap.add_argument("--clients-per-cell", type=int, default=8)
    ap.add_argument("--seq", type=int, default=16)
    ap.add_argument("--batch-per-client", type=int, default=2)
    ap.add_argument("--dirichlet", type=float, default=None,
                    help="non-IID token-pool skew alpha (None = IID)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    task = TransformerTask(arch_name=args.arch, seq_len=args.seq,
                           local_batch=args.batch_per_client,
                           dirichlet_alpha=args.dirichlet)
    n = args.cells * args.clients_per_cell
    cfg = FleetConfig(
        topology=FleetTopology(num_cells=args.cells,
                               clients_per_cell=args.clients_per_cell),
        rounds=args.rounds, seed=args.seed, task=task)
    print(f"arch={args.arch} (reduced), clients={n} "
          f"({args.cells} cells x {args.clients_per_cell})")

    res = run_fleet(cfg)
    for rnd in range(0, args.rounds, max(1, args.rounds // 6)):
        print(f"round {rnd:3d} loss={res.losses[rnd]:.4f} "
              f"rho={res.mean_prune[rnd]:.3f} "
              f"arrived={int(res.participants[rnd])}/{n} "
              f"deadline={np.mean(res.deadlines[rnd]) * 1e3:.0f}ms")
    print(f"done; final loss {res.losses[-1]:.4f}, "
          f"simulated wall-clock {res.wall_clock[-1]:.1f}s")
    assert np.all(np.isfinite(res.losses))


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
