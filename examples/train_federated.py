"""End-to-end driver: the paper's full §V experiment — pruned wireless FL
with the proposed optimizer vs benchmarks, several hundred rounds.

  PYTHONPATH=src python examples/train_federated.py                # shallow net
  PYTHONPATH=src python examples/train_federated.py --dnn          # Fig. 6 model
  PYTHONPATH=src python examples/train_federated.py --scheme gba
  PYTHONPATH=src python examples/train_federated.py --rounds 400 --non-iid 0.5
"""

import argparse

import numpy as np

from repro.federated import system
from repro.models import mlp


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--scheme", default="proposed",
                    choices=["proposed", "gba", "exhaustive", "ideal",
                             "fpr:0.0", "fpr:0.35", "fpr:0.7"])
    ap.add_argument("--rounds", type=int, default=200)
    ap.add_argument("--dnn", action="store_true",
                    help="60+20 hidden DNN (Fig. 6) instead of shallow net")
    ap.add_argument("--lr", type=float, default=5e-3)
    ap.add_argument("--lambda", dest="weight", type=float, default=0.0004)
    ap.add_argument("--non-iid", type=float, default=None,
                    help="Dirichlet alpha for non-IID client data")
    ap.add_argument("--structured", action="store_true",
                    help="TPU block pruning instead of unstructured")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt", default=None, help="save final params here")
    args = ap.parse_args()

    cfg = system.FLConfig(
        rounds=args.rounds, scheme=args.scheme, lr=args.lr,
        hidden=mlp.DNN_HIDDEN if args.dnn else mlp.SHALLOW_HIDDEN,
        weight=args.weight, seed=args.seed,
        non_iid_alpha=args.non_iid, structured=args.structured,
        eval_every=max(args.rounds // 20, 1))
    res = system.run(cfg, progress=True)

    print(f"\nscheme={args.scheme} rounds={args.rounds}")
    print(f"final accuracy : {res.accuracy[-1][1]:.4f}")
    print(f"final loss     : {res.losses[-1]:.4f}")
    print(f"mean latency   : {np.mean(res.latencies)*1e3:.1f} ms/round")
    print(f"mean rho       : {res.prune_rates.mean():.3f}")
    print(f"mean PER       : {res.per_rates.mean():.4f}")
    print(f"Theorem-1 bound: {res.bound_final:.3f}")

    if args.ckpt:
        from repro import checkpoint
        checkpoint.save(args.ckpt, res.params)
        print(f"saved params to {args.ckpt}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
