"""Block-sparse serving throughput: tokens/s decoding a pruned model with
its training tile masks, vs the dense baseline on the same masked params.

The serve layer (``repro/serve``) reuses the fleet's per-leaf block-norm
tile masks at inference: weights are stored as kept-tile stacks, so both
weight memory and decode matmul compute scale with the kept fraction
(1 - rho).  This bench measures what that buys end-to-end — the jitted
``ServeEngine`` continuous-batching scan, greedy decode, host sync
included — sweeping batch x pruning rate x linear impl on a
matmul-bound bench arch (d_model 512, 6 layers).  ``dense`` multiplies
by the masked weights without exploiting sparsity; its tokens/s is the
denominator of the reported speedups.  The acceptance gate is the
``gather`` arm at rho = 0.75, batch 32: >= 1.5x dense tokens/s on CPU.

``--tradeoff`` prices serving into the paper's objective (14a): it
measures per-token latency at rho in {0, 0.75}, fits the latency model
``t(rho) = t0 * (alpha + (1 - alpha)(1 - rho))`` (alpha = the
non-matmul floor: attention, norms, engine bookkeeping), and re-solves
the Table-I trade-off with ``tradeoff.ServingCostModel`` attached.  The
recorded point shows the serving-aware optimum picking a *different*
pruning rate than the uplink-only optimum: once served-token latency is
on the bill, keeping the model dense (or nearly so) stops being free.

``--smoke`` is the CI-sized path: train a 2-round tiny fleet, export the
pruned checkpoint, decode it with ``gather`` and ``dense``, and assert
the logits agree — the full export -> serve round trip as a gate, plus
one tiny timing arm so the artifact is never empty.

  PYTHONPATH=src python -m benchmarks.serve_bench --json     # sized sweep
  PYTHONPATH=src python -m benchmarks.serve_bench --tradeoff --json
  PYTHONPATH=src python -m benchmarks.serve_bench --smoke --json

Writes ``serve_bench.csv`` and, with ``--json``, ``BENCH_serve.json``
(merged arm-wise like ``fleet_bench``; ``check_regression`` diffs
``tokens_per_s`` and the dense-relative speedups).
"""

from __future__ import annotations

import argparse
import json
import math
import os
import time

import jax
import numpy as np

from benchmarks import common
from benchmarks.fleet_bench import env_metadata
from repro.configs.base import ArchConfig, BlockSpec, StageSpec
from repro.core import tradeoff
from repro.fleet import FleetConfig, FleetTopology, run_fleet
from repro.fleet.task import TransformerTask
from repro.serve import (ServeConfig, ServeEngine, SparseModel,
                         export_from_result, load_pruned, make_bundle)

JSON_NAME = "BENCH_serve.json"

# mirror of check_regression.ARM_KEYS (serve rows: mode="serve",
# fleet-only keys None; fleet rows: serve-only keys None)
_ARM_KEYS = ("mode", "kernel", "clients", "buffer", "cohort",
             "batch", "rho", "impl")


def bench_arch(d_model: int = 512) -> ArchConfig:
    """Matmul-bound bench model: per-step decode compute is dominated by
    the prunable projections (qkvo + MLP + tied unembed), so tile
    skipping has something to win."""
    return ArchConfig(
        name=f"serve-bench-{d_model}", family="dense", source="bench",
        d_model=d_model, num_heads=8, num_kv_heads=4, d_ff=4 * d_model,
        vocab_size=8192,
        stages=(StageSpec(6, (BlockSpec("attn", "mlp"),)),))


def tiny_arch() -> ArchConfig:
    return ArchConfig(
        name="serve-smoke", family="dense", source="bench",
        d_model=32, num_heads=4, num_kv_heads=2, d_ff=64, vocab_size=64,
        stages=(StageSpec(2, (BlockSpec("attn", "mlp"),)),))


def _time_generate(eng: ServeEngine, prompts: np.ndarray,
                   repeats: int) -> tuple[float, float]:
    """(compile seconds, best-of-``repeats`` warm seconds) for one
    ``generate`` call — jitted scan + host sync, the serving unit of
    work."""
    t0 = time.perf_counter()
    eng.generate(prompts)                       # compile + run
    cold = time.perf_counter() - t0
    warm = math.inf
    for _ in range(max(repeats, 1)):
        t0 = time.perf_counter()
        eng.generate(prompts)
        warm = min(warm, time.perf_counter() - t0)
    return cold - warm, warm


def bench_decode(task, params, *, rho: float, impl: str, batch: int,
                 gen: int = 32, repeats: int = 3, seed: int = 0) -> dict:
    """One serving arm: greedy-decode ``gen`` tokens for ``batch``
    length-1 prompts (every scan step is decode-shaped, so tokens/s is a
    pure decode number)."""
    arch = task.config()
    bundle = make_bundle(task, params, rho)
    model = SparseModel(arch, bundle, impl=impl, attn_impl="xla")
    eng = ServeEngine(model, ServeConfig(max_slots=batch,
                                         page_len=2 * gen, max_new=gen))
    prompts = np.random.RandomState(seed).randint(
        0, arch.vocab_size, (batch, 1)).astype(np.int32)
    compile_s, warm = _time_generate(eng, prompts, repeats)
    return {
        "mode": "serve",
        "impl": impl,
        "batch": batch,
        "rho": rho,
        "gen": gen,
        "d_model": arch.d_model,
        "layers": arch.num_layers,
        "compile_s": compile_s,
        "run_s": warm,
        "tokens_per_s": batch * gen / warm,
    }


def _speedups(records: list[dict]) -> list[dict]:
    """Sparse-impl-over-dense tokens/s ratio per (batch, rho)."""
    by_key = {}
    for r in records:
        if r.get("mode") != "serve":
            continue
        by_key.setdefault((r["batch"], r["rho"]), {})[r["impl"]] = r
    out = []
    for (batch, rho), arms in sorted(by_key.items()):
        if "dense" not in arms:
            continue
        for impl, r in sorted(arms.items()):
            if impl == "dense":
                continue
            out.append({
                "batch": batch,
                "rho": rho,
                "impl": impl,
                "speedup": r["tokens_per_s"]
                / arms["dense"]["tokens_per_s"],
            })
    return out


def run_sweep(batches: list[int], rhos: list[float], impls: list[str],
              gen: int, repeats: int, d_model: int) -> list[dict]:
    task = TransformerTask(arch=bench_arch(d_model), target_tiles=8)
    params = task.init_params(jax.random.PRNGKey(0))
    header = ["mode", "impl", "batch", "rho", "gen", "d_model", "layers",
              "compile_s", "run_s", "tokens_per_s"]
    rows, records = [], []
    for batch in batches:
        for rho in rhos:
            for impl in impls:
                r = bench_decode(task, params, rho=rho, impl=impl,
                                 batch=batch, gen=gen, repeats=repeats)
                records.append(r)
                rows.append([r[h] for h in header])
                print(f"{impl:>7s} batch={batch:>3d} rho={rho:.2f} "
                      f"compile={r['compile_s']:5.1f}s "
                      f"run={r['run_s']:6.2f}s "
                      f"{r['tokens_per_s']:9.0f} tok/s")
    for s in _speedups(records):
        print(f"  {s['impl']}/dense @ batch={s['batch']:>3d} "
              f"rho={s['rho']:.2f}: {s['speedup']:.2f}x")
    path = common.write_csv("serve_bench.csv", header, rows)
    print(f"wrote {path}")
    return records


# ---------------------------------------------------------------------------
# --tradeoff: price measured serving latency into objective (14a)
# ---------------------------------------------------------------------------

def fit_alpha(t0: float, t075: float) -> float:
    """Latency-floor fraction of ``t(rho) = t0 (alpha + (1-alpha)(1-rho))``
    from per-token measurements at rho = 0 and rho = 0.75."""
    return float(np.clip((t075 / t0 - 0.25) / 0.75, 0.0, 1.0))


def run_tradeoff(gen: int, repeats: int, d_model: int, batch: int,
                 weight: float, tokens_per_round: float,
                 serve_weight: float) -> dict:
    """Measure the serving latency curve, fit the cost model, and show the
    serving-aware optimum moving off the uplink-only one.

    ``weight`` is the paper's lambda; the default 0.01 sits where the
    uplink-only solve keeps the model dense (communication is cheap
    enough that pruning only hurts convergence), which is exactly where
    serving cost — linear in kept weights — changes the answer.
    """
    task = TransformerTask(arch=bench_arch(d_model), target_tiles=8)
    params = task.init_params(jax.random.PRNGKey(0))
    arms = {rho: bench_decode(task, params, rho=rho, impl="gather",
                              batch=batch, gen=gen, repeats=repeats)
            for rho in (0.0, 0.75)}
    t0 = 1.0 / arms[0.0]["tokens_per_s"]
    t075 = 1.0 / arms[0.75]["tokens_per_s"]
    alpha = fit_alpha(t0, t075)
    serving = tradeoff.ServingCostModel(
        base_latency_s=t0, overhead_frac=alpha,
        tokens_per_round=tokens_per_round, weight=serve_weight)

    prob = common.build_problem(seed=0, weight=weight)
    plain = tradeoff.solve_alternating(prob)
    priced = tradeoff.solve_alternating(prob, serving=serving)
    rec = {
        "d_model": d_model,
        "batch": batch,
        "lambda": weight,
        "tokens_per_round": tokens_per_round,
        "serve_weight": serve_weight,
        "measured_t0_s": t0,
        "measured_t075_s": t075,
        "alpha": alpha,
        "plain_rho_mean": float(np.mean(plain.prune)),
        "plain_deadline_s": float(plain.deadline),
        "serving_rho_mean": float(np.mean(priced.prune)),
        "serving_deadline_s": float(priced.deadline),
        "serving_cost_s": serving.cost(priced.prune),
    }
    print(f"per-token latency: rho=0 {t0 * 1e3:.3f} ms, "
          f"rho=0.75 {t075 * 1e3:.3f} ms  -> alpha={alpha:.3f}")
    print(f"lambda={weight}: uplink-only rho_mean="
          f"{rec['plain_rho_mean']:.3f} (deadline "
          f"{rec['plain_deadline_s']:.3f}s) | serving-aware rho_mean="
          f"{rec['serving_rho_mean']:.3f} (deadline "
          f"{rec['serving_deadline_s']:.3f}s)")
    if abs(rec["serving_rho_mean"] - rec["plain_rho_mean"]) < 1e-6:
        print("WARNING: serving term did not move the optimum "
              "(raise --tokens-per-round or pick a lambda where the "
              "uplink-only solve stays dense)")
    return rec


# ---------------------------------------------------------------------------
# --smoke: the CI round trip (fleet export -> block-sparse decode)
# ---------------------------------------------------------------------------

def run_smoke(tmpdir: str, repeats: int) -> list[dict]:
    """Train 2 fleet rounds on the tiny LM, export the pruned bundle,
    decode it sparse and dense, assert the logits agree, and time one
    tiny arm pair so the smoke artifact still carries a speedup row."""
    arch = tiny_arch()
    task = TransformerTask(arch=arch, target_tiles=4, seq_len=8,
                           local_batch=1, eval_batch=4)
    cfg = FleetConfig(
        topology=FleetTopology(num_cells=2, clients_per_cell=4),
        rounds=2, task=task)
    res = run_fleet(cfg)
    path = os.path.join(tmpdir, "smoke_bundle.npz")
    export_from_result(path, task, res, rho=0.5)
    bundle = load_pruned(path, task)

    prompts = np.random.RandomState(0).randint(
        0, arch.vocab_size, (8, 4)).astype(np.int32)
    outs = {}
    for impl in ("gather", "dense"):
        model = SparseModel(arch, bundle, impl=impl, attn_impl="xla")
        eng = ServeEngine(model, ServeConfig(max_slots=8, page_len=32,
                                             max_new=8))
        outs[impl] = eng.generate(prompts, return_logits=True)
    tok_g, log_g = outs["gather"]
    tok_d, log_d = outs["dense"]
    np.testing.assert_allclose(log_g, log_d, rtol=2e-4, atol=2e-4)
    assert np.array_equal(tok_g, tok_d), "sparse/dense decode diverged"
    print("smoke: export -> block-sparse decode matches dense "
          f"(8 prompts x 8 tokens, rho=0.5, |dlogits| "
          f"<= {np.max(np.abs(log_g - log_d)):.2e})")

    params = task.init_params(jax.random.PRNGKey(0))
    records = [bench_decode(task, params, rho=0.5, impl=impl, batch=4,
                            gen=16, repeats=repeats)
               for impl in ("gather", "dense")]
    for r in records:
        r["mode"] = "serve-smoke"       # never collides with sized arms
        print(f"smoke {r['impl']:>7s} {r['tokens_per_s']:9.0f} tok/s")
    return records


def write_json(records: list[dict], path: str | None = None,
               tradeoff_rec: dict | None = None,
               merge: bool = True) -> str:
    os.makedirs(common.RESULTS_DIR, exist_ok=True)
    path = path or os.path.join(common.RESULTS_DIR, JSON_NAME)
    if merge and os.path.exists(path):
        with open(path) as f:
            old = json.load(f)
        fresh = {tuple(r.get(k) for k in _ARM_KEYS) for r in records}
        kept = [r for r in old.get("results", [])
                if tuple(r.get(k) for k in _ARM_KEYS) not in fresh]
        records = kept + records
        if tradeoff_rec is None:
            tradeoff_rec = old.get("tradeoff")
    doc = {
        "schema": "serve_bench/v1",
        "created_unix": time.time(),
        "backend": jax.default_backend(),
        "cpu_count": os.cpu_count(),
        "env": env_metadata(),
        "results": records,
        "serve_speedups": _speedups(records),
    }
    if tradeoff_rec:
        doc["tradeoff"] = tradeoff_rec
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
    return path


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--batch", default="8,32",
                    help="comma-separated decode batch sizes")
    ap.add_argument("--rho", default="0,0.5,0.75,0.9",
                    help="comma-separated pruning rates")
    ap.add_argument("--impl", default="dense,gather",
                    help="comma-separated linear impls "
                         "(dense,gather,cond,pallas)")
    ap.add_argument("--gen", type=int, default=32,
                    help="greedy-decoded tokens per request")
    ap.add_argument("--d-model", type=int, default=512,
                    help="bench arch width (256-512 is matmul-bound)")
    ap.add_argument("--tradeoff", action="store_true",
                    help="measure the latency curve and price it into "
                         "the (14a) solve (ServingCostModel)")
    ap.add_argument("--lambda", dest="lam", type=float, default=0.01,
                    help="--tradeoff: paper lambda for the solved "
                         "instance")
    ap.add_argument("--tokens-per-round", type=float, default=20000.0,
                    help="--tradeoff: served tokens amortized per round")
    ap.add_argument("--serve-weight", type=float, default=1.0,
                    help="--tradeoff: serving-term weight")
    ap.add_argument("--repeats", type=int, default=3,
                    help="warm generate() calls per arm; best is kept")
    ap.add_argument("--json", nargs="?", const="", default=None,
                    metavar="PATH",
                    help=f"write {JSON_NAME} (default under "
                         "benchmarks/results/; merges arm-wise)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized: 2-round fleet export -> sparse==dense "
                         "decode gate + one tiny timing arm pair")
    args = ap.parse_args()

    emit_json = args.json is not None
    json_path = args.json or None

    if args.smoke:
        import tempfile
        with tempfile.TemporaryDirectory() as d:
            records = run_smoke(d, args.repeats)
        if emit_json:
            print(f"wrote {write_json(records, json_path)}")
        return

    tradeoff_rec = None
    records = []
    if args.tradeoff:
        tradeoff_rec = run_tradeoff(
            args.gen, args.repeats, args.d_model, batch=32,
            weight=args.lam, tokens_per_round=args.tokens_per_round,
            serve_weight=args.serve_weight)
    else:
        records = run_sweep([int(b) for b in args.batch.split(",")],
                            [float(r) for r in args.rho.split(",")],
                            args.impl.split(","),
                            args.gen, args.repeats, args.d_model)
    if emit_json:
        print(f"wrote {write_json(records, json_path, tradeoff_rec)}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
