"""Chip smoke: the repo's main path on a TPU, at smollm-135m's published widths.

  python chip_smoke.py             # one chip: device, train, serve, kernels
  python chip_smoke.py --chips 4   # four chips: a fleet round sharded over a
                                   # ("cells", "data") mesh vs one device

One chip, four phases, one JSON line each:

* device  -- refuse anything but a TPU; name the chip and the compile cache.
* train   -- ``run_fleet`` (sync, reference kernel) on smollm-135m at its
             published widths: 2 cells x 4 clients, 256-token sequences,
             3 rounds.  Losses must be finite and fall.
* serve   -- ``export_from_result`` -> ``load_pruned`` -> ``SparseModel`` ->
             ``ServeEngine``: 4 prompts x 32 tokens, 32 new tokens, with the
             tile-skipping ``gather`` layers and with ``dense`` ones.  The
             tokens must be identical.
* kernels -- every Pallas kernel of the main path, compiled for the chip
             (``interpret=False``), against its oracle in ``kernels/ref.py``.

``--chips 4`` runs only the sharded fleet round (about 100k clients,
orthogonal cells, a 10% cohort per cell) and the same run on the first
device alone; they must agree within the cohort-equivalence tolerance and
the population must be split over all four devices.

The last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": n}}``.
Any failure exits non-zero before that line is printed.  Timings here are
smoke readings of one run, not benchmark numbers.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

SEED = 0
# train: 2 cells x 4 clients, 2 sequences of 256 tokens each, 3 rounds
TRAIN_SEQ_LEN, TRAIN_ROUNDS = 256, 3
# serve: 4 prompts of 32 tokens, 32 new tokens each
SERVE_BATCH, PROMPT_LEN, NEW_TOKENS = 4, 32, 32
# --chips 4: CI's 100k-client cohort smoke, 3 rounds
COHORT_CLIENTS, COHORT_ROUNDS = 100_000, 3
BUNDLE = ROOT / ".serve_bundle" / "smollm-135m.npz"
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# cohort-equivalence tolerance (tests/test_cohort_equivalence.py)
EQUIV_RTOL, EQUIV_ATOL = 1e-6, 1e-9


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


class CompileClock:
    """Seconds JAX spends in backend compilation (persistent-cache reads
    included) while the block is open."""

    def __init__(self):
        self.seconds = 0.0

    def _listen(self, event, duration, **_):
        if event == BACKEND_COMPILE_EVENT:
            self.seconds += duration

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._listen)


def peak_bytes() -> int | None:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_device(chips: int) -> dict:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX platform is "
                 f"{devs[0].platform!r}); nothing was run")
    if len(devs) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} devices, "
                 f"JAX sees {len(devs)}")
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    emit("device", **device, jax=jax.__version__, compile_cache=cache)
    return device


def train_config(arch):
    from repro.fleet import FleetConfig, FleetTopology, TransformerTask
    task = TransformerTask(arch=arch, seq_len=TRAIN_SEQ_LEN, local_batch=2)
    # lr: the transformer task's learning rate in examples/fleet_sim.py
    return FleetConfig(
        topology=FleetTopology(num_cells=2, clients_per_cell=4),
        rounds=TRAIN_ROUNDS, seed=SEED, lr=0.5, task=task)


def phase_train(cfg):
    from repro.fleet import build_simulation, run_fleet
    t0 = time.perf_counter()
    with CompileClock() as clock:
        res = run_fleet(cfg)
    first_s = time.perf_counter() - t0
    losses = np.asarray(res.losses, np.float64)
    assert np.all(np.isfinite(losses)), f"non-finite losses {losses}"
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"

    # warm rounds: the same program again, compiled (or read back from
    # the compile cache) before the clock starts
    sim = build_simulation(cfg)
    run = sim.simulate.lower(sim.params, sim.round_keys).compile()
    jax.block_until_ready(run(sim.params, sim.round_keys))
    t0 = time.perf_counter()
    jax.block_until_ready(run(sim.params, sim.round_keys))
    warm_round_s = (time.perf_counter() - t0) / cfg.rounds
    # what XLA planned for this very executable, beside what the
    # allocator saw at its peak
    mem = run.memory_analysis()
    planned = {f: getattr(mem, f"{f}_size_in_bytes") for f in
               ("argument", "output", "alias", "temp", "generated_code")}
    emit("train", losses=losses.tolist(),
         mean_prune=np.asarray(res.mean_prune).tolist(),
         compile_s=clock.seconds, first_call_s=first_s,
         warm_round_s=warm_round_s, planned_bytes=planned,
         peak_bytes_in_use=peak_bytes())
    return res


def phase_serve(cfg, res):
    from repro.serve import (ServeConfig, ServeEngine, SparseModel,
                             export_from_result, load_pruned)
    task = cfg.task
    bundle = export_from_result(str(BUNDLE), task, res)
    arch = task.config()
    prompts = np.random.RandomState(SEED).randint(
        0, arch.vocab_size, (SERVE_BATCH, PROMPT_LEN)).astype(np.int32)
    scfg = ServeConfig(max_slots=SERVE_BATCH,
                       page_len=PROMPT_LEN + NEW_TOKENS, max_new=NEW_TOKENS)
    out = {}
    # the matmul precision is left at the chip's default: the serving
    # layers set their own (serve/sparse.py)
    for impl in ("gather", "dense"):
        eng = ServeEngine(SparseModel(arch, load_pruned(str(BUNDLE), task),
                                      impl=impl), scfg)
        t0 = time.perf_counter()
        with CompileClock() as clock:
            toks = eng.generate(prompts)
        first_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        toks = eng.generate(prompts)
        warm_s = time.perf_counter() - t0
        out[impl] = dict(tokens=toks, compile_s=clock.seconds,
                         first_call_s=first_s,
                         tokens_per_s=SERVE_BATCH * NEW_TOKENS / warm_s)
    g, d = out["gather"]["tokens"], out["dense"]["tokens"]
    emit("serve", rho=bundle.rho, tokens_differing=int(np.sum(g != d)),
         **{f"{impl}_{k}": v for impl, r in out.items()
            for k, v in r.items() if k != "tokens"},
         peak_bytes_in_use=peak_bytes())
    assert g.shape == (SERVE_BATCH, NEW_TOKENS), g.shape
    assert np.array_equal(g, d), "block-sparse decode diverged from dense"


def phase_kernels(arch) -> None:
    """Every kernel and its oracle run at full float32 precision (the
    kernels set it on their dots, the oracles under a float32 default), so
    they differ in summation order and in the chip's exp/log; one bf16
    pass would miss these bounds more than tenfold."""
    from repro.kernels import fleet_fused as FF
    from repro.kernels import ops, ref
    from repro.models import mlp
    d, dff = arch.d_model, arch.d_ff
    h, hkv = arch.num_heads, arch.num_kv_heads
    hd = d // h
    keys = iter(jax.random.split(jax.random.PRNGKey(SEED), 32))

    def normal(*shape):
        return jax.random.normal(next(keys), shape)

    checks = []                            # (name, got, want, rtol, atol)
    hi = jax.default_matmul_precision("float32")

    # block-sparse matmul: MXU tiles and the trained smollm 576x1536 grid
    # (72x192 tiles, padded to the hardware tiling inside ops)
    x, w = normal(256, d), 0.05 * normal(d, dff)
    for bk, bn in ((128, 128), (-(-d // 8), -(-dff // 8))):
        mask = (jax.random.uniform(next(keys), (-(-d // bk), -(-dff // bn)))
                > 0.4).astype(jnp.float32)
        xt = normal(256, dff)
        with hi:
            want = ref.block_sparse_matmul(x, w, mask, bk, bn)
            want_t = ref.block_sparse_matmul_t(xt, w, mask, bk, bn)
        wp = jnp.pad(w, ((0, (-d) % bk), (0, (-dff) % bn)))
        checks += [
            (f"masked_matmul_{bk}x{bn}",
             ops.masked_matmul(x, w, mask, block_k=bk, block_n=bn,
                               interpret=False), want, 1e-5, 1e-4),
            (f"masked_matmul_t_{bk}x{bn}",
             ops.masked_matmul(xt, w, mask, block_k=bk, block_n=bn,
                               transpose_rhs=True, interpret=False),
             want_t, 1e-5, 1e-4),
            (f"tile_norms_{bk}x{bn}",
             ops.tile_norms(w, bk, bn, interpret=False),
             ref.block_norms(wp, bk, bn), 1e-5, 1e-6)]

    # attention at smollm's head layout (9 q heads, 3 KV heads, hd 64),
    # K/V head-major as the serving cache holds them
    b, s = 4, 512
    q, k, v = normal(b, h, hd), normal(b, hkv, s, hd), normal(b, hkv, s, hd)
    pos = jnp.asarray([0, 100, 511, 300], jnp.int32)
    head_mask = np.asarray([1, 0, 1][:hkv] + [1] * max(0, hkv - 3),
                           np.float32)
    qp, kp, vp = normal(2, 256, h, hd), normal(2, hkv, 256, hd), \
        normal(2, hkv, 256, hd)
    with hi:
        want = ref.decode_attention(q, k, v, pos, head_mask=head_mask)
        want_p = ref.prefill_attention(qp, kp, vp, causal=True)
    checks += [
        ("flash_decode", ops.flash_decode(q, k, v, pos, head_mask=head_mask,
                                          interpret=False), want, 1e-4, 1e-5),
        ("flash_prefill", ops.flash_prefill(qp, kp, vp, causal=True,
                                            block_q=128, block_s=128,
                                            interpret=False),
         want_p, 1e-4, 1e-5)]

    # the fused fleet-gradient kernel at the default MLP task's shapes
    # (32 -> 16 -> 4, 8x8 tiles, 13 clients x 8 samples)
    params = mlp.init_mlp_classifier(next(keys), 32, (16,), 4)
    c = 13
    xc = normal(c, 8, 32)
    yc = jax.random.randint(next(keys), (c, 8), 0, 4)
    rho = jax.random.uniform(next(keys), (c,)) * 0.7
    wts = jax.random.uniform(next(keys), (c,)) * 50
    keeps = FF.layer_keeps(FF.layer_norm_states(params, 8), rho)
    g_pl, l_pl = FF.fused_fleet_grads(params, xc, yc, keeps, wts, 8,
                                      impl="pallas", interpret=False)
    with hi:
        g_ref, l_ref = FF.reference_grads(params, xc, yc, rho, wts, 8)
    # bound: 1e-4 of each leaf's largest entry.  The fused paths form
    # p = exp(log_softmax(z)) and so carry the chip's f32 log error: on a
    # v5e the kernel and its XLA twin alike sit 1.7e-5 to 3.2e-5 of it from
    # a float64 host oracle, the f32 oracle (which differentiates
    # logsumexp and takes no log) 5e-7, one bf16 pass 1.3e-3 to 5.8e-3.
    for (path, a), r in zip(jax.tree_util.tree_leaves_with_path(g_pl),
                            jax.tree.leaves(g_ref)):
        scale = float(np.max(np.abs(np.asarray(r)))) or 1.0
        checks.append((f"fused_fleet_grads{jax.tree_util.keystr(path)}",
                       a, r, 1e-4, 1e-4 * scale))
    checks.append(("fused_fleet_losses", l_pl, l_ref, 1e-5, 1e-5))

    errs, worst = {}, {}
    for name, got, want, rtol, atol in checks:
        got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
        assert got.shape == want.shape, (name, got.shape, want.shape)
        diff = np.abs(got - want)
        errs[name] = float(np.max(diff))
        # 1.0 is the bound: |got - want| <= atol + rtol * |want|
        worst[name] = float(np.max(diff / (atol + rtol * np.abs(want))))
    emit("kernels", max_abs_err=errs, worst_over_bound=worst)
    bad = {n: r for n, r in worst.items() if not r <= 1.0}
    assert not bad, f"kernels off their oracles (x bound): {bad}"


def cohort_config():
    """The orthogonal-cell sync cohort round of CI's 100k-client smoke
    (benchmarks/fleet_bench.py ``bench_cohort``): cells of 250 clients,
    a uniform 10% cohort per cell, reference kernel."""
    from repro.fleet import FleetConfig, FleetTopology, ScheduleConfig
    per_cell = 250
    cells = COHORT_CLIENTS // per_cell
    m = per_cell // 10
    return FleetConfig(
        topology=FleetTopology(num_cells=cells, clients_per_cell=per_cell),
        schedule=ScheduleConfig(participation="uniform",
                                participants_per_cell=m),
        rounds=COHORT_ROUNDS, seed=SEED, cohort_gather=True,
        cell_chunk=max(1, min(cells, 4096 // m)))


def phase_mesh(cfg, chips: int) -> None:
    from repro.fleet import build_simulation, run_fleet
    from repro.launch.mesh import make_fleet_mesh
    devs = jax.devices()[:chips]
    mesh = make_fleet_mesh()
    assert mesh.devices.size == chips, mesh

    # full float32 matmuls on both sides: the two runs then differ only in
    # how the sharded reductions associate
    hi = jax.default_matmul_precision("float32")
    t0 = time.perf_counter()
    with CompileClock() as clock, hi:
        sim = build_simulation(cfg, mesh=mesh)
        carry, metrics = sim.simulate(sim.params, sim.round_keys)
        jax.block_until_ready(metrics)
    sharded_s = time.perf_counter() - t0
    sharded = sim.finalize(carry, metrics)

    # the population is placed P("cells", "data"): every device must hold
    # its own quarter, not a copy of the whole
    pop = sim.num_samples
    c, i = pop.shape
    want_shape = (c // mesh.shape["cells"], i // mesh.shape["data"])
    shards = pop.addressable_shards
    shard_shapes = sorted({tuple(s.data.shape) for s in shards})
    shard_devs = {s.device for s in shards}
    assert shard_shapes == [want_shape], (shard_shapes, want_shape)
    assert shard_devs == set(devs), shard_devs
    deadline = metrics["deadline"]
    deadline_shards = sorted({tuple(s.data.shape)
                              for s in deadline.addressable_shards})

    t0 = time.perf_counter()
    with jax.default_device(devs[0]), hi:
        single = run_fleet(cfg)
    single_s = time.perf_counter() - t0

    diffs = {}
    for key in ("losses", "mean_prune"):
        a = np.asarray(getattr(sharded, key), np.float64)
        b = np.asarray(getattr(single, key), np.float64)
        assert np.all(np.isfinite(a)), (key, a)
        np.testing.assert_allclose(a, b, rtol=EQUIV_RTOL, atol=EQUIV_ATOL,
                                   err_msg=key)
        diffs[key] = float(np.max(np.abs(a - b)))
    emit("mesh", mesh=dict(mesh.shape), clients=cfg.topology.num_clients,
         cohort_m=cfg.schedule.participants_per_cell,
         population_shard=list(want_shape), deadline_shards=deadline_shards,
         sharded_first_call_s=sharded_s, sharded_compile_s=clock.seconds,
         single_first_call_s=single_s, losses=np.asarray(
             sharded.losses).tolist(), max_abs_diff=diffs)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    device = phase_device(args.chips)
    if args.chips == 1:
        from repro.configs import get_config
        arch = get_config("smollm-135m")          # published widths
        cfg = train_config(arch)
        res = phase_train(cfg)
        phase_serve(cfg, res)
        phase_kernels(arch)
    else:
        phase_mesh(cohort_config(), args.chips)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
