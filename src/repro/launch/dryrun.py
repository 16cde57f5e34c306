import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape)
on the production mesh, prove it fits, and extract roofline terms.

The two lines above MUST stay the first statements in this module: jax
locks the device count on first init, and the dry-run needs 512 host
placeholder devices for the 2x16x16 multi-pod mesh.  Do not set that flag
anywhere global — smoke tests and benches see 1 device.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--fl]
  ... --out benchmarks/results   # one JSON per combo for §Roofline
"""

import argparse
import json
import sys
import time
import traceback

import jax

from repro.configs import ARCH_NAMES, INPUT_SHAPES, get_config
from repro.launch import hlo_cost as HC
from repro.launch import mesh as MESH
from repro.launch import roofline as RF
from repro.launch import shardings as SH
from repro.launch import steps as ST
from repro.models import sharding as MS


# The chip the production meshes plan for, as a described v5e topology
# reports its ``device_kind``; roofline terms use its peaks.
PLANNED_DEVICE_KIND = "TPU v5 lite"


def mesh_tag(multi_pod: bool) -> str:
    return "2x16x16" if multi_pod else "16x16"


def dryrun_one(arch: str, shape_name: str, multi_pod: bool = False,
               fl: bool = False, verbose: bool = True,
               sharding_overrides: dict | None = None):
    """Lower + compile one combo; returns a RooflineReport (or None if the
    shape is skipped for this arch, e.g. long_500k on whisper)."""
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    if not ST.shape_supported(cfg, shape):
        if verbose:
            print(f"SKIP {arch} x {shape_name}: unsupported "
                  f"(full-attention arch without long-context variant)")
        return None

    mesh = MESH.make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    rules = dict(MS.DEFAULT_RULES)
    if sharding_overrides:
        rules.update(sharding_overrides)

    with mesh, MS.use_rules(rules, mesh):
        if fl:
            spec = _fl_spec(cfg, shape, mesh)
        else:
            spec = ST.input_specs(cfg, shape, mesh)
        jitted = jax.jit(spec["step"],
                         in_shardings=spec["in_shardings"],
                         out_shardings=spec["out_shardings"])
        lowered = jitted.lower(*spec["args"])
        compiled = lowered.compile()

    wall = time.time() - t0
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    # loop-aware counters: XLA's cost_analysis counts while bodies ONCE;
    # hlo_cost re-derives flops/bytes/collective bytes with trip counts
    hc = HC.hlo_cost(compiled.as_text(),
                     default_group=int(mesh.devices.size))

    params_shape = spec["args"][0]
    n_active = RF.active_param_count(cfg, params_shape)

    report = RF.RooflineReport(
        arch=arch, shape=shape_name, mesh=mesh_tag(multi_pod),
        chips=mesh.devices.size,
        flops_per_chip=float(hc.flops),
        bytes_per_chip=float(hc.hbm_bytes),
        collective_bytes_per_chip=float(hc.collective_bytes),
        peak_memory_per_chip=float(getattr(mem, "peak_memory_in_bytes", 0)
                                   or _mem_total(mem)),
        argument_bytes=float(getattr(mem, "argument_size_in_bytes", 0)),
        output_bytes=float(getattr(mem, "output_size_in_bytes", 0)),
        temp_bytes=float(getattr(mem, "temp_size_in_bytes", 0)),
        collectives={op: {"count": float(hc.collective_counts[op]),
                          "bytes": float(hc.collective_op_bytes[op])}
                     for op in hc.collective_counts},
        model_flops=RF.model_flops(cfg, shape, n_active),
        wall_s=wall,
        device_kind=PLANNED_DEVICE_KIND,
        raw_xla_flops=float(cost.get("flops", 0.0)),
        raw_xla_bytes=float(cost.get("bytes accessed", 0.0)),
    )
    if verbose:
        print(f"OK   {report.row()}  ({wall:.1f}s compile)")
    return report


def _mem_total(mem) -> int:
    return (getattr(mem, "argument_size_in_bytes", 0)
            + getattr(mem, "output_size_in_bytes", 0)
            + getattr(mem, "temp_size_in_bytes", 0)
            + getattr(mem, "generated_code_size_in_bytes", 0))


def _fl_spec(cfg, shape, mesh) -> dict:
    """Dry-run spec for the distributed pruned-FL step (paper technique
    on the production mesh): clients on ("pod","data"), model on "model"."""
    from repro.federated import trainer as FT
    from repro.models import model as M
    import functools

    client_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    n = FT.num_clients(mesh, client_axes)
    per_client = max(shape.global_batch // n, 1)
    step = FT.make_fl_train_step(cfg, mesh, client_axes=client_axes)

    params_shape = jax.eval_shape(
        functools.partial(M.init_params, cfg), jax.random.PRNGKey(0))
    batch, vec, _shardings = FT.fl_input_specs(cfg, mesh, client_axes,
                                               per_client, shape.seq_len)
    return {
        "step": step,
        "args": (params_shape, batch, vec, vec, vec),
        # shard_map's jit wrapper takes shardings from in_specs; the
        # explicit NamedShardings from fl_input_specs are for callers
        # that device_put real arrays before invoking the step
        "in_shardings": None,
        "out_shardings": None,
    }


def fleet_dryrun(verbose: bool = True) -> dict:
    """Multi-host fleet dry-run: the cohort-sharded fleet round's two
    compute blocks in manual SPMD (``shard_map``) on the two-axis
    ("cells", "data") fleet mesh over the 512 host placeholder devices.

    * The per-cell Algorithm-1 solve shards whole cells over "cells" —
      each device block solves C/cells cells; the intra-cell client axis
      stays unsharded (the vertex walk sorts it).
    * The cohort gradient reduction shards the flat (C*m) cohort client
      axis over "data" and psum-reduces the Eq.-(5) weighted sum — the
      manual twin of ``engine._constrain_clients``.

    Asserts both axes actually partition (shard shapes, output
    shardings) and returns the summary dict.
    """
    import numpy as np
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import wireless as W
    from repro.fleet import solver as FSOLVER

    mesh = MESH.make_fleet_mesh(cells=32, data=16)
    assert mesh.axis_names == ("cells", "data"), mesh.axis_names
    assert dict(mesh.shape) == {"cells": 32, "data": 16}, dict(mesh.shape)

    cells, per_cell, m = 64, 64, 16          # 4096 clients, 1024-cohort
    wcfg = W.WirelessConfig()
    scfg = FSOLVER.SolverConfig()
    rng = np.random.default_rng(0)
    h_up = jnp.asarray(10.0 ** -rng.uniform(8, 12, (cells, per_cell)))
    k = jnp.asarray(rng.integers(16, 64, (cells, per_cell)).astype(float))
    cpu = jnp.asarray(rng.uniform(2e8, 8e9, (cells, per_cell)))
    p_tx = jnp.full((cells, per_cell), wcfg.tx_power_ue_w)
    rho_max = jnp.full((cells, per_cell), 0.9)
    m_cell = jnp.full((cells,), 1e-4)
    mask = jnp.ones((cells, per_cell))

    def solve_block(h, kk, f, p, mp, mc, msk):
        return FSOLVER.solve_fleet(
            h, kk, f, p, mp, mc, msk, None, bandwidth_hz=wcfg.bandwidth_hz,
            noise_psd=wcfg.noise_psd_w_per_hz, waterfall_m0=wcfg.waterfall_m0,
            model_bits=wcfg.model_bits,
            cycles_per_sample=wcfg.cycles_per_sample, weight=4e-4,
            solver=scfg)

    cell_spec = P("cells")
    t0 = time.time()
    solve_sharded = jax.jit(jax.shard_map(
        solve_block, mesh=mesh,
        in_specs=(cell_spec,) * 7, out_specs=cell_spec,
        check_vma=False))
    sol = solve_sharded(h_up, k, cpu, p_tx, rho_max, m_cell, mask)
    jax.block_until_ready(sol.prune)
    solve_s = time.time() - t0

    want = NamedSharding(mesh, cell_spec)
    assert sol.prune.sharding.is_equivalent_to(want, sol.prune.ndim), \
        sol.prune.sharding
    shard_shape = sol.prune.addressable_shards[0].data.shape
    assert shard_shape == (cells // 32, per_cell), shard_shape
    assert bool(jnp.all(sol.feasible)), "dry-run cells must be feasible"

    # -- cohort gradient reduction over "data" ------------------------------
    n_flat, dim = cells * m, 128
    wts = jax.device_put(jnp.asarray(rng.uniform(0, 1, (n_flat,))),
                         NamedSharding(mesh, P("data")))
    grads = jax.device_put(
        jnp.asarray(rng.normal(size=(n_flat, dim)).astype(np.float32)),
        NamedSharding(mesh, P("data")))

    def grad_block(w_i, g_i):
        return jax.lax.psum(jnp.einsum("c,c...->...", w_i, g_i), "data")

    t0 = time.time()
    grad_sharded = jax.jit(jax.shard_map(
        grad_block, mesh=mesh, in_specs=(P("data"), P("data")),
        out_specs=P(), check_vma=False))
    g_sum = grad_sharded(wts, grads)
    jax.block_until_ready(g_sum)
    grad_s = time.time() - t0

    gshard = wts.addressable_shards[0].data.shape
    assert gshard == (n_flat // 16,), gshard
    ref = jnp.einsum("c,c...->...", wts, grads)
    np.testing.assert_allclose(np.asarray(g_sum), np.asarray(ref),
                               rtol=1e-5)

    out = {"mesh": dict(mesh.shape), "devices": int(mesh.devices.size),
           "cells": cells, "clients_per_cell": per_cell, "cohort_m": m,
           "solve_shard_shape": list(shard_shape),
           "grad_shard_clients": int(gshard[0]),
           "solve_s": solve_s, "grad_s": grad_s}
    if verbose:
        print(f"OK   fleet shard_map dry-run on {out['devices']} devices "
              f"mesh={out['mesh']}")
        print(f"     solve: {cells} cells x {per_cell} clients, "
              f"{shard_shape[0]} cells/device block ({solve_s:.1f}s)")
        print(f"     cohort grad: {n_flat} clients over 16 data shards, "
              f"{gshard[0]} clients/device ({grad_s:.1f}s)")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, choices=list(ARCH_NAMES),
                    help="one architecture (default: all)")
    ap.add_argument("--shape", default=None, choices=list(INPUT_SHAPES),
                    help="one input shape (default: all)")
    ap.add_argument("--all", action="store_true",
                    help="run every (arch x shape)")
    ap.add_argument("--multi-pod", action="store_true",
                    help="2x16x16 (512 chips) instead of 16x16 (256)")
    ap.add_argument("--fl", action="store_true",
                    help="dry-run the distributed pruned-FL step instead "
                         "of the plain train/serve step (train shapes only)")
    ap.add_argument("--fleet", action="store_true",
                    help="dry-run the cohort-sharded fleet round on the "
                         "two-axis ('cells', 'data') mesh via shard_map "
                         "and assert both axes partition")
    ap.add_argument("--out", default=None,
                    help="directory for per-combo JSON reports")
    args = ap.parse_args(argv)

    if args.fleet:
        try:
            rep = fleet_dryrun()
        except Exception as e:
            traceback.print_exc()
            print(f"FAIL fleet dry-run: {e}")
            return 1
        if args.out:
            os.makedirs(args.out, exist_ok=True)
            path = os.path.join(args.out, "fleet_dryrun_32x16.json")
            with open(path, "w") as f:
                json.dump(rep, f, indent=2)
        return 0

    archs = [args.arch] if args.arch else list(ARCH_NAMES)
    shapes = [args.shape] if args.shape else list(INPUT_SHAPES)

    failures = []
    n_ok = n_skip = 0
    for arch in archs:
        for shape in shapes:
            if args.fl and INPUT_SHAPES[shape].mode != "train":
                continue
            try:
                rep = dryrun_one(arch, shape, multi_pod=args.multi_pod,
                                 fl=args.fl)
            except Exception as e:  # a failure here is a bug in our system
                traceback.print_exc()
                failures.append((arch, shape, repr(e)))
                print(f"FAIL {arch} x {shape}: {e}")
                continue
            if rep is None:
                n_skip += 1
                continue
            n_ok += 1
            if args.out:
                os.makedirs(args.out, exist_ok=True)
                tag = "fl_" if args.fl else ""
                path = os.path.join(
                    args.out,
                    f"{tag}{arch}_{shape}_{rep.mesh}.json".replace("/", "-"))
                RF.save_report(rep, path)

    print(f"\n{n_ok} ok, {n_skip} skipped, {len(failures)} failed "
          f"on mesh {mesh_tag(args.multi_pod)}")
    for arch, shape, err in failures:
        print(f"  FAILED: {arch} x {shape}: {err}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
