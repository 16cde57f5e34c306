"""Kernel microbenchmarks: ``name,us_per_call,derived`` CSV.

On this CPU container the Pallas kernels execute in interpret mode, so
their *wall* time is not the TPU story; what we measure here is

  * the pure-jnp oracle wall time (XLA:CPU) as a sanity baseline, and
  * the *modeled* FLOP/DMA reduction of the block-sparse path: the kernel
    skips (1-density) of its K-loop iterations, which on TPU converts
    directly into MXU cycles and HBM->VMEM DMA bytes saved.

The correctness of the skipping logic (masked tiles contribute exactly 0)
is asserted on every run.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref
from benchmarks import common


def _time(fn, *args, iters: int = 20) -> float:
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def run(quick: bool = False):
    rows = []
    m, k, n = (256, 512, 512) if quick else (512, 1024, 1024)

    kx, kw = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(kx, (m, k))
    w = jax.random.normal(kw, (k, n))

    dense = jax.jit(lambda a, b: a @ b)
    t_dense = _time(dense, x, w)
    rows.append(["dense_matmul_jnp", t_dense, f"{2*m*k*n/1e9:.2f}_GFLOP"])

    for density in (1.0, 0.5, 0.25):
        mask = np.zeros((k // 128, n // 128), np.float32)
        flat = np.arange(mask.size)
        keep = flat[: int(round(mask.size * density))]
        mask.reshape(-1)[keep] = 1.0
        mask = jnp.asarray(mask)

        oracle = jax.jit(lambda a, b, mm: ref.block_sparse_matmul(
            a, b, mm, 128, 128))
        t_oracle = _time(oracle, x, w, mask)
        # modeled TPU cost: kernel visits only live (k,n) tiles
        rows.append([f"masked_matmul_density{density}", t_oracle,
                     f"flops_x{density:.2f}"])
        # correctness of skipping: Pallas (interpret) == oracle
        y = ops.masked_matmul(x, w, mask)
        np.testing.assert_allclose(np.asarray(y),
                                   np.asarray(oracle(x, w, mask)),
                                   rtol=2e-4, atol=2e-4)

    # decode attention: oracle timing + kernel correctness
    b, h, hkv, hd, s = 4, 8, 2, 64, (1024 if quick else 4096)
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q = jax.random.normal(ks[0], (b, h, hd))
    kk = jax.random.normal(ks[1], (b, hkv, s, hd))
    vv = jax.random.normal(ks[2], (b, hkv, s, hd))
    pos = jnp.full((b,), s - 1, jnp.int32)
    oracle_attn = jax.jit(lambda *a: ref.decode_attention(*a))
    t_attn = _time(oracle_attn, q, kk, vv, pos)
    rows.append([f"decode_attention_S{s}", t_attn,
                 f"{(2*b*h*s*hd*2)/1e6:.1f}_MFLOP"])
    out = ops.flash_decode(q, kk, vv, pos)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(oracle_attn(q, kk, vv, pos)),
                               rtol=2e-4, atol=2e-4)

    # prefill attention: oracle timing + fused-kernel correctness
    sp = 512 if quick else 1024
    ksp = jax.random.split(jax.random.PRNGKey(4), 3)
    qp = jax.random.normal(ksp[0], (1, sp, 4, 64))
    kp = jax.random.normal(ksp[1], (1, 2, sp, 64))
    vp = jax.random.normal(ksp[2], (1, 2, sp, 64))
    oracle_prefill = jax.jit(lambda *a: ref.prefill_attention(*a))
    t_pref = _time(oracle_prefill, qp, kp, vp, iters=5)
    rows.append([f"prefill_attention_S{sp}", t_pref,
                 f"{(2*sp*sp*4*64*2/2)/1e9:.2f}_GFLOP"])
    outp = ops.flash_prefill(qp, kp, vp, block_q=128, block_s=128)
    np.testing.assert_allclose(np.asarray(outp),
                               np.asarray(oracle_prefill(qp, kp, vp)),
                               rtol=2e-4, atol=2e-4)

    wnorm = jax.random.normal(jax.random.PRNGKey(2), (1024, 1024))
    oracle_norms = jax.jit(lambda a: ref.block_norms(a, 128, 128))
    t_norms = _time(oracle_norms, wnorm)
    rows.append(["block_norms_1024", t_norms, "mask_gen"])

    header = ["name", "us_per_call", "derived"]
    common.print_table(header, rows, "Kernel microbenchmarks (CPU oracle "
                       "timings; Pallas correctness asserted)")
    common.write_csv("kernel_bench.csv", header, rows)
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
