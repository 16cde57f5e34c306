"""Per-kernel shape/dtype sweeps: Pallas (interpret=True on CPU) vs the
pure-jnp oracles in kernels/ref.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels import block_norms as _bn
from repro.kernels import block_sparse_matmul as _bsm
from repro.kernels import decode_attention as _da

# fp32 matmul tolerance allows for accumulation-order differences between
# the tiled kernel (per-block partial sums) and the single jnp.dot oracle
TOLS = {jnp.float32: dict(rtol=2e-4, atol=2e-4),
        jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


# ---------------------------------------------------------------------------
# block_sparse_matmul
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,k,n", [(128, 128, 128), (256, 384, 512),
                                   (64, 256, 128), (128, 512, 256)])
@pytest.mark.parametrize("density", [0.0, 0.5, 1.0])
def test_bsm_shapes(m, k, n, density):
    kx, kw, km = jax.random.split(jax.random.PRNGKey(m + k + n), 3)
    x = jax.random.normal(kx, (m, k), jnp.float32)
    w = jax.random.normal(kw, (k, n), jnp.float32)
    mk, mn = k // 128, n // 128
    mask = (jax.random.uniform(km, (mk, mn)) < density).astype(jnp.float32)
    bm = min(128, m)
    y = _bsm.block_sparse_matmul(x, w, mask, bm, 128, 128, interpret=True)
    yr = ref.block_sparse_matmul(x, w, mask, 128, 128)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), **TOLS[jnp.float32])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_bsm_dtypes(dtype):
    kx, kw, km = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(kx, (128, 256)).astype(dtype)
    w = jax.random.normal(kw, (256, 256)).astype(dtype)
    mask = (jax.random.uniform(km, (2, 2)) < 0.5).astype(jnp.float32)
    y = _bsm.block_sparse_matmul(x, w, mask, 128, 128, 128, interpret=True)
    yr = ref.block_sparse_matmul(x, w, mask, 128, 128)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), **TOLS[dtype])


def test_bsm_empty_mask_is_zero():
    x = jnp.ones((128, 128))
    w = jnp.ones((128, 128))
    y = _bsm.block_sparse_matmul(x, w, jnp.zeros((1, 1)), 128, 128, 128,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(y), 0.0)


def test_bsm_full_mask_is_dense():
    kx, kw = jax.random.split(jax.random.PRNGKey(4))
    x = jax.random.normal(kx, (128, 256))
    w = jax.random.normal(kw, (256, 384))
    y = _bsm.block_sparse_matmul(x, w, jnp.ones((2, 3)), 128, 128, 128,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w),
                               **TOLS[jnp.float32])


@pytest.mark.parametrize("m,k,n", [(128, 128, 128), (256, 384, 512),
                                   (64, 256, 128)])
@pytest.mark.parametrize("density", [0.0, 0.5, 1.0])
def test_bsm_transpose_rhs_shapes(m, k, n, density):
    """x @ (w ⊙ M)^T — the pruned backward product, same mask layout."""
    kx, kw, km = jax.random.split(jax.random.PRNGKey(m + k + n + 1), 3)
    x = jax.random.normal(kx, (m, n), jnp.float32)
    w = jax.random.normal(kw, (k, n), jnp.float32)
    mask = (jax.random.uniform(km, (k // 128, n // 128)) < density
            ).astype(jnp.float32)
    bm = min(128, m)
    y = _bsm.block_sparse_matmul(x, w, mask, bm, 128, 128,
                                 transpose_rhs=True, interpret=True)
    yr = ref.block_sparse_matmul_t(x, w, mask, 128, 128)
    assert y.shape == (m, k)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               **TOLS[jnp.float32])


def test_bsm_transpose_matches_forward_transpose():
    """The two kernels implement the same masked operator under the same
    (K//bk, N//bn) mask layout: applying each to an identity input
    recovers (w ⊙ M) and (w ⊙ M)^T respectively — a direct
    kernel-vs-kernel check with no oracle, so a consistent-but-wrong
    mask indexing in the transposed kernel cannot hide."""
    kw, km = jax.random.split(jax.random.PRNGKey(7))
    k, n = 256, 128
    w = jax.random.normal(kw, (k, n))
    mask = (jax.random.uniform(km, (2, 1)) < 0.5).astype(jnp.float32)
    masked = _bsm.block_sparse_matmul(jnp.eye(k), w, mask, 128, 128, 128,
                                      interpret=True)          # (k, n)
    masked_t = _bsm.block_sparse_matmul(jnp.eye(n), w, mask, 128, 128, 128,
                                        transpose_rhs=True,
                                        interpret=True)        # (n, k)
    np.testing.assert_allclose(np.asarray(masked_t),
                               np.asarray(masked).T, rtol=1e-6, atol=1e-6)
    # and the forward identity really is w ⊙ expand(mask)
    em = np.repeat(np.repeat(np.asarray(mask), 128, 0), 128, 1)
    np.testing.assert_allclose(np.asarray(masked), np.asarray(w) * em,
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("lead,kdim,n", [((7,), 100, 200), ((2, 9), 300, 100),
                                         ((50,), 130, 257)])
def test_masked_matmul_odd_ragged_shapes(lead, kdim, n):
    """Satellite coverage: odd/ragged shapes through the padding wrapper,
    interpret-mode on CPU."""
    kx, kw, km = jax.random.split(jax.random.PRNGKey(kdim + n), 3)
    x = jax.random.normal(kx, lead + (kdim,))
    w = jax.random.normal(kw, (kdim, n))
    tiles = ((kdim + 127) // 128, (n + 127) // 128)
    mask = (jax.random.uniform(km, tiles) < 0.6).astype(jnp.float32)
    y = ops.masked_matmul(x, w, mask)
    pk, pn = (-kdim) % 128, (-n) % 128
    yr = ref.block_sparse_matmul(
        jnp.pad(x.reshape(-1, kdim), ((0, 0), (0, pk))),
        jnp.pad(w, ((0, pk), (0, pn))), mask, 128, 128)[:, :n]
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr.reshape(
        lead + (n,))), rtol=2e-5, atol=2e-5)


def test_masked_matmul_transpose_rhs_ragged():
    kx, kw, km = jax.random.split(jax.random.PRNGKey(11), 3)
    x = jax.random.normal(kx, (3, 50, 300))
    w = jax.random.normal(kw, (200, 300))
    mask = (jax.random.uniform(km, (2, 3)) < 0.6).astype(jnp.float32)
    y = ops.masked_matmul(x, w, mask, transpose_rhs=True)
    assert y.shape == (3, 50, 200)
    wp = jnp.pad(w, ((0, 56), (0, 84)))
    yr = ref.block_sparse_matmul_t(
        jnp.pad(x.reshape(-1, 300), ((0, 0), (0, 84))), wp, mask, 128, 128)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(yr[:, :200].reshape(3, 50, 200)),
                               rtol=2e-5, atol=2e-5)


def test_masked_matmul_all_pruned_and_all_dense():
    kx, kw = jax.random.split(jax.random.PRNGKey(12))
    x = jax.random.normal(kx, (40, 200))
    w = jax.random.normal(kw, (200, 90))
    zero = ops.masked_matmul(x, w, jnp.zeros((2, 1)))
    np.testing.assert_allclose(np.asarray(zero), 0.0)
    dense = ops.masked_matmul(x, w, jnp.ones((2, 1)))
    np.testing.assert_allclose(np.asarray(dense), np.asarray(x @ w),
                               rtol=2e-5, atol=2e-5)
    zero_t = ops.masked_matmul(x @ w, w, jnp.zeros((2, 1)),
                               transpose_rhs=True)
    np.testing.assert_allclose(np.asarray(zero_t), 0.0)
    dense_t = ops.masked_matmul(x @ w, w, jnp.ones((2, 1)),
                                transpose_rhs=True)
    np.testing.assert_allclose(np.asarray(dense_t),
                               np.asarray((x @ w) @ w.T), rtol=2e-4,
                               atol=2e-4)


def test_masked_matmul_wrapper_pads_and_batches():
    """Public ops.masked_matmul: ragged shapes + leading batch dims."""
    kx, kw, km = jax.random.split(jax.random.PRNGKey(1), 3)
    x = jax.random.normal(kx, (3, 50, 200))       # batched, ragged
    w = jax.random.normal(kw, (200, 300))
    mask = (jax.random.uniform(km, (2, 3)) < 0.7).astype(jnp.float32)
    y = ops.masked_matmul(x, w, mask)
    wp = jnp.pad(w, ((0, 56), (0, 84)))
    yr = ref.block_sparse_matmul(
        jnp.pad(x.reshape(-1, 200), ((0, 0), (0, 56))), wp, mask, 128, 128)
    yr = yr[:, :300].reshape(3, 50, 300)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=2e-5,
                               atol=2e-5)


def test_masked_matmul_equals_dense_when_full():
    kx, kw = jax.random.split(jax.random.PRNGKey(2))
    x = jax.random.normal(kx, (64, 256))
    w = jax.random.normal(kw, (256, 128))
    y = ops.masked_matmul(x, w, jnp.ones((2, 1)))
    np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# block_norms
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k,n,bk,bn", [(128, 128, 128, 128),
                                       (256, 512, 128, 128),
                                       (384, 256, 128, 256),
                                       (512, 384, 256, 128)])
def test_block_norms_shapes(k, n, bk, bn):
    w = jax.random.normal(jax.random.PRNGKey(k + n), (k, n))
    out = _bn.block_norms(w, bk, bn, interpret=True)
    expect = ref.block_norms(w, bk, bn)
    assert out.shape == (k // bk, n // bn)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_block_norms_dtypes(dtype):
    w = jax.random.normal(jax.random.PRNGKey(0), (256, 256)).astype(dtype)
    out = _bn.block_norms(w, 128, 128, interpret=True)
    expect = ref.block_norms(w, 128, 128)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), rtol=2e-2)


@pytest.mark.parametrize("bk,bn", [(72, 192), (24, 40)])
@pytest.mark.parametrize("transpose_rhs", [False, True])
def test_masked_matmul_off_tiling_grid(bk, bn, transpose_rhs):
    """Grids off the (8, 128) tiling (smollm's 72x192) are padded tile by
    tile to it inside the wrapper; the result is still x @ (w * mask)."""
    kdim, n = 3 * bk, 4 * bn
    kx, kw, km = jax.random.split(jax.random.PRNGKey(3), 3)
    w = jax.random.normal(kw, (kdim, n))
    mask = (jax.random.uniform(km, (3, 4)) > 0.4).astype(jnp.float32)
    x = jax.random.normal(kx, (5, n if transpose_rhs else kdim))
    y = ops.masked_matmul(x, w, mask, block_k=bk, block_n=bn,
                          transpose_rhs=transpose_rhs, interpret=True)
    oracle = ref.block_sparse_matmul_t if transpose_rhs \
        else ref.block_sparse_matmul
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(oracle(x, w, mask, bk, bn)),
                               rtol=1e-4, atol=1e-4)


def test_tile_norms_off_tiling_grid():
    w = jax.random.normal(jax.random.PRNGKey(4), (144, 384))
    out = ops.tile_norms(w, 72, 192, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.block_norms(w, 72, 192)),
                               rtol=1e-5)


def test_tile_norms_wrapper_ragged():
    w = jax.random.normal(jax.random.PRNGKey(0), (200, 300))
    out = ops.tile_norms(w)
    assert out.shape == (2, 3)
    wp = jnp.pad(w, ((0, 56), (0, 84)))
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.block_norms(wp, 128, 128)),
                               rtol=1e-5, atol=1e-5)


def test_block_norms_match_pruning_module():
    """kernels/block_norms == core.pruning.block_l2_norms (mask source)."""
    from repro.core.pruning import block_l2_norms
    w = jax.random.normal(jax.random.PRNGKey(3), (256, 384))
    a = ops.tile_norms(w, 128, 128)
    b = block_l2_norms(w, block=128)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5)


# ---------------------------------------------------------------------------
# decode_attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,h,hkv,hd,s", [(2, 4, 2, 64, 128),
                                          (1, 8, 1, 64, 512),
                                          (4, 4, 4, 128, 256),
                                          (2, 16, 8, 64, 384)])
def test_decode_attention_shapes(b, h, hkv, hd, s):
    ks = jax.random.split(jax.random.PRNGKey(b * h + s), 4)
    q = jax.random.normal(ks[0], (b, h, hd))
    k = jax.random.normal(ks[1], (b, hkv, s, hd))
    v = jax.random.normal(ks[2], (b, hkv, s, hd))
    pos = jax.random.randint(ks[3], (b,), 0, s)
    out = ops.flash_decode(q, k, v, pos, block_s=128)
    expect = ref.decode_attention(q, k, v, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window", [32, 100, 256])
def test_decode_attention_windowed(window):
    ks = jax.random.split(jax.random.PRNGKey(window), 4)
    b, h, hkv, hd, s = 2, 4, 2, 64, 256
    q = jax.random.normal(ks[0], (b, h, hd))
    k = jax.random.normal(ks[1], (b, hkv, s, hd))
    v = jax.random.normal(ks[2], (b, hkv, s, hd))
    pos = jnp.asarray([s - 1, s // 2])
    out = ops.flash_decode(q, k, v, pos, block_s=128, window=window)
    expect = ref.decode_attention(q, k, v, pos, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_dtypes(dtype):
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks[0], (2, 4, 64)).astype(dtype)
    k = jax.random.normal(ks[1], (2, 2, 128, 64)).astype(dtype)
    v = jax.random.normal(ks[2], (2, 2, 128, 64)).astype(dtype)
    pos = jnp.asarray([100, 60])
    out = ops.flash_decode(q, k, v, pos, block_s=128)
    expect = ref.decode_attention(q, k, v, pos)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("s", [64, 200, 536])
def test_decode_attention_any_cache_length(s):
    """A cache length some block divides is read where it lies (no pad of
    the cache per call); 536 = 8 * 67 has no block in [128, 512] and is
    padded.  Both match the oracle."""
    ks = jax.random.split(jax.random.PRNGKey(s), 4)
    b, h, hkv, hd = 2, 6, 3, 64
    q = jax.random.normal(ks[0], (b, h, hd))
    k = jax.random.normal(ks[1], (b, hkv, s, hd))
    v = jax.random.normal(ks[2], (b, hkv, s, hd))
    pos = jnp.asarray([s - 1, s // 3], jnp.int32)
    jaxpr = jax.make_jaxpr(lambda *a: ops.flash_decode(*a))(q, k, v, pos)
    call, = [e for e in jaxpr.jaxpr.eqns
             if e.params.get("name") == "decode_attention"]
    # the kernel reads the caller's k and v themselves, or padded copies
    in_place = call.invars[1:3] == jaxpr.jaxpr.invars[1:3]
    assert in_place == (s != 536)
    out = ops.flash_decode(q, k, v, pos)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.decode_attention(q, k, v, pos)),
                               rtol=2e-5, atol=2e-5)


def test_decode_attention_pos_zero():
    """Only the first key visible at pos=0."""
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (1, 2, 64))
    k = jax.random.normal(ks[1], (1, 1, 128, 64))
    v = jax.random.normal(ks[2], (1, 1, 128, 64))
    out = ops.flash_decode(q, k, v, jnp.zeros((1,), jnp.int32), block_s=128)
    np.testing.assert_allclose(np.asarray(out)[0, 0], np.asarray(v)[0, 0, 0],
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# flash_prefill
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,s,h,hkv,hd", [(1, 128, 4, 2, 64),
                                          (2, 256, 8, 2, 64),
                                          (1, 512, 4, 1, 128),
                                          (2, 128, 4, 4, 64)])
def test_flash_prefill_causal(b, s, h, hkv, hd):
    ks = jax.random.split(jax.random.PRNGKey(b * s + h), 3)
    q = jax.random.normal(ks[0], (b, s, h, hd))
    k = jax.random.normal(ks[1], (b, hkv, s, hd))
    v = jax.random.normal(ks[2], (b, hkv, s, hd))
    out = ops.flash_prefill(q, k, v, block_q=64, block_s=64)
    expect = ref.prefill_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window", [32, 128])
def test_flash_prefill_windowed(window):
    b, s, h, hkv, hd = 1, 256, 4, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(window), 3)
    q = jax.random.normal(ks[0], (b, s, h, hd))
    k = jax.random.normal(ks[1], (b, hkv, s, hd))
    v = jax.random.normal(ks[2], (b, hkv, s, hd))
    out = ops.flash_prefill(q, k, v, window=window, block_q=64, block_s=64)
    expect = ref.prefill_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-5, atol=2e-5)


def test_flash_prefill_cross_ragged():
    """causal=False with T != S and ragged T (whisper cross-attention)."""
    b, s, t, h, hd = 1, 128, 94, 4, 64
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (b, s, h, hd))
    k = jax.random.normal(ks[1], (b, h, t, hd))
    v = jax.random.normal(ks[2], (b, h, t, hd))
    out = ops.flash_prefill(q, k, v, causal=False, block_q=64, block_s=64)
    expect = ref.prefill_attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_prefill_dtypes(dtype):
    b, s, h, hkv, hd = 1, 128, 4, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (b, s, h, hd)).astype(dtype)
    k = jax.random.normal(ks[1], (b, hkv, s, hd)).astype(dtype)
    v = jax.random.normal(ks[2], (b, hkv, s, hd)).astype(dtype)
    out = ops.flash_prefill(q, k, v, block_q=64, block_s=64)
    expect = ref.prefill_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_flash_prefill_matches_model_flash():
    """Pallas kernel == the pure-JAX chunked flash in models/attention."""
    from repro.models import attention as A
    b, s, h, hkv, hd = 1, 256, 4, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (b, s, h, hd))
    k = jax.random.normal(ks[1], (b, s, hkv, hd))
    v = jax.random.normal(ks[2], (b, s, hkv, hd))
    kern = ops.flash_prefill(q, k.transpose(0, 2, 1, 3),
                             v.transpose(0, 2, 1, 3), block_q=64, block_s=64)
    jaxflash = A.flash_attention(q, k, v, hd ** -0.5, causal=True,
                                 q_chunk=64, kv_chunk=64)
    np.testing.assert_allclose(np.asarray(kern), np.asarray(jaxflash),
                               rtol=2e-4, atol=2e-4)
