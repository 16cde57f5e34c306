"""Substrate tests: optimizers, checkpointing, data pipelines, sharding
rules, roofline HLO parsing."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import checkpoint
from repro import optimizers as opt
from repro.data import synthetic, tokens
from repro.launch import roofline as RF


# ---------------------------------------------------------------------------
# Optimizers
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["sgd", "momentum", "adam"])
def test_optimizer_minimizes_quadratic(name):
    o = opt.REGISTRY[name]()
    params = {"x": jnp.asarray([3.0, -2.0])}
    state = o.init(params)
    lr = 0.1
    for _ in range(200):
        grads = jax.tree.map(lambda p: 2 * p, params)   # d/dx x^2
        params, state = o.update(params, grads, state, lr)
    assert float(jnp.max(jnp.abs(params["x"]))) < 1e-2


def test_clip_by_global_norm():
    g = {"a": jnp.asarray([3.0, 4.0])}           # norm 5
    clipped = opt.clip_by_global_norm(g, 1.0)
    assert float(jnp.linalg.norm(clipped["a"])) == pytest.approx(1.0, rel=1e-5)
    passthrough = opt.clip_by_global_norm(g, 10.0)
    np.testing.assert_allclose(np.asarray(passthrough["a"]), [3.0, 4.0])


# ---------------------------------------------------------------------------
# Checkpoint
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip(tmp_path):
    tree = {"layer": {"w": jnp.arange(6.0).reshape(2, 3),
                      "b": jnp.ones((3,), jnp.float32)},
            "stack": [jnp.zeros((2,)), jnp.asarray(5)]}
    path = os.path.join(tmp_path, "ckpt.npz")
    checkpoint.save(path, tree)
    restored = checkpoint.restore(path, jax.tree.map(jnp.zeros_like, tree))
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))


def test_checkpoint_bfloat16_roundtrip(tmp_path):
    """bf16 leaves (smollm's param dtype) come back bit-exact."""
    w = jax.random.normal(jax.random.PRNGKey(0), (4, 8)).astype(jnp.bfloat16)
    path = os.path.join(tmp_path, "bf16.npz")
    checkpoint.save(path, {"w": w})
    back = checkpoint.restore(path, {"w": jnp.zeros((4, 8), jnp.bfloat16)})
    assert back["w"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(back["w"], np.float32),
                                  np.asarray(w, np.float32))


def test_checkpoint_shape_mismatch_raises(tmp_path):
    path = os.path.join(tmp_path, "ckpt.npz")
    checkpoint.save(path, {"w": jnp.zeros((2, 3))})
    with pytest.raises(ValueError):
        checkpoint.restore(path, {"w": jnp.zeros((3, 2))})


# ---------------------------------------------------------------------------
# Data
# ---------------------------------------------------------------------------

def test_synthetic_dataset_learnable_and_deterministic():
    d1 = synthetic.make_dataset(seed=3)
    d2 = synthetic.make_dataset(seed=3)
    np.testing.assert_allclose(d1.x_train, d2.x_train)
    assert d1.x_train.shape == (2000, 784)
    assert set(np.unique(d1.y_train)) <= set(range(10))


def test_partition_iid_sizes():
    d = synthetic.make_dataset(seed=0)
    parts = synthetic.partition_iid([30, 40, 50], d, seed=1)
    assert [len(p) for p in parts] == [30, 40, 50]
    # disjoint
    all_idx = np.concatenate(parts)
    assert len(np.unique(all_idx)) == 120


def test_partition_dirichlet_sizes_and_skew():
    d = synthetic.make_dataset(seed=0)
    parts = synthetic.partition_dirichlet([100, 100], d, alpha=0.1, seed=0)
    assert [len(p) for p in parts] == [100, 100]
    # strong skew: each client's top class dominates
    for p in parts:
        counts = np.bincount(d.y_train[p], minlength=10)
        assert counts.max() / counts.sum() > 0.3


def test_token_stream_deterministic():
    a = tokens.TokenStream(512, seed=1).sample(4, 64)
    b = tokens.TokenStream(512, seed=1).sample(4, 64)
    np.testing.assert_array_equal(a, b)
    assert a.dtype == np.int32
    assert a.min() >= 0 and a.max() < 512


# ---------------------------------------------------------------------------
# Roofline HLO parsing
# ---------------------------------------------------------------------------

def test_collective_stats_parsing():
    hlo = """
  %ag = f32[8,128]{1,0} all-gather(f32[1,128]{1,0} %x), replica_groups={}
  %ar = bf16[256]{0} all-reduce(bf16[256]{0} %y), to_apply=%add
  %rs.1 = f32[2,64]{1,0} reduce-scatter(f32[16,64]{1,0} %z), dimensions={0}
  %ags = (f32[4]{0}, f32[32]{0}) all-gather-start(f32[4]{0} %w)
  %agd = f32[32]{0} all-gather-done((f32[4]{0}, f32[32]{0}) %ags)
  %cp = u32[16]{0} collective-permute(u32[16]{0} %p), source_target_pairs={{0,1}}
"""
    st = RF.collective_stats(hlo)
    assert st.counts["all-gather"] == 2      # plain + start (done skipped)
    assert st.counts["all-reduce"] == 1
    assert st.counts["reduce-scatter"] == 1
    assert st.counts["collective-permute"] == 1
    assert st.bytes_by_op["all-reduce"] == 256 * 2
    assert st.bytes_by_op["all-gather"] == 1 * 128 * 4 + 4 * 4
    assert st.bytes_by_op["reduce-scatter"] == 16 * 64 * 4
    assert st.total_bytes == sum(st.bytes_by_op.values())


def test_collective_stats_ignores_non_collectives():
    hlo = "%d = f32[128,128]{1,0} dot(f32[128,128] %a, f32[128,128] %b)"
    st = RF.collective_stats(hlo)
    assert st.total_bytes == 0 and not st.counts


def test_chip_peaks_known_kind_and_unknown_raises():
    v5e = RF.chip_peaks("TPU v5 lite")
    assert (v5e.flops, v5e.hbm_bw, v5e.hbm_bytes) == (197e12, 819e9, 16e9)
    with pytest.raises(KeyError, match="no published peaks"):
        RF.chip_peaks("cpu")


def test_roofline_report_terms():
    rep = RF.RooflineReport(
        arch="x", shape="train_4k", mesh="16x16", chips=256,
        flops_per_chip=197e12 * 0.010,          # 10 ms compute
        bytes_per_chip=819e9 * 0.005,           # 5 ms memory
        collective_bytes_per_chip=50e9 * 0.001,  # 1 ms collective
        peak_memory_per_chip=1 << 30, argument_bytes=0, output_bytes=0,
        temp_bytes=0, collectives={}, model_flops=197e12 * 0.010 * 256 * 0.5,
        wall_s=1.0, device_kind="TPU v5 lite")
    assert rep.t_compute == pytest.approx(0.010)
    assert rep.t_memory == pytest.approx(0.005)
    assert rep.t_collective == pytest.approx(0.001)
    assert rep.bottleneck == "compute"
    assert rep.useful_flops_ratio == pytest.approx(0.5)


# ---------------------------------------------------------------------------
# Sharding rules (pure pspec logic; 1-device mesh)
# ---------------------------------------------------------------------------

def test_param_pspec_rules():
    from repro.launch import shardings as SH
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    # too small to shard on a 1x1 mesh -> unsharded
    spec = SH.param_pspec("stages/0/b0/attn/wq/w", (256, 512), mesh)
    assert all(s in (None, "data", "model") for s in spec)


def test_data_pspec_batch_dim():
    from repro.launch import shardings as SH
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    spec = SH.data_pspec((8, 128), mesh, batch_dim=0)
    assert len(spec) == 2


# ---------------------------------------------------------------------------
# Compile cache placement
# ---------------------------------------------------------------------------

@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_env_var_wins(monkeypatch, restore_cache_dir):
    from repro.launch import compile_cache as CC
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(CC.ENV_VAR, "/elsewhere/cache")
    assert CC.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_fixed_in_checkout(monkeypatch,
                                                    restore_cache_dir):
    from repro.launch import compile_cache as CC
    monkeypatch.delenv(CC.ENV_VAR, raising=False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(root, ".jax_cache")
    assert CC.enable_compile_cache() == want
    assert CC.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
