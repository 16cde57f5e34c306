"""The numbers that decide ``correct``, checked by hand at small sizes: the
worst-leaf gaps, the control's precision, and the payload Algorithm 1 is
given."""

import json
import pathlib
import sys

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import calibrate  # noqa: E402
import run  # noqa: E402

FLEET = run.load_module(BENCH / "drivers" / "fleet.py")


def _config(name: str) -> dict:
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def test_norm_gap_misses_a_turn_that_the_direction_gap_sees():
    ref = {"w": np.array([3.0, 4.0]), "b": np.array([0.0, 5.0])}
    turned = {"w": np.array([4.0, 3.0]), "b": np.array([0.0, 5.0])}
    assert FLEET.leaf_gap(turned, ref) == 0.0
    # |(1, -1)| over the leaf's norm 5
    assert FLEET.leaf_gap(turned, ref, direction=True) == pytest.approx(
        2.0 ** 0.5 / 5.0)


def test_leaves_below_the_floor_are_left_out():
    ref = {"a": np.array([1.0]), "b": np.array([1.0]),
           "tiny": np.array([1e-9])}
    prog = {"a": np.array([1.0]), "b": np.array([1.0]),
            "tiny": np.array([2e-9])}
    assert FLEET.leaf_gap(prog, ref) == 0.0
    assert FLEET.leaf_gap(prog, ref, direction=True) == 0.0
    prog["a"] = np.array([1.1])
    # measured against the larger of the leaf's norm and the median's
    assert FLEET.leaf_gap(prog, ref) == pytest.approx(0.1)


@pytest.mark.parametrize("name,precision", [
    ("smollm-135m", "fp8"), ("fleet-1m-mlp", "high")])
def test_control_is_one_precision_below_the_config(name, precision):
    assert calibrate.control_precision(_config(name)) == precision


def test_control_precision_of_float32_at_the_default():
    config = {"task": {"dtype": "float32", "matmul_precision": "default"}}
    assert calibrate.control_precision(config) == "bf16"


def test_high_is_three_bfloat16_passes():
    import jax
    import jax.numpy as jnp
    from reference import precision as PR
    a = jax.random.normal(jax.random.PRNGKey(0), (16, 64))
    b = jax.random.normal(jax.random.PRNGKey(1), (64, 8))
    exact = np.asarray(a, np.float64) @ np.asarray(b, np.float64)
    err = lambda p: float(np.max(np.abs(
        np.asarray(PR.einsum("ij,jk->ik", a, b, p), np.float64) - exact)))
    f32, high, bf16 = err("f32"), err("high"), err("bf16")
    # the tail x tail term left out: far below one pass, above float32
    assert f32 < high < bf16 / 30
    head = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
    tail = lambda x: head(x - head(x))
    hp = jax.lax.Precision.HIGHEST
    by_hand = (jnp.dot(head(a), head(b), precision=hp)
               + jnp.dot(head(a), tail(b), precision=hp)
               + jnp.dot(tail(a), head(b), precision=hp))
    np.testing.assert_array_equal(PR.einsum("ij,jk->ik", a, b, "high"),
                                  by_hand)


def test_payload_is_the_models_own_bits():
    """Algorithm 1 prices the model the cell trains: 610 float32 weights
    and biases of the 60 -> 10 logistic regression, in the program's
    configuration and in the reference alike."""
    import jax
    from reference import fleet as REF
    from reference import tasks as REF_TASKS
    config = _config("fleet-1m-mlp")
    config["fleet"]["topology"].update(num_cells=2, clients_per_cell=4)
    traffic = json.loads((BENCH / "traffic" / "sync-cohort.json").read_text())
    traffic["fleet"]["schedule"]["participants_per_cell"] = 2
    spec = FLEET.resolve(config, traffic)
    assert FLEET._program_config(config, spec).wireless.model_bits == \
        610 * 32
    task = REF_TASKS.make(config, spec["task"])
    fleet = REF.Fleet(spec, task)
    fleet.set_model_bits(jax.eval_shape(task.init, jax.random.PRNGKey(0)))
    assert fleet.model_bits == 610 * 32


def test_thresholds_follow_the_program_on_mixed_dtypes():
    """bfloat16 matrices beside a float32 router, as an expert model draws
    them: the reference's thresholds are ``jnp.quantile`` of the
    concatenated |w| (float32) to one float32 ulp, and its masks are the
    program's (``magnitude_thresholds``, ``threshold_masks``) leaf for
    leaf."""
    import jax
    import jax.numpy as jnp
    from reference import fleet as REF
    from repro.core import pruning
    k = jax.random.split(jax.random.PRNGKey(3), 4)
    params = {
        "attn": {"w": jax.random.normal(k[0], (32, 48), jnp.bfloat16)},
        "ffn": {"w": jax.random.normal(k[1], (48, 32), jnp.bfloat16)},
        "norm": {"scale": jnp.ones((32,), jnp.bfloat16)},
        "router": {"w": jax.random.normal(k[2], (32, 8)) * 0.02}}
    rates = jnp.asarray([0.0, 0.1, 0.3337, 0.5, 0.7, 0.9001, 1.0])
    ref = np.asarray(REF.thresholds(params, rates))
    assert ref.dtype == np.float32
    mags = jnp.concatenate([jnp.abs(params[n]["w"]).reshape(-1)
                            for n in ("attn", "ffn", "router")])
    quantile = np.asarray(jnp.quantile(mags, rates))
    assert quantile.dtype == np.float32
    assert np.all(np.abs(ref - quantile) <= np.spacing(quantile))
    prog = pruning.magnitude_thresholds(params, rates)
    for r, p in zip(ref, prog):
        mine = REF.masks(params, r)
        theirs = pruning.threshold_masks(params, p)
        for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(theirs)):
            np.testing.assert_array_equal(a, b)
