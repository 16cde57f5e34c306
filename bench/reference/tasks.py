"""Plain references of the two fleet tasks: their data, weights and loss.

Each task draws its data and its initial weights from keys laid out as the
system under test lays them out, and computes its loss in float32 through
``precision.einsum``.  Nothing here imports the system.

* ``MLPTask`` -- per-class Gaussian templates, an MLP classifier (ReLU
                 hidden layers), mean cross-entropy.
* ``LMTask``  -- a fixed pool of Zipf/Markov token rows (client ``i`` owns
                 row ``i mod pool``), the reference model of the
                 configuration's family (``reference/<model_type>.py``).
"""

from __future__ import annotations

import pathlib

import jax
import jax.numpy as jnp
import numpy as np

import families
from reference import precision as PR


class MLPTask:
    def __init__(self, spec: dict):
        self.dim = spec["feature_dim"]
        self.hidden = tuple(spec["hidden"])
        self.classes = spec["num_classes"]
        self.batch = spec["local_batch"]
        self.noise = spec["data_noise"]
        self.param_dtype = jnp.float32
        self.client_chunk = 1000

    def build(self, task_key, data_key):
        self.templates = jax.random.normal(task_key, (self.classes, self.dim))
        self.data_key = data_key

    def init(self, key):
        sizes = (self.dim,) + self.hidden + (self.classes,)
        keys = jax.random.split(key, len(sizes) - 1)
        return {f"layer{i}": {
            "w": jax.random.normal(keys[i], (a, b)) * (2.0 / a) ** 0.5,
            "b": jnp.zeros((b,))}
            for i, (a, b) in enumerate(zip(sizes[:-1], sizes[1:]))}

    def client_batch(self, idx):
        ky, kx = jax.random.split(jax.random.fold_in(self.data_key, idx))
        y = jax.random.randint(ky, (self.batch,), 0, self.classes)
        x = self.templates[y] + self.noise * jax.random.normal(
            kx, (self.batch, self.dim))
        return {"x": x, "y": y}

    def loss(self, params, batch, precision="f32"):
        h = batch["x"]
        n = len(params)
        for i in range(n):
            p = params[f"layer{i}"]
            h = PR.einsum("bi,io->bo", h, p["w"], precision) \
                + p["b"].astype(jnp.float32)
            if precision == "bf16":
                h = h.astype(jnp.bfloat16).astype(jnp.float32)
            if i < n - 1:
                h = jax.nn.relu(h)
        logp = jax.nn.log_softmax(h, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, batch["y"][:, None],
                                             axis=-1))


class TokenStream:
    """Zipf unigram tokens with a sparse first-order Markov structure."""

    def __init__(self, vocab: int, seed: int, branch: int = 32):
        self.vocab = int(vocab)
        self.rng = np.random.default_rng(seed)
        p = 1.0 / np.arange(1, self.vocab + 1, dtype=np.float64)
        self.unigram = p / p.sum()
        self.succ = self.rng.integers(0, self.vocab, size=(self.vocab, branch))

    def sample(self, rows: int, seq: int) -> np.ndarray:
        out = np.empty((rows, seq), np.int32)
        cur = self.rng.choice(self.vocab, size=rows, p=self.unigram)
        out[:, 0] = cur
        for t in range(1, seq):
            markov = self.rng.random(rows) < 0.8
            pick = self.succ[cur, self.rng.integers(0, self.succ.shape[1],
                                                    size=rows)]
            fresh = self.rng.choice(self.vocab, size=rows, p=self.unigram)
            cur = np.where(markov, pick, fresh).astype(np.int32)
            out[:, t] = cur
        return out


class LMTask:
    """A causal language model of ``family``, the module of its plain
    reference: ``init``, ``loss`` and ``train_flops``."""

    def __init__(self, family, model: dict, spec: dict):
        self.family, self.model = family, model
        self.seq = spec["seq_len"]
        self.batch = spec["local_batch"]
        self.pool_rows = spec["pool_clients"]
        self.param_dtype = jnp.dtype(model["torch_dtype"])
        self.client_chunk = 1

    def build(self, task_key, data_key):
        del data_key
        seeds = np.asarray(jax.random.randint(
            task_key, (2,), 0, np.iinfo(np.int32).max))
        pool = TokenStream(self.model["vocab_size"], int(seeds[0])).sample(
            self.pool_rows * self.batch, self.seq)
        self.pool = jnp.asarray(pool.reshape(self.pool_rows, self.batch,
                                             self.seq))

    def init(self, key):
        return self.family.init(self.model, key)

    def client_batch(self, idx):
        return {"tokens": self.pool[idx % self.pool_rows]}

    def loss(self, params, batch, precision="f32"):
        return self.family.loss(self.model, params, batch, precision)


def make(config: dict, spec: dict, bench: pathlib.Path = families.BENCH):
    """The configuration's task; a model's family is found under
    ``bench``."""
    if "model" in config:
        model = config["model"]
        return LMTask(families.load("reference", model, bench), model, spec)
    return MLPTask(config["task"])
