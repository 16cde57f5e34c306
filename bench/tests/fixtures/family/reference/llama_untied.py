"""Fixture family ``llama_untied``: the llama reference with an untied
output head.  The head is ``params["unembed"]["w"]``, (hidden, vocab),
drawn as the system draws it: from the second of the init key's five
splits, normal and scaled by fan-in."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from reference import llama
from reference import precision as PR
from yardstick import flops as FLOPS


def init(model: dict, key) -> dict:
    params = llama.init(model, key)
    d, v = model["hidden_size"], model["vocab_size"]
    k_head = jax.random.split(key, 5)[1]
    params["unembed"] = {"w": (jax.random.normal(k_head, (d, v)) * d ** -0.5
                               ).astype(llama.DTYPES[model["torch_dtype"]])}
    return params


def loss(model: dict, params: dict, batch: dict, precision: str = "f32"):
    """Mean next-token cross-entropy, the logits through the head."""
    tokens = batch["tokens"]
    x = llama.hidden(model, params, tokens, precision)[:, :-1]
    lg = PR.einsum("bsd,dv->bsv", x, params["unembed"]["w"], precision)
    logp = jax.nn.log_softmax(lg, axis=-1)
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(nll)


def train_flops(model: dict, batch: int, seq: int) -> float:
    """The blocks' products and the head's (hidden x vocab); the embedding
    is a lookup and counts nothing."""
    head = model["hidden_size"] * model["vocab_size"]
    # matmul_params holds the blocks and one head of this size
    blocks = FLOPS.matmul_params(model) - head
    return (6.0 * (blocks + head) * batch * seq
            + 3.0 * FLOPS.attention_flops(model, batch, seq))
