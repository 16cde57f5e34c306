"""Operations that the work needs, from shapes alone.

These count what the mathematics of a step requires, never what a compiler
emits: no masked zeros, no recompute, no control flow.  Multiply-adds count
two operations.  A cell's work is its model family's count,
``reference/<model_type>.py::train_flops``: ``train_flops`` here is the
``llama`` family's, not every model's, and a family whose layers it does
not describe (experts, latent attention, another head) counts its own.

* ``mlp_train_flops``  -- forward and backward of an MLP classifier over
                          ``samples`` rows: 6 x (weights + biases) per row.
* ``train_flops``      -- the llama family's count: forward and backward of
                          a llama-style causal LM on ``batch`` rows of
                          ``seq`` tokens: 6 N per token, N the parameters
                          that multiply (layers and the tied output
                          embedding, not the norms), plus three times the
                          causal attention products.
* ``attention_flops``  -- QK^T and PV of causal self-attention in the
                          forward pass: query t reads t + 1 keys.
"""

from __future__ import annotations


def mlp_params(dims) -> int:
    return sum(a * b + b for a, b in zip(dims[:-1], dims[1:]))


def mlp_train_flops(dims, samples: int) -> float:
    return 6.0 * mlp_params(dims) * samples


def matmul_params(model: dict) -> int:
    d, h = model["hidden_size"], model["num_attention_heads"]
    kv, f = model["num_key_value_heads"], model["intermediate_size"]
    hd = d // h
    per_layer = d * hd * (2 * h + 2 * kv) + 3 * d * f
    return model["num_hidden_layers"] * per_layer + model["vocab_size"] * d


def attention_flops(model: dict, batch: int, seq: int) -> float:
    h = model["num_attention_heads"]
    hd = model["hidden_size"] // h
    pairs = seq * (seq + 1) / 2.0            # causal (query, key) pairs
    return model["num_hidden_layers"] * batch * h * 2.0 * 2.0 * hd * pairs


def train_flops(model: dict, batch: int, seq: int) -> float:
    tokens = batch * seq
    return (6.0 * matmul_params(model) * tokens
            + 3.0 * attention_flops(model, batch, seq))

