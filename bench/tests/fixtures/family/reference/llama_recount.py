"""Fixture family ``llama_recount``: the llama reference as it is, with a
work count of its own.  The count is a test stand-in for a family that
counts its own work, as a family whose layers the llama count does not
describe (experts, latent attention) must: here PaLM's model FLOPs
(arXiv:2204.02311, appendix B), 6 N per token plus 12 L H Q T, which
charges every query all T positions where the yardstick's count charges
only the causal ones."""

from __future__ import annotations

from reference import llama
from yardstick import flops as FLOPS

init, loss = llama.init, llama.loss


def train_flops(model: dict, batch: int, seq: int) -> float:
    h = model["num_attention_heads"]
    q = model["hidden_size"] // h
    per_token = (6.0 * FLOPS.matmul_params(model)
                 + 12.0 * model["num_hidden_layers"] * h * q * seq)
    return per_token * batch * seq
