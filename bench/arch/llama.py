"""The llama family's program mapping (SmolLM and kin): a configuration's
Hugging Face keys onto the program's ``ArchConfig``, a single stage of
attention + MLP blocks."""

from __future__ import annotations


def arch(config: dict):
    from repro.configs import get_config
    from repro.configs.base import BlockSpec, StageSpec
    m = config["model"]
    return get_config(config["program_arch"]).replace(
        d_model=m["hidden_size"], num_heads=m["num_attention_heads"],
        num_kv_heads=m["num_key_value_heads"], d_ff=m["intermediate_size"],
        vocab_size=m["vocab_size"], rope_theta=float(m["rope_theta"]),
        tie_embeddings=m["tie_word_embeddings"],
        stages=(StageSpec(m["num_hidden_layers"],
                          (BlockSpec("attn", "mlp"),)),),
        param_dtype=m["torch_dtype"], compute_dtype=m["torch_dtype"])
