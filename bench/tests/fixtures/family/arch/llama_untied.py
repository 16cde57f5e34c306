"""Fixture family ``llama_untied``: a llama-shaped model whose output head
is a matrix of its own (``tie_word_embeddings`` false), which the program
holds as ``params["unembed"]``.  It maps onto the program as the llama
family beside it does."""

from __future__ import annotations

import pathlib

import families

_LLAMA = families.load_module(pathlib.Path(__file__).with_name("llama.py"))


def arch(config: dict):
    if config["model"]["tie_word_embeddings"]:
        raise ValueError("llama_untied has an untied output head")
    return _LLAMA.arch(config)
