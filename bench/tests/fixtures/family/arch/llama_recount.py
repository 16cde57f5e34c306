"""Fixture family ``llama_recount``: a llama model that maps onto the
program as the llama family beside it does; only its reference's work
count is its own."""

from __future__ import annotations

import pathlib

import families

arch = families.load_module(pathlib.Path(__file__).with_name("llama.py")).arch
