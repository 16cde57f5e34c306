"""A model family's files, found by the configuration's ``model_type``.

The family of a configuration is its ``model``'s ``model_type``, Hugging
Face's own key, and ``llama`` where the file gives none.  Each family is two
files under the benchmark's directory:

* ``arch/<model_type>.py``      -- ``arch(config) -> ArchConfig``: the
                                   configuration's keys mapped onto the
                                   program's (imports the program);
* ``reference/<model_type>.py`` -- ``init(model, key)``, ``loss(model,
                                   params, batch, precision)`` and
                                   ``train_flops(model, batch, seq)``: the
                                   plain reference and the operations one
                                   client's forward and backward need
                                   (imports nothing of the program).

Adding a family takes these two new files only.
"""

from __future__ import annotations

import importlib.util
import pathlib

BENCH = pathlib.Path(__file__).resolve().parent
DEFAULT = "llama"


def load_module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}".replace(".", "_").replace(
            "-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load(kind: str, model: dict, bench: pathlib.Path = BENCH):
    """The family's ``kind`` file (``arch`` or ``reference``) under
    ``bench``, as a module."""
    name = model.get("model_type", DEFAULT)
    path = bench / kind / f"{name}.py"
    if not path.is_file():
        raise LookupError(f"no {kind} for model_type {name!r}: looked for "
                          f"{path}")
    return load_module(path)
