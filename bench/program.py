"""The system under test, built from a configuration file.

The modules of the benchmark that import the program are this one, the
drivers and the family mappings ``arch/<model_type>.py``; nothing under
``reference/`` does.  ``arch`` finds the configuration's family mapping
by its ``model_type`` (``families.py``).
"""

from __future__ import annotations

import pathlib

import families


def arch(config: dict, bench: pathlib.Path = families.BENCH):
    """The program's ``ArchConfig`` of ``config``, from its family's
    ``arch/<model_type>.py`` under ``bench``."""
    return families.load("arch", config["model"], bench).arch(config)
