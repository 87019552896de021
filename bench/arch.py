"""The architecture a configuration names, found by name.

A configuration file's ``reference`` key names two modules:
``bench/references/<name>.py``, the plain reference of the published model
(its weight layout, its arithmetic, ``logits_at``), which imports nothing
of the program; and ``bench/adapters/<name>.py``, how the program serves
it (``model_config``, ``params``, ``departures``). A new architecture adds
both files and edits none.
"""
from __future__ import annotations

import importlib


def reference(cfg: dict):
    return importlib.import_module(f"bench.references.{cfg['reference']}")


def adapter(cfg: dict):
    return importlib.import_module(f"bench.adapters.{cfg['reference']}")
