"""Plain references, one module per model family, named by a
configuration's ``reference`` key.  Each module gives the family's
weight layout (``weight_spec``), its forward pass (``forward``,
``output_table``) and its operations per token (``token_flops``), and
imports nothing of the program."""
from __future__ import annotations

import importlib


def reference(config: dict):
    """The module ``bench/models/<config["reference"]>.py``."""
    return importlib.import_module(f"bench.models.{config['reference']}")
