"""Plain references, one module per family, found by the name a
configuration file gives under ``reference``."""

import importlib


def load(cfg: dict):
    return importlib.import_module(f"benchmarks.references.{cfg['reference']}")
