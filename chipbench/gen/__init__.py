"""Input generators, one module per kind. A traffic file names its
``generator``; every kind draws everything from ``--seed``."""

import importlib


def load(kind: str):
    return importlib.import_module(f"chipbench.gen.{kind}")
