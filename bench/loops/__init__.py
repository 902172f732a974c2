"""One module per loop that drives a cell, found by the ``loop`` name of its
traffic mix; see :mod:`bench.context` for what a loop defines."""

import importlib


def get(name: str):
    return importlib.import_module(f"{__name__}.{name}")
