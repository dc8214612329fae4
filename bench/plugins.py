"""Pieces of the benchmark found by name: a Python file under ``bench/``
loaded as a module of its own, so that a later cell, mix or metric adds a
file and edits none."""
from __future__ import annotations

import functools
import importlib.util
import os

BENCH = os.path.dirname(os.path.abspath(__file__))

__all__ = ["BENCH", "load"]


@functools.lru_cache(maxsize=None)
def load(*parts: str):
    """The module in ``bench/<parts...>.py`` (``FileNotFoundError`` when
    there is none)."""
    path = os.path.join(BENCH, *parts) + ".py"
    if not os.path.isfile(path):
        raise FileNotFoundError(path)
    name = "bench_" + "_".join(parts).replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
