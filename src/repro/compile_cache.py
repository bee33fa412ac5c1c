"""Persistent XLA compilation cache for the program's entry points.

The cache key includes the directory, so the directory must not move
between runs: it is either the one `JAX_COMPILATION_CACHE_DIR` names (JAX
reads that variable itself) or one fixed path inside the checkout. Entry
points (`chip_smoke.py`, `examples/*.py`, `benchmarks/run.py`) call
`enable()` before their first compilation; library code and tests never do.
"""
from __future__ import annotations

import os

import jax

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def enable() -> str:
    """Turn the persistent cache on; return the directory it uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
