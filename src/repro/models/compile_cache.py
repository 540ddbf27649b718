"""Where JAX keeps its persistent compilation cache.

An entry point (``chip_smoke.py``, ``benchmarks/run.py``) calls
`enable` once, before its first compile. Where ``JAX_COMPILATION_CACHE_DIR``
is set, JAX reads it itself and nothing here changes it. Otherwise the
cache lives at a fixed path inside the checkout: JAX keys entries by
their directory too, so a path that moved between runs would never
hit. Importing this module sets nothing, and no library code calls
`enable`, so the test suite runs without a cache.
"""
from __future__ import annotations

import os

#: ``<repo>/.jax_cache`` (gitignored)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")

ENV = "JAX_COMPILATION_CACHE_DIR"


def enable() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    path = os.environ.get(ENV)
    if path:
        return path
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
