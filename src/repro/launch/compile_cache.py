"""JAX's persistent compilation cache for the entry points.

Called by each entry point's ``main`` (``serve``, ``train``,
``chip_smoke.py``, ``bench/run.py`` and ``bench/control*.py``), never
at import.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, that directory is the cache and
no other is set.  Otherwise the cache lives at the fixed
``.jax_cache/`` of the checkout (git-ignored): the path is part of what
a later process must find, so it never depends on the pid, the clock
or a temporary directory.
"""
from __future__ import annotations

import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..",
    ".jax_cache")


def enable() -> str:
    """Turn the persistent cache on; return the directory in use."""
    path = os.environ.get(ENV) or os.path.normpath(CHECKOUT_CACHE)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
