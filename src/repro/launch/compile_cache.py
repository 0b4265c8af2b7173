"""Persistent compilation cache for the program's entry points.

Entry points (``chip_smoke.py``, ``python -m repro.launch.service``,
``python -m benchmarks.run``) call :func:`enable_compile_cache` once
when run as programs; nothing calls it at import and tests never do
(they call ``repro.launch.service.main`` directly, so the launcher calls
it from its ``__main__`` block), so a test process compiles without
touching the cache.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
this sets no other directory.  Otherwise the cache lives at the fixed
path ``<repo>/.jax_compile_cache`` (listed in ``.gitignore``): the path
is part of what makes an entry found again, so it never holds a
temporary name, a process id or a time.
"""

from __future__ import annotations

import os
import pathlib

import jax

REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = REPO_ROOT / ".jax_compile_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on for every compile, kernels included,
    and return its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    # small Pallas kernels compile in well under the 1 s default
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
