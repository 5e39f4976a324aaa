"""JAX's persistent compilation cache for the entry points.

Called by ``chip_smoke.py``, ``examples/serve_anytime.py`` and
``repro.launch.serve.main`` — never at import, so tests and library users
keep JAX's own defaults.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: fixed in-repo location (gitignored); a path made from a tempdir, pid or
#: time would never be found again by the next run
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and wins:
    nothing else is set.  Otherwise the cache goes to ``<repo>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
