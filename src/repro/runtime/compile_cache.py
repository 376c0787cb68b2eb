"""Persistent XLA compilation cache for the command-line entry points.

A fit compiles one accumulate per block geometry and a handful of
finalize/fold programs; on a fresh process that compile is a large part of
a short run.  ``enable_compile_cache`` lets a later process load them from
disk instead:

* where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
  nothing is set here;
* otherwise the cache lives at ``<checkout>/.jax_cache`` — one fixed path,
  because the path is part of what makes an entry findable again.

Either way every compile is kept, however short: JAX's default keeps only
those of a second or more, and a fit's programs mostly compile in less,
so a later process would compile them all again.

Library code never calls this; the launchers, benchmarks and
``chip_smoke.py`` call it before their first compile.
"""

from __future__ import annotations

import os
import pathlib

REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
