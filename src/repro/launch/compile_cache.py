"""JAX's persistent compilation cache, kept at one fixed place.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and this
sets no other directory.  Otherwise the cache lives at ``.jax_cache/`` in
the root of the checkout (git-ignored): a fixed path, never a temporary,
per-process or timestamped one, so a later run of the same checkout finds
what an earlier one compiled.  Entry points call ``enable_compile_cache()``
before their first compile; library code never does.

A process whose programs span several devices (tiers placed on pod slices
of several chips) runs with the cache off, wherever it points: on a
four-chip v5e host such a run halted the chips of a two-chip slice when its
programs were read back from a warm cache, and passed compiled cold
(PERF.md, open questions).  jax decides whether to use the cache once per
process, so the choice is made here, before anything compiles.
"""
from __future__ import annotations

import os
from typing import Optional

import jax

REPO_ROOT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", "..")
)


def enable_compile_cache(*, several_devices: bool = False) -> Optional[str]:
    """Turn the persistent cache on and return its directory; with
    ``several_devices`` turn it off and return None."""
    if several_devices:
        jax.config.update("jax_enable_compilation_cache", False)
        return None
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(REPO_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
