"""Where JAX keeps its persistent compilation cache.

Entry points that compile (``chip_smoke.py``, ``bench/run.py``)
call ``configure()`` before their first compile; importing the package
does not.  ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own
setting and is left alone.  Otherwise the cache goes to one fixed
directory in the checkout, ``.jax_cache`` (git-ignored), so the next
run from the same checkout finds what this one compiled.
"""

from __future__ import annotations

import os

DEFAULT_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), ".jax_cache")


def configure() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR


__all__ = ["DEFAULT_DIR", "configure"]
