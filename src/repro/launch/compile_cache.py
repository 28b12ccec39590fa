"""Where JAX keeps its persistent compilation cache.

Entry points (the trainer, the server, ``chip_smoke.py``) call
:func:`use_compile_cache` before they compile anything; importing the
package never does, so the test suite writes no cache entries.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads that directory from
  the environment, and nothing here overrides it.
* otherwise: the cache lives at a fixed path inside the checkout,
  ``<repo>/.jax_cache`` (git-ignored).  The path is part of the cache key, so
  it is never built from a temporary name, a pid or the time.
"""

from __future__ import annotations

import os

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "use_compile_cache"]

# src/repro/launch/ -> the checkout root
CHECKOUT_CACHE_DIR = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..", ".jax_cache"))


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory (see the module docstring for which one)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
