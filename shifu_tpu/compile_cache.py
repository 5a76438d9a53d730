"""Where XLA's persistent compilation cache lives.

One rule, applied by every entry point (``cli._dispatch``,
``chip_smoke.py``, the elastic controllers) before anything compiles:

- ``JAX_COMPILATION_CACHE_DIR`` set from outside: nothing is touched —
  every cache file lands there and nowhere else;
- unset: it becomes ``<checkout>/.jax_cache``.  The path is part of the
  cache key, so it is a fixed directory — never a temp name, pid or time —
  and a second run in the same checkout finds what the first compiled.

No jax import here (``initialize_distributed`` and ``lint`` stay
jax-free); a jax that is already imported read its config from the
environment at import, so it is told the same directory.
"""

from __future__ import annotations

import os
import sys

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def configure() -> str:
    """Apply the rule above; returns the directory in force."""
    path = os.environ.get(ENV)
    if path:
        return path
    os.environ[ENV] = DEFAULT_DIR
    jax = sys.modules.get("jax")
    if jax is not None:
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
