"""Where compiled programs live: XLA's persistent cache on disk, and the one
set of jitted programs this process keeps for its next job.

**On disk.**  One rule, applied by every entry point (``cli._dispatch``,
``chip_smoke.py``, the elastic controllers) before anything compiles:

- ``JAX_COMPILATION_CACHE_DIR`` set from outside: nothing is touched —
  every cache file lands there and nowhere else;
- unset: it becomes ``<checkout>/.jax_cache``.  The path is part of the
  cache key, so it is a fixed directory — never a temp name, pid or time —
  and a second run in the same checkout finds what the first compiled.

**In the process.**  ``jax.jit`` finds a program again by the function object
it wrapped, so a trainer that makes its jitted closures inside the job traces,
lowers and loads them from the disk cache again in every job of a process (a
retrain daemon's, the benchmark's).  :data:`PROGRAMS` keeps the last job's
jitted callables under a key the trainer makes of everything they close over
or are specialised to, and hands them to the next job that asks with an equal
key.  One entry, not a table: a step program is a few hundred megabytes of
code resident on the device, and a daemon whose table grows between retrains
must not keep one executable a cycle alive.  It holds callables, never
arrays.

No jax import here (``initialize_distributed`` and ``lint`` stay
jax-free); a jax that is already imported read its config from the
environment at import, so it is told the same directory.
"""

from __future__ import annotations

import os
import sys
import threading
from typing import Any, Callable, Hashable, Tuple

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


class ProgramHolder:
    """The jitted programs of the last job that asked, for the next one."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._key: Any = None
        self._held: Any = None

    def programs(self, key: Hashable, build: Callable[[], Any]) -> Tuple[Any, bool]:
        """(programs, reused): the held ones when ``key`` equals the key
        they were built under; else ``build()``'s, held from now on — the old
        entry is dropped first, so its executables can leave the device
        before the new ones are loaded."""
        with self._lock:
            if self._held is not None and key == self._key:
                return self._held, True
            self._key = self._held = None
            held = build()
            self._key, self._held = key, held
            return held, False

    def clear(self) -> None:
        with self._lock:
            self._key = self._held = None


PROGRAMS = ProgramHolder()          # one a process: what bounds the code kept on the device
