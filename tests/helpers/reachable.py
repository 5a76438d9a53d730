"""What a held object keeps alive on the device: the jax Arrays reachable from
it, for the tests that hold a trainer's programs across jobs
(``compile_cache.PROGRAMS``)."""

import gc
import types

import jax


def reachable_arrays(root):
    """jax Arrays reachable from ``root`` through what the garbage collector
    can see, a function's module globals and classes left out (they are the
    interpreter's, not the programs')."""
    seen, stack, found = set(), [root], []
    while stack:
        o = stack.pop()
        if id(o) in seen or isinstance(o, (types.ModuleType, type, str, bytes, int, float)):
            continue
        seen.add(id(o))
        if isinstance(o, jax.Array):
            found.append(o)
            continue
        skip = getattr(o, "__globals__", None) if isinstance(o, types.FunctionType) else None
        stack.extend(r for r in gc.get_referents(o) if r is not skip)
    return found
