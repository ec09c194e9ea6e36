"""Compile caches and program names of the stage programs.

A stage program has one life: the in-memory operator cache
(``exec/local.py _OP_CACHE``) -> ``jax.jit(named(fn, program_name(key)))``
under ``_compile_timed`` -> JAX's jit cache -> JAX's persistent
compilation cache, in the directory :func:`place_jax_cache` chose. That
persistent cache is the one cross-process cache: it keys a program by
its module (name included) and the compiler's environment, so a second
process deserializes what a first one compiled.
"""

from __future__ import annotations

import hashlib
import os
import re
from typing import Optional, Tuple


#: where jax's own persistent compilation cache goes when the
#: environment does not place it: one fixed directory in the checkout
#: (the path is part of what a later process must find again, so it
#: never comes from tempfile, a pid or the time)
JAX_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")
_JAX_CACHE_PLACED = False


def place_jax_cache() -> str:
    """The one rule for jax's persistent compilation cache, which
    holds every XLA program this process compiles: the stage programs
    and the many small eager-op dispatches a cold process otherwise
    compiles one by one. ``JAX_COMPILATION_CACHE_DIR`` set: jax already
    reads it, and this program never touches the setting. Unset: the
    cache goes to :data:`JAX_CACHE_DIR`. Either way the two thresholds
    drop to zero, because exactly those small programs are the
    cold-start long tail. Called before the first compile by every
    entry point (a session, a worker process, the benchmark). Returns
    the directory in use."""
    global _JAX_CACHE_PLACED
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if _JAX_CACHE_PLACED:
        return env_dir or JAX_CACHE_DIR
    _JAX_CACHE_PLACED = True
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    if not env_dir:
        from jax.experimental.compilation_cache import \
            compilation_cache as cc
        jax.config.update("jax_compilation_cache_dir", JAX_CACHE_DIR)
        # jax latches the cache decision at the FIRST compile; module
        # imports usually compile something before this runs, so the
        # latch must be reset for the dir to take
        cc.reset_cache()
    return env_dir or JAX_CACHE_DIR


_ADDRESS = re.compile(r" at 0x[0-9a-fA-F]+")


def program_name(key) -> str:
    """``sail_<site>_<8 hex>``: the name a stage's jitted program runs
    under, so that its XLA module (``jit_sail_join_phase_1a2b3c4d``), the
    executor's ``dispatch`` span and the device trace's operations name
    the same thing. ``site`` is ``key[0]`` as the call sites write it;
    the digest is of the structural key's repr alone — no ``id()``, no
    dictionary identity, no seed, no process — because JAX's persistent
    cache keys the module name: a name that moved between processes
    would make every start a cold one."""
    site = key[0] if isinstance(key, tuple) and key \
        and isinstance(key[0], str) else "op"
    digest = hashlib.sha256(
        _ADDRESS.sub("", repr(key)).encode()).hexdigest()[:8]
    return f"sail_{site}_{digest}"


def named(fn, name: str):
    """``fn`` under ``name`` (what ``jax.jit`` calls the module)."""
    try:
        fn.__name__ = fn.__qualname__ = name
        return fn
    except (AttributeError, TypeError):
        def program(*args, **kwargs):
            return fn(*args, **kwargs)
        program.__name__ = program.__qualname__ = name
        return program


def signature(args) -> Optional[Tuple]:
    """Hashable abstract signature of a call: the pytree structure plus
    per-leaf (shape, dtype, weak_type). Non-array leaves contribute
    their type only (jit traces them as weak-typed scalars)."""
    import jax
    try:
        leaves, treedef = jax.tree_util.tree_flatten(args)
        sig = []
        for x in leaves:
            if hasattr(x, "shape") and hasattr(x, "dtype"):
                sig.append((tuple(x.shape), str(x.dtype),
                            bool(getattr(x, "weak_type", False))))
            else:
                sig.append(("py", type(x).__name__))
        return (treedef, tuple(sig))
    except Exception:  # noqa: BLE001 — unflattenable args: no persistence
        return None
