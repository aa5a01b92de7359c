"""Where the compile caches live: placed from outside, else one fixed
directory inside the checkout.

Two caches persist compiled programs across processes: JAX's own
persistent compilation cache, and this package's AOT executable cache
(``FLAGS_compile_cache_dir``). The directory is part of JAX's cache
key, so a directory that moves (a ``mkdtemp`` name, a pid, a timestamp)
never hits. Measuring entry points therefore call ``place_jax_cache()``
before their first use of JAX and, where they turn the AOT cache on,
point it at ``aot_cache_dir()``.
"""
from __future__ import annotations

import os
import shutil

__all__ = ["cache_root", "place_jax_cache", "aot_cache_dir",
           "fresh_scratch_dir"]

_JAX_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def cache_root() -> str:
    """``<checkout>/.cache`` (listed in ``.gitignore``)."""
    return os.path.join(_CHECKOUT, ".cache")


def place_jax_cache() -> str:
    """Turn JAX's persistent compilation cache on and return its
    directory. Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it
    itself and no directory is set in code; otherwise the cache goes to
    ``<checkout>/.cache/jax``. Every compile is kept, however short:
    the default one-second floor would recompile the small programs on
    every start."""
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    placed = os.environ.get(_JAX_CACHE_ENV)
    if placed:
        return placed
    path = os.path.join(cache_root(), "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def aot_cache_dir() -> str:
    """The fixed home of this package's AOT cache, beside JAX's: under
    the directory ``JAX_COMPILATION_CACHE_DIR`` names when it is set
    (what the machine keeps between calls, it keeps there), else under
    ``cache_root()``."""
    return os.path.join(os.environ.get(_JAX_CACHE_ENV) or cache_root(),
                        "paddle_aot")


def fresh_scratch_dir(name: str) -> str:
    """``<cache_root>/<name>``, emptied: the scratch directory of a
    measuring tool (model artifacts, the cold and shared caches it
    builds itself). A fixed name with fresh contents — what is cached
    against a ``mkdtemp`` path moves with it on every run."""
    path = os.path.join(cache_root(), name)
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    return path
