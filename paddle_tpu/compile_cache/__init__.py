"""paddle_tpu.compile_cache — persistent AOT compile cache.

Cold start is the un-amortized cost of an XLA-backed stack: every fresh
process re-traces and re-compiles programs whose inputs, code, and
flags have not changed since the last run. The reference framework's
in-process caches (PHI ``KernelFactory``, the executor program cache)
stop at the process boundary; this package extends them across it:

- ``fingerprint``: stable cache keys over (function/model identity,
  abstract operand signature, mesh, compile-relevant ``FLAGS_*``,
  jax/jaxlib + backend versions) — computed WITHOUT tracing;
- ``store``: a disk store with atomic writes, size-bounded LRU
  eviction, and corruption-tolerant reads (a bad entry is evicted,
  never fatal);
- ``cache``: ``CompileCache`` — serialized AOT executables via
  ``jax.experimental.serialize_executable`` with a ``jax.export``
  StableHLO fallback tier, plus the ``paddle_compile_cache_*`` metric
  families;
- ``manifest``: ``WarmupManifest`` — the batch signatures a serving
  process actually compiled, so a restart pre-warms exactly the
  observed lattice from cache;
- ``placement``: where the caches live — JAX's persistent compilation
  cache where ``JAX_COMPILATION_CACHE_DIR`` says, else at one fixed
  path inside the checkout, with this package's AOT cache beside it.

Wired into the three compile sites: ``jit.to_static`` (non-
differentiating calls), ``jit.TrainStep``, and the serving
``Predictor``/``InferenceServer`` warmup + runtime dispatch. Enable
with ``FLAGS_compile_cache_dir=/path`` (and optionally
``FLAGS_compile_cache_max_bytes``); measure with
``tools/bench_coldstart.py``.
"""
from __future__ import annotations

from . import fingerprint  # noqa: F401
from .cache import (  # noqa: F401
    CompileCache, default_cache, reset_default_cache, stats,
)
from .fingerprint import (  # noqa: F401
    avals_signature, bytes_fingerprint, cache_key, compile_relevant_flags,
    environment_fingerprint, function_fingerprint, layer_fingerprint,
    mark_compile_relevant, mesh_fingerprint,
)
from .manifest import WarmupManifest  # noqa: F401
from .placement import (  # noqa: F401
    aot_cache_dir, cache_root, fresh_scratch_dir, place_jax_cache,
)
from .store import CacheStore  # noqa: F401

__all__ = [
    "CompileCache", "CacheStore", "WarmupManifest",
    "default_cache", "reset_default_cache", "stats",
    "cache_root", "place_jax_cache", "aot_cache_dir",
    "fresh_scratch_dir",
    "cache_key", "function_fingerprint", "layer_fingerprint",
    "mesh_fingerprint", "environment_fingerprint", "avals_signature",
    "bytes_fingerprint", "compile_relevant_flags", "mark_compile_relevant",
    "fingerprint",
]
