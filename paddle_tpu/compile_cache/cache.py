"""CompileCache: persistent AOT executables keyed by stable fingerprints.

The reference framework amortizes compilation *within* a process (PHI
``KernelFactory``, the executor program cache); this module amortizes it
*across* processes: a compiled XLA executable is serialized to disk
(``jax.experimental.serialize_executable`` — the loaded form skips both
the Python trace and the XLA compile) keyed by the full fingerprint
from ``fingerprint.cache_key``. Where the backend cannot serialize
executables, the fallback tier stores the traced program as a
``jax.export`` StableHLO blob instead — a load then skips the Python
trace (the expensive half of cold start for big Python model stacks)
and pays only the XLA compile.

Every lookup/store reports into the ``paddle_compile_cache_*`` metric
families on the default observability registry:

    paddle_compile_cache_hits_total{site=}      persistent-cache hits
    paddle_compile_cache_misses_total{site=}    lookups that compiled
    paddle_compile_cache_errors_total{site=,kind=}  corrupt / unserializable
    paddle_compile_cache_fallbacks_total{site=} stablehlo-tier stores on
                                                backends that cannot
                                                serialize executables
                                                (designed, not an error)
    paddle_compile_cache_evictions_total        LRU evictions
    paddle_compile_cache_stored_total{site=,kind=}  entries written
    paddle_compile_cache_bytes                  on-disk size
    paddle_compile_cache_entries                on-disk entry count
    paddle_compile_cache_load_ms{site=}         deserialize+load latency

Enabled by pointing ``FLAGS_compile_cache_dir`` at a directory (empty =
disabled, the default); ``FLAGS_compile_cache_max_bytes`` bounds the
LRU store.
"""
from __future__ import annotations

import pickle
import threading
import time
from typing import Callable, Optional, Tuple

from ..observability.registry import default_registry
from .store import CacheStore

__all__ = ["CompileCache", "default_cache", "reset_default_cache", "stats"]

KIND_EXECUTABLE = "executable"
KIND_STABLEHLO = "stablehlo"


class _Metrics:
    """The paddle_compile_cache_* families (process-wide, shared by
    every CompileCache instance)."""

    def __init__(self, registry=None):
        reg = registry or default_registry()
        self.hits = reg.counter(
            "paddle_compile_cache_hits_total",
            "persistent compile-cache hits (an AOT executable or traced "
            "program was loaded instead of compiled)", ("site",))
        self.misses = reg.counter(
            "paddle_compile_cache_misses_total",
            "persistent compile-cache misses (a fresh compile ran)",
            ("site",))
        self.errors = reg.counter(
            "paddle_compile_cache_errors_total",
            "cache entries evicted as corrupt / failed serializations",
            ("site", "kind"))
        self.fallbacks = reg.counter(
            "paddle_compile_cache_fallbacks_total",
            "stores that skipped the executable tier because this "
            "backend cannot serialize executables (the StableHLO tier "
            "is the designed path there — not an error)", ("site",))
        self.evictions = reg.counter(
            "paddle_compile_cache_evictions_total",
            "entries removed by LRU size bounding")
        self.stored = reg.counter(
            "paddle_compile_cache_stored_total",
            "entries written, by payload kind", ("site", "kind"))
        self.bytes = reg.gauge(
            "paddle_compile_cache_bytes", "total on-disk cache size")
        self.entries = reg.gauge(
            "paddle_compile_cache_entries", "on-disk cache entry count")
        self.load_ms = reg.histogram(
            "paddle_compile_cache_load_ms",
            "deserialize+load latency of cache hits", ("site",))


_metrics_lock = threading.Lock()
_metrics: Optional[_Metrics] = None


def _get_metrics() -> _Metrics:
    global _metrics
    with _metrics_lock:
        if _metrics is None:
            _metrics = _Metrics()
        return _metrics


# Whether this backend can serialize compiled executables, probed once
# per process (None = not yet probed). Distinguishes the DESIGNED
# fallback on backends without serialization support (counted under
# fallbacks_total) from a genuine serialize failure on a supporting
# backend (counted under errors_total) — otherwise such backends ring
# the error alarm once per compile, masking real corruption.
_serialize_support_lock = threading.Lock()
_serialize_support: Optional[bool] = None


def _serialize_supported() -> bool:
    global _serialize_support
    with _serialize_support_lock:
        if _serialize_support is None:
            try:
                import jax
                from jax.experimental import serialize_executable
                probe = jax.jit(lambda: 0).lower().compile()
                serialize_executable.serialize(probe)
                _serialize_support = True
            except Exception:  # noqa: BLE001 - any probe failure means
                # the executable tier is unavailable on this backend
                _serialize_support = False
        return _serialize_support


class CompileCache:
    """Disk-backed cache of compiled programs.

    ``load`` returns a ready-to-call executable (or None); ``store``
    serializes a ``jax.stages.Compiled``; ``get_or_compile`` is the
    one-stop wrapper the compile sites use. All failure modes degrade
    to a recompile — nothing in here may raise into a serving loop."""

    def __init__(self, directory: str, max_bytes: int = 0, registry=None):
        self.store_backend = CacheStore(directory, max_bytes)
        self.metrics = _Metrics(registry) if registry is not None \
            else _get_metrics()
        self._refresh_gauges()

    @property
    def directory(self) -> str:
        return self.store_backend.directory

    def _refresh_gauges(self):
        entries = self.store_backend.entries()
        self.metrics.entries.set(len(entries))
        self.metrics.bytes.set(sum(size for _, size, _ in entries))

    # ------------------------------------------------------------ load
    def load(self, key: str, site: str = "default"):
        """Materialize the cached executable for ``key``, or None.
        Counts a hit or a miss; a corrupt/unloadable entry is evicted
        and counted as an error + miss."""
        return self.load_ex(key, site=site)[0]

    def load_ex(self, key: str, site: str = "default"):
        """``load`` plus the stored payload kind of a hit —
        ``(fn, "executable" | "stablehlo")`` or ``(None, None)`` — so
        ``get_or_compile`` can record the tier in xstats provenance."""
        t0 = time.perf_counter()
        try:
            record = self.store_backend.get(key)
        except Exception:  # noqa: BLE001 - corrupt record: already evicted
            self.metrics.errors.labels(site=site, kind="corrupt").inc()
            record = None
        fn, kind = None, None
        if record is not None:
            try:
                fn = self._materialize(record)
                kind = record["kind"]
            except Exception:  # noqa: BLE001 - undeserializable (e.g. a
                # different jaxlib wrote it despite the env fingerprint,
                # or a truncated payload that unpickled): evict, recompile
                self.store_backend.remove(key)
                self.metrics.errors.labels(site=site,
                                           kind="deserialize").inc()
                fn, kind = None, None
        if fn is None:
            self.metrics.misses.labels(site=site).inc()
            return None, None
        self.metrics.hits.labels(site=site).inc()
        self.metrics.load_ms.labels(site=site).observe(
            (time.perf_counter() - t0) * 1e3)
        return fn, kind

    def _materialize(self, record):
        kind = record["kind"]
        if kind == KIND_EXECUTABLE:
            import jax
            from jax.experimental import serialize_executable
            device_ids, payload = pickle.loads(record["payload"])
            # load onto the devices the executable was compiled for, in
            # their assignment order; left out, JAX loads a one-device
            # program onto every local device and the first call fails
            by_id = {d.id: d for d in jax.devices()}
            return serialize_executable.deserialize_and_load(
                *payload,
                execution_devices=[by_id[i] for i in device_ids])
        if kind == KIND_STABLEHLO:
            import jax
            from jax import export as jexport
            exported = jexport.deserialize(record["payload"])
            # the trace is skipped; XLA still compiles at first call
            return jax.jit(exported.call)
        raise ValueError(f"unknown cache record kind {kind!r}")

    # ----------------------------------------------------------- store
    def store(self, key: str, compiled, meta: Optional[dict] = None,
              site: str = "default",
              exported_fallback: Optional[Callable] = None
              ) -> Optional[str]:
        """Serialize ``compiled`` under ``key``; returns the stored kind
        or None. When executable serialization is unsupported on this
        backend, ``exported_fallback()`` (returning a ``jax.export``
        Exported or its serialized bytes) provides the traced-lowering
        tier instead."""
        payload, kind = None, None
        if _serialize_supported():
            try:
                from jax.experimental import serialize_executable
                device_ids = [
                    d.id for d in
                    compiled.runtime_executable().local_devices()]
                payload = pickle.dumps(
                    (device_ids, serialize_executable.serialize(compiled)),
                    protocol=4)
                kind = KIND_EXECUTABLE
            except Exception:  # noqa: BLE001 - a genuine serialize
                # failure on a supporting backend: count it, fall
                # through to the stablehlo tier
                self.metrics.errors.labels(site=site,
                                           kind="serialize").inc()
        else:
            # backend without executable serialization: the stablehlo
            # tier is the designed path, counted as a fallback
            self.metrics.fallbacks.labels(site=site).inc()
        if payload is None and exported_fallback is not None:
            try:
                exported = exported_fallback()
                payload = exported if isinstance(exported, bytes) \
                    else exported.serialize()
                kind = KIND_STABLEHLO
            except Exception:  # noqa: BLE001 - no persistable form at all
                self.metrics.errors.labels(site=site,
                                           kind="export").inc()
                return None
        if payload is None:
            return None
        try:
            before = {k for k, _, _ in self.store_backend.entries()}
            self.store_backend.put(key, {"kind": kind, "payload": payload,
                                         "meta": meta})
            after = {k for k, _, _ in self.store_backend.entries()}
            evicted = len(before - after - {key})
            if evicted:
                self.metrics.evictions.inc(evicted)
        except Exception:  # noqa: BLE001 - a full/readonly disk must not
            # break the compile path; the executable is still used live
            self.metrics.errors.labels(site=site, kind="write").inc()
            return None
        self.metrics.stored.labels(site=site, kind=kind).inc()
        self._refresh_gauges()
        return kind

    # -------------------------------------------------------- combined
    def get_or_compile(self, key: str, build: Callable, *,
                       site: str = "default", meta: Optional[dict] = None,
                       exported_fallback: Optional[Callable] = None,
                       xstats_meta: Optional[dict] = None
                       ) -> Tuple[Callable, bool]:
        """Load ``key`` or ``build()`` (a ``jax.stages.Compiled``),
        store it, and return ``(callable, was_hit)``.

        ``xstats_meta`` (``{"kind", "signature", "fingerprint",
        "spec_hash", "lower_thunk", "provenance"}``, all optional)
        registers the resulting executable in the xstats registry with
        hit/miss/tier provenance added here — the one chokepoint every
        persistent-cache compile site flows through."""
        fn, tier = self.load_ex(key, site=site)
        if fn is not None:
            self._register_xstats(site, key, fn, hit=True, tier=tier,
                                  xstats_meta=xstats_meta)
            return fn, True
        # a miss compiles: the build is compile badput on the goodput
        # ledger (a frame, so jax.monitoring compile events firing
        # inside claim their share instead of double-counting)
        from ..observability.goodput import default_ledger
        with default_ledger().timed("compile"):
            compiled = build()
        stored = self.store(key, compiled, meta=meta, site=site,
                            exported_fallback=exported_fallback)
        self._register_xstats(site, key, compiled, hit=False,
                              tier=stored, xstats_meta=xstats_meta)
        return compiled, False

    @staticmethod
    def _register_xstats(site: str, key: str, fn, *, hit: bool,
                         tier: Optional[str],
                         xstats_meta: Optional[dict]):
        """Best-effort xstats registration of a cache-mediated
        executable; the cost/memory analysis is read straight off the
        Compiled when the tier allows (the stablehlo tier hands over
        the caller's lower thunk instead)."""
        try:
            from ..observability import xstats
            if not xstats.enabled():
                return
            m = xstats_meta or {}
            prov = dict(m.get("provenance") or {})
            prov["cache"] = "hit" if hit else "miss"
            if tier:
                prov["tier"] = tier
            signature = m.get("signature") or ((("key",), key),)
            xstats.register_executable(
                site, signature, kind=m.get("kind"),
                fingerprint=m.get("fingerprint"),
                spec_hash=m.get("spec_hash"), provenance=prov,
                compiled=fn if hasattr(fn, "cost_analysis") else None,
                lower_thunk=m.get("lower_thunk"))
        except Exception:  # noqa: BLE001 - observability must never
            pass           # break the compile path


# ------------------------------------------------------- default cache
_default_lock = threading.Lock()
_default: Optional[Tuple[Tuple[str, int], CompileCache]] = None


def default_cache() -> Optional[CompileCache]:
    """The process-wide cache configured by ``FLAGS_compile_cache_dir``
    / ``FLAGS_compile_cache_max_bytes``; None when disabled (empty dir,
    the default). Re-reads the flags so tests and long-lived processes
    can repoint it with ``set_flags``."""
    from ..framework.flags import flag_value
    global _default
    directory = str(flag_value("FLAGS_compile_cache_dir") or "")
    if not directory:
        return None
    max_bytes = int(flag_value("FLAGS_compile_cache_max_bytes"))
    cfg = (directory, max_bytes)
    with _default_lock:
        if _default is None or _default[0] != cfg:
            _default = (cfg, CompileCache(directory, max_bytes))
        return _default[1]


def reset_default_cache():
    """Drop the memoized default cache and the serialize-support probe
    (tests that swap directories or monkeypatch serialization)."""
    global _default, _serialize_support
    with _default_lock:
        _default = None
    with _serialize_support_lock:
        _serialize_support = None


def stats() -> dict:
    """Process-wide compile-cache accounting, summed over sites — the
    numbers ``tools/bench_coldstart.py`` cross-checks against a scraped
    ``/metrics`` page."""
    m = _get_metrics()

    def total(counter):
        return int(sum(child.value for _, child in counter.items()))

    return {
        "hits": total(m.hits),
        "misses": total(m.misses),
        "errors": total(m.errors),
        "fallbacks": total(m.fallbacks),
        "evictions": total(m.evictions),
        "stored": total(m.stored),
        "bytes": int(m.bytes.value),
        "entries": int(m.entries.value),
    }
