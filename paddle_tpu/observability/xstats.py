"""Executable cost & roofline observability — the ``/execz`` registry,
cost-model MFU attribution, and on-demand / anomaly-triggered device
profiling (``/profilez``).

Three layers, one module:

**Executable registry.** Every compile site — ``compile_cache.
get_or_compile`` (the persistent-cache chokepoint), ``StaticFunction``,
``TrainStep``, ``Predictor._aot_serving_call``, and the
``CachedDecoder`` prefill/decode/chunked/verify entry points — registers
each compiled signature here with its provenance (site tag, cache
hit/miss/fallback tier, function fingerprint, spec-tree hash) and a
handle through which XLA's own cost model is read: ``cost_analysis()``
(FLOPs, bytes accessed, transcendentals) and ``memory_analysis()``
(argument / output / temp / generated-code bytes). Where the site holds
a ``jax.stages.Compiled`` the analysis is a direct C++ call; where only
a jitted function exists (persistent cache disabled) the site hands over
a *lower thunk* and the analysis is computed lazily at scrape time from
``Lowered.cost_analysis()`` — never on the dispatch hot path.

**MFU / roofline join.** The continuous step profiler (PR 11) drops one
wall-time envelope per step; this module joins each envelope's *kind*
(train / prefill / decode / verify) with the most recently dispatched
executable of that kind and derives live gauges::

    paddle_mfu{kind=}            achieved FLOP/s over device peak
    paddle_exec_bw_util{kind=}   achieved bytes/s over peak bandwidth
    paddle_exec_flops{kind=}     cost-model FLOPs of the live executable
    paddle_exec_bytes_accessed{kind=}

plus a roofline classification per executable: arithmetic intensity
(FLOPs / bytes accessed) against the platform ridge point
(peak FLOP/s / peak bytes/s). Peaks come from ``FLAGS_device_peak_flops``
/ ``FLAGS_device_peak_bytes_per_s`` (CPU CI sets these explicitly) or
the built-in per-platform table. Everything is served as
``GET /execz`` on the telemetry httpd, replica workers, and — fleet
aggregated — the router.

**Profile capture.** ``GET /profilez?duration_ms=`` runs one bounded
``jax.profiler`` trace capture and returns a chrome-trace document
(also persisted into a bounded on-disk ring, rate-limited by
``FLAGS_profile_min_interval_s``); ``GET /profilez`` lists the ring.
With ``FLAGS_profile_on_anomaly`` armed, a stepprof straggler triggers
exactly one rate-limited background capture whose artifact records the
promoted ``stepprof::straggler`` span's trace id — a slow step at 3am
leaves behind an actual device profile, not just a counter bump.
"""
from __future__ import annotations

import gzip
import json
import os
import tempfile
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from .registry import default_registry

__all__ = [
    "ExecEntry", "ExecRegistry", "ProfileRing",
    "default_exec_registry", "default_profile_ring",
    "register_executable", "note_dispatch", "on_step_envelope",
    "on_anomaly", "enabled", "device_peaks", "execz_payload",
    "profilez_payload", "capture_profile", "wait_captures",
    "reset_for_tests", "SITE_KINDS", "signature_of",
]


def _flag(name, default):
    from ..framework.flags import flag_value
    try:
        return flag_value(name)
    except KeyError:
        return default


# enabled() and device_peaks() sit on per-step hot paths; both are
# pure functions of the flag set, so they cache on flags_generation
# (any set_flags call invalidates) instead of re-reading flags.
_enabled_cache: Tuple[Optional[int], bool] = (None, True)
_peaks_cache: Tuple[Optional[int], Optional[dict]] = (None, None)


def _flags_generation() -> Optional[int]:
    try:
        from ..framework.flags import flags_generation
        return flags_generation()
    except Exception:  # noqa: BLE001
        return None


def enabled() -> bool:
    global _enabled_cache
    gen = _flags_generation()
    if gen is not None and _enabled_cache[0] == gen:
        return _enabled_cache[1]
    val = bool(_flag("FLAGS_xstats_enable", True))
    _enabled_cache = (gen, val)
    return val


# Which MFU/roofline step kind a compile site's executables belong to.
# The step profiler's envelopes use the same kind vocabulary, which is
# what makes the join a dict lookup.
SITE_KINDS: Dict[str, str] = {
    "train_step": "train",
    "generate_prefill": "prefill",
    "generate_chunked": "prefill",
    "generate_decode": "decode",
    "generate_verify": "verify",
    "serving": "serving",
    "jit": "jit",
}

# THE peaks table: per-chip peak dense-matmul FLOP/s and HBM bandwidth,
# keyed by the ``device_kind`` jax reports, each with where the numbers
# come from. bench.py's MFU and the roofline gauges below both read it.
# A device that is not listed has no peaks: ``chip_peaks`` raises, and
# the gauges stay unset (or take FLAGS_device_peak_flops /
# FLAGS_device_peak_bytes_per_s) rather than borrow another chip's.
DEVICE_PEAKS: Dict[str, dict] = {
    "TPU v5 lite": {
        "flops": 197e12, "bytes_per_s": 819e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                  "bf16, 16 GB HBM at 819 GB/s per chip"},
}


def chip_peaks(device_kind: str) -> dict:
    """``{"flops", "bytes_per_s", "source"}`` of one chip of this
    ``device_kind``; an unknown kind is an error, not a default."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r} in "
            f"xstats.DEVICE_PEAKS (known: {sorted(DEVICE_PEAKS)}); add "
            f"the chip with its source before reporting a utilization "
            f"on it") from None


def device_peaks() -> dict:
    """Resolve the (peak FLOP/s, peak bytes/s) pair: explicit flags
    first, then the ``DEVICE_PEAKS`` entry of this device's kind, else
    0 (= unknown; MFU gauges stay unset rather than report garbage).
    Cached per flags-generation — the stepprof join reads this every
    step."""
    global _peaks_cache
    gen = _flags_generation()
    if gen is not None and _peaks_cache[0] == gen and \
            _peaks_cache[1] is not None:
        return _peaks_cache[1]
    out = _device_peaks_uncached()
    _peaks_cache = (gen, out)
    return out


def _device_peaks_uncached() -> dict:
    import jax
    flops = float(_flag("FLAGS_device_peak_flops", 0.0))
    bps = float(_flag("FLAGS_device_peak_bytes_per_s", 0.0))
    source = "flag" if (flops or bps) else "table"
    dev = jax.devices()[0]
    if not (flops and bps):
        entry = DEVICE_PEAKS.get(dev.device_kind, {})
        flops = flops or entry.get("flops", 0.0)
        bps = bps or entry.get("bytes_per_s", 0.0)
    if not (flops or bps):
        source = "unknown"
    return {"flops": flops, "bytes_per_s": bps, "source": source,
            "platform": dev.platform, "device_kind": dev.device_kind}


def signature_of(tree) -> tuple:
    """Canonical ((shape, dtype), ...) signature of a pytree of arrays
    / ShapeDtypeStructs — the registry's per-site entry key."""
    import jax
    return tuple(
        (tuple(int(d) for d in getattr(a, "shape", ())),
         str(getattr(a, "dtype", type(a).__name__)))
        for a in jax.tree_util.tree_leaves(tree))


def _sig_arg_bytes(signature) -> int:
    """Total operand bytes implied by a signature — exact, computable
    without XLA, and the memory floor for thunk-tier entries whose
    memory_analysis was never materialized."""
    total = 0
    for shape, dtype in signature:
        try:
            n = 1
            for d in shape:
                n *= int(d)
            total += n * np.dtype(dtype).itemsize
        except Exception:  # noqa: BLE001 - exotic dtypes (PRNG keys)
            pass           # just don't count
    return int(total)


def _scalar(v) -> float:
    try:
        return float(v)
    except Exception:  # noqa: BLE001
        return 0.0


def _cost_dict(obj) -> dict:
    """Normalize {Lowered,Compiled}.cost_analysis() (dict, or a
    one-per-partition list of dicts) into the keys we publish."""
    ca = obj.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    ca = ca or {}
    return {
        "flops": _scalar(ca.get("flops", 0.0)),
        "bytes_accessed": _scalar(ca.get("bytes accessed", 0.0)),
        "transcendentals": _scalar(ca.get("transcendentals", 0.0)),
    }


def _memory_dict(compiled) -> dict:
    ma = compiled.memory_analysis()
    if ma is None:
        return {}
    return {
        "arg_bytes": int(getattr(ma, "argument_size_in_bytes", 0)),
        "out_bytes": int(getattr(ma, "output_size_in_bytes", 0)),
        "temp_bytes": int(getattr(ma, "temp_size_in_bytes", 0)),
        "alias_bytes": int(getattr(ma, "alias_size_in_bytes", 0)),
        "code_bytes": int(getattr(ma, "generated_code_size_in_bytes",
                                  0)),
    }


class ExecEntry:
    """One registered executable: (site, signature) identity plus the
    cost/memory analysis and provenance the /execz page serves."""

    __slots__ = ("site", "kind", "signature", "fingerprint", "spec_hash",
                 "provenance", "created_unix_ms", "dispatches",
                 "last_dispatch_unix_ms", "analysis", "analysis_error",
                 "sig_arg_bytes", "_compiled", "_lower_thunk")

    def __init__(self, site: str, signature: tuple, *,
                 kind: Optional[str] = None,
                 fingerprint: Optional[str] = None,
                 spec_hash: Optional[str] = None,
                 provenance: Optional[dict] = None,
                 compiled=None,
                 lower_thunk: Optional[Callable] = None):
        self.site = site
        self.kind = kind or SITE_KINDS.get(site, "other")
        self.signature = signature
        self.fingerprint = fingerprint
        self.spec_hash = spec_hash
        self.provenance = dict(provenance or {})
        self.created_unix_ms = time.time_ns() // 1_000_000
        self.dispatches = 0
        self.last_dispatch_unix_ms = None
        self.analysis: Optional[dict] = None
        self.analysis_error: Optional[str] = None
        self.sig_arg_bytes = _sig_arg_bytes(signature)
        self._compiled = compiled
        self._lower_thunk = lower_thunk

    def program_text(self) -> Optional[str]:
        """The program behind this entry — the attached executable's
        optimized HLO, else the lowering's StableHLO — or None once the
        analysis has been read and the handles dropped. What a caller
        greps to see which path an executable took (a Pallas kernel is
        a ``tpu_custom_call`` in both forms)."""
        if self._compiled is not None:
            return self._compiled.as_text()
        if self._lower_thunk is not None:
            return self._lower_thunk().as_text()
        return None

    def roofline(self, peaks: Optional[dict] = None) -> dict:
        """Arithmetic intensity vs the platform ridge point."""
        ana = self.analysis or {}
        flops = ana.get("flops", 0.0)
        ba = ana.get("bytes_accessed", 0.0)
        out = {"intensity": round(flops / ba, 4) if ba else None,
               "classification": "unknown"}
        peaks = peaks if peaks is not None else device_peaks()
        if ba and flops and peaks["flops"] and peaks["bytes_per_s"]:
            ridge = peaks["flops"] / peaks["bytes_per_s"]
            out["ridge"] = round(ridge, 4)
            out["classification"] = ("compute_bound"
                                     if flops / ba >= ridge
                                     else "memory_bound")
        return out

    def payload(self, peaks: Optional[dict] = None) -> dict:
        return {
            "site": self.site,
            "kind": self.kind,
            "signature": [[list(s), d] for s, d in self.signature],
            "fingerprint": self.fingerprint,
            "spec_hash": self.spec_hash,
            "provenance": self.provenance,
            "created_unix_ms": self.created_unix_ms,
            "dispatches": self.dispatches,
            "last_dispatch_unix_ms": self.last_dispatch_unix_ms,
            "sig_arg_bytes": self.sig_arg_bytes,
            "analysis": self.analysis,
            "analysis_error": self.analysis_error,
            "roofline": self.roofline(peaks),
        }


class ExecRegistry:
    """Process-wide bounded registry of compiled executables keyed by
    (site, signature), with per-kind "live executable" tracking for
    the stepprof MFU join."""

    def __init__(self, max_entries: Optional[int] = None, registry=None):
        self._max = max_entries
        self._lock = threading.Lock()
        self._analysis_lock = threading.Lock()
        self._entries: Dict[tuple, ExecEntry] = {}
        self._order: List[tuple] = []        # registration order (LRU)
        self._kind_latest: Dict[str, ExecEntry] = {}
        self._kind_state: Dict[str, dict] = {}
        # cached metric-label children — the per-step paths must not
        # pay a labels() lookup per call
        self._site_dispatch_children: Dict[str, object] = {}
        self._kind_gauge_children: Dict[str, tuple] = {}
        reg = registry or default_registry()
        self._c_registered = reg.counter(
            "paddle_exec_registered_total",
            "executables registered in the xstats registry", ("site",))
        self._c_dispatches = reg.counter(
            "paddle_exec_dispatches_total",
            "dispatches of registered executables", ("site",))
        self._c_evicted = reg.counter(
            "paddle_exec_evicted_total",
            "registry entries evicted by the size bound")
        self._c_analysis_errors = reg.counter(
            "paddle_exec_analysis_errors_total",
            "cost/memory analysis attempts that raised", ("site",))
        self._g_entries = reg.gauge(
            "paddle_exec_entries", "live xstats registry entries")
        self._g_flops = reg.gauge(
            "paddle_exec_flops",
            "cost-model FLOPs of the live executable per step kind",
            ("kind",))
        self._g_bytes = reg.gauge(
            "paddle_exec_bytes_accessed",
            "cost-model bytes accessed of the live executable per "
            "step kind", ("kind",))
        self._g_mfu = reg.gauge(
            "paddle_mfu",
            "model FLOPs utilization per step kind: registry FLOPs / "
            "(step wall time x device peak FLOP/s)", ("kind",))
        self._g_bw = reg.gauge(
            "paddle_exec_bw_util",
            "bandwidth utilization per step kind: registry bytes "
            "accessed / (step wall time x device peak bytes/s)",
            ("kind",))

    # --------------------------------------------------- registration
    def _bound(self) -> int:
        if self._max is not None:
            return int(self._max)
        return int(_flag("FLAGS_xstats_max_entries", 512))

    def register(self, site: str, signature: tuple, *,
                 kind: Optional[str] = None,
                 fingerprint: Optional[str] = None,
                 spec_hash: Optional[str] = None,
                 provenance: Optional[dict] = None,
                 compiled=None,
                 lower_thunk: Optional[Callable] = None) -> ExecEntry:
        """Insert (or refresh) the entry for (site, signature). A
        re-registration of a live key merges provenance and upgrades a
        thunk-tier entry to a Compiled-backed one; it never duplicates."""
        key = (site, signature)
        evicted = 0
        with self._lock:
            ent = self._entries.get(key)
            if ent is not None:
                if provenance:
                    ent.provenance.update(provenance)
                if compiled is not None and ent.analysis is None:
                    ent._compiled = compiled
                if fingerprint and not ent.fingerprint:
                    ent.fingerprint = fingerprint
                if spec_hash and not ent.spec_hash:
                    ent.spec_hash = spec_hash
                self._kind_latest[ent.kind] = ent
                return ent
            ent = ExecEntry(site, signature, kind=kind,
                            fingerprint=fingerprint,
                            spec_hash=spec_hash, provenance=provenance,
                            compiled=compiled, lower_thunk=lower_thunk)
            self._entries[key] = ent
            self._order.append(key)
            self._kind_latest[ent.kind] = ent
            bound = self._bound()
            while len(self._order) > bound:
                old = self._order.pop(0)
                dropped = self._entries.pop(old, None)
                if dropped is not None:
                    evicted += 1
                    for k2, v2 in list(self._kind_latest.items()):
                        if v2 is dropped:
                            self._kind_latest.pop(k2, None)
            self._g_entries.set(len(self._entries))
        self._c_registered.labels(site=site).inc()
        if evicted:
            self._c_evicted.inc(evicted)
        return ent

    def note_dispatch(self, entry: ExecEntry):
        """One executed dispatch of ``entry``: bumps counters and makes
        it the live executable of its kind for the MFU join. Cheap
        enough for per-step call sites."""
        with self._lock:
            entry.dispatches += 1
            entry.last_dispatch_unix_ms = time.time_ns() // 1_000_000
            self._kind_latest[entry.kind] = entry
            child = self._site_dispatch_children.get(entry.site)
            if child is None:
                child = self._site_dispatch_children[entry.site] = \
                    self._c_dispatches.labels(site=entry.site)
        child.inc()

    def lookup(self, site: str, signature: tuple) -> Optional[ExecEntry]:
        with self._lock:
            return self._entries.get((site, signature))

    def entries(self) -> List[ExecEntry]:
        with self._lock:
            return [self._entries[k] for k in self._order
                    if k in self._entries]

    # ------------------------------------------------------- analysis
    def ensure_analysis(self, entry: ExecEntry) -> Optional[dict]:
        """Materialize the entry's cost/memory analysis. Direct (and
        cheap) when a Compiled is attached; a thunk-tier entry pays one
        abstract ``lower()`` here — scrape time, never dispatch time."""
        if entry.analysis is not None or entry.analysis_error is not None:
            return entry.analysis
        with self._analysis_lock:
            if entry.analysis is not None or \
                    entry.analysis_error is not None:
                return entry.analysis
            ana = None
            try:
                compiled = entry._compiled
                if compiled is not None and \
                        hasattr(compiled, "cost_analysis"):
                    ana = _cost_dict(compiled)
                    ana.update(_memory_dict(compiled))
                    ana["source"] = "compiled"
                elif entry._lower_thunk is not None:
                    lowered = entry._lower_thunk()
                    try:
                        ana = _cost_dict(lowered)
                        ana["source"] = "lowered"
                    except Exception:  # noqa: BLE001 - programs with
                        # symbolic dims (shape-polymorphic serving
                        # exports) cannot run HLO cost analysis
                        # pre-compile; pay one scrape-time compile to
                        # read the optimized program's numbers instead
                        compiled = lowered.compile()
                        ana = _cost_dict(compiled)
                        ana.update(_memory_dict(compiled))
                        ana["source"] = "compiled_at_scrape"
                if ana is not None:
                    ana.setdefault("arg_bytes", entry.sig_arg_bytes)
                    entry.analysis = ana
                    # analysis computed: the executable handle has done
                    # its job — drop the refs so the registry never
                    # pins a dead executable or its closed-over arrays
                    entry._compiled = None
                    entry._lower_thunk = None
                else:
                    entry.analysis_error = "no analysis source"
            except Exception as e:  # noqa: BLE001 - a cost-model bug
                # must never break a scrape; record and move on
                entry.analysis_error = f"{type(e).__name__}: {e}"
                self._c_analysis_errors.labels(site=entry.site).inc()
        return entry.analysis

    def ensure_analyses(self):
        for ent in self.entries():
            self.ensure_analysis(ent)

    # ------------------------------------------------- stepprof join
    def on_step_envelope(self, env: dict):
        """Join one step-profiler envelope with the live executable of
        its kind: set the paddle_mfu / bandwidth gauges and fold the
        achieved numbers into the per-kind state /execz serves. Uses
        only analysis that is ALREADY materialized — the hot path
        never lowers or compiles anything."""
        kind = env.get("kind")
        wall_ms = env.get("wall_ms")
        if not kind or not wall_ms:
            return
        with self._lock:
            entry = self._kind_latest.get(kind)
        if entry is None:
            return
        ana = entry.analysis
        if ana is None:
            return
        wall_s = float(wall_ms) / 1e3
        peaks = device_peaks()
        state = {"wall_ms": round(float(wall_ms), 4),
                 "flops": ana.get("flops", 0.0),
                 "bytes_accessed": ana.get("bytes_accessed", 0.0),
                 "achieved_flops_per_s":
                 round(ana.get("flops", 0.0) / wall_s, 2),
                 "roofline": entry.roofline(peaks)["classification"],
                 "site": entry.site}
        children = self._kind_gauge_children.get(kind)
        if children is None:
            children = (self._g_flops.labels(kind=kind),
                        self._g_bytes.labels(kind=kind),
                        self._g_mfu.labels(kind=kind),
                        self._g_bw.labels(kind=kind))
            with self._lock:
                self._kind_gauge_children[kind] = children
        g_flops, g_bytes, g_mfu, g_bw = children
        g_flops.set(ana.get("flops", 0.0))
        g_bytes.set(ana.get("bytes_accessed", 0.0))
        if peaks["flops"]:
            mfu = ana.get("flops", 0.0) / (wall_s * peaks["flops"])
            g_mfu.set(mfu)
            state["mfu"] = round(mfu, 6)
            env["mfu"] = round(mfu, 6)
        if peaks["bytes_per_s"]:
            bw = ana.get("bytes_accessed", 0.0) / (
                wall_s * peaks["bytes_per_s"])
            g_bw.set(bw)
            state["bw_util"] = round(bw, 6)
        with self._lock:
            prev = self._kind_state.get(kind)
            n = (prev or {}).get("steps", 0) + 1
            state["steps"] = n
            if prev is not None and "wall_ms_ewma" in prev:
                state["wall_ms_ewma"] = round(
                    prev["wall_ms_ewma"]
                    + 0.1 * (float(wall_ms) - prev["wall_ms_ewma"]), 4)
            else:
                state["wall_ms_ewma"] = round(float(wall_ms), 4)
            self._kind_state[kind] = state

    # --------------------------------------------------------- views
    def execz_payload(self, compute: bool = True) -> dict:
        """The /execz page. ``compute=True`` (the scrape default)
        materializes pending analyses first — thunk-tier entries pay
        their one abstract lower here."""
        if compute:
            self.ensure_analyses()
        peaks = device_peaks()
        entries = [e.payload(peaks) for e in self.entries()]
        sites: Dict[str, dict] = {}
        for e in entries:
            s = sites.setdefault(e["site"], {"entries": 0,
                                             "dispatches": 0,
                                             "flops": 0.0})
            s["entries"] += 1
            s["dispatches"] += e["dispatches"]
            s["flops"] = max(s["flops"],
                             (e["analysis"] or {}).get("flops", 0.0))
        with self._lock:
            kinds = {k: dict(v) for k, v in self._kind_state.items()}
        return {"peaks": peaks, "entries": entries, "sites": sites,
                "kinds": kinds, "n_entries": len(entries)}

    def clear(self):
        with self._lock:
            self._entries.clear()
            self._order.clear()
            self._kind_latest.clear()
            self._kind_state.clear()
            self._g_entries.set(0)


# ----------------------------------------------------------- profiling
class ProfileRing:
    """Bounded on-disk ring of device-profile captures.

    One capture = a bounded-duration ``jax.profiler`` trace (the
    backend's ``*.trace.json.gz`` chrome events when the platform
    produces them) merged with the span flight recorder's window, as
    one chrome-trace JSON artifact ``load_profiler_result`` can read
    back. Captures are rate-limited (``FLAGS_profile_min_interval_s``)
    and single-flight — a scrape storm or an anomaly burst yields one
    profile, not a pile-up of tracing sessions."""

    def __init__(self, directory: Optional[str] = None,
                 registry=None):
        self._dir_override = directory
        self._lock = threading.Lock()
        self._artifacts: List[dict] = []
        self._last_capture_t: Optional[float] = None
        self._in_flight = False
        self._seq = 0
        self._threads: List[threading.Thread] = []
        reg = registry or default_registry()
        self._c_captures = reg.counter(
            "paddle_profile_captures_total",
            "completed profile captures by trigger reason", ("reason",))
        self._c_rate_limited = reg.counter(
            "paddle_profile_rate_limited_total",
            "capture requests refused by the rate limit or an "
            "in-flight capture")

    # ------------------------------------------------------ plumbing
    def directory(self) -> str:
        d = self._dir_override or str(_flag("FLAGS_profile_dir", "")
                                      or "")
        if not d:
            d = os.path.join(tempfile.gettempdir(),
                             f"paddle_tpu_profilez_{os.getpid()}")
        os.makedirs(d, mode=0o700, exist_ok=True)
        return d

    def _try_begin(self, now: float) -> bool:
        min_interval = float(_flag("FLAGS_profile_min_interval_s",
                                   30.0))
        with self._lock:
            if self._in_flight:
                return False
            if self._last_capture_t is not None and \
                    now - self._last_capture_t < min_interval:
                return False
            self._in_flight = True
            self._last_capture_t = now
            self._seq += 1
            return True

    # ------------------------------------------------------- capture
    def capture(self, duration_ms: float, *, reason: str = "manual",
                trace_id: Optional[str] = None
                ) -> Optional[Tuple[dict, dict]]:
        """Run one bounded capture; returns ``(meta, chrome_doc)`` or
        None when rate-limited / another capture is in flight."""
        if not self._try_begin(time.monotonic()):
            self._c_rate_limited.inc()
            return None
        try:
            return self._run_capture(duration_ms, reason, trace_id)
        finally:
            with self._lock:
                self._in_flight = False

    def _run_capture(self, duration_ms, reason, trace_id):
        duration_ms = max(1.0, min(
            float(duration_ms),
            float(_flag("FLAGS_profile_max_ms", 2000.0))))
        start_unix_ns = time.time_ns()
        events: List[dict] = []
        jax_trace = False
        tdir = tempfile.mkdtemp(prefix="jxtrace-",
                                dir=self.directory())
        try:
            import jax
            jax.profiler.start_trace(tdir)
            jax_trace = True
        except Exception:  # noqa: BLE001 - a concurrent profiler
            pass           # session degrades to span-only capture
        time.sleep(duration_ms / 1e3)
        if jax_trace:
            try:
                import jax
                jax.profiler.stop_trace()
                events.extend(self._read_jax_trace(tdir))
            except Exception:  # noqa: BLE001 - device events are
                pass           # best-effort garnish
        events.extend(self._window_spans(start_unix_ns))
        import shutil
        shutil.rmtree(tdir, ignore_errors=True)
        with self._lock:
            seq = self._seq
        meta = {
            "id": f"capture-{start_unix_ns // 1_000_000}-{seq}",
            "reason": reason,
            "trace_id": trace_id,
            "duration_ms": duration_ms,
            "start_unix_ms": start_unix_ns // 1_000_000,
            "events": len(events),
        }
        doc = {"traceEvents": events, "paddle_profilez": meta}
        path = os.path.join(self.directory(),
                            meta["id"] + ".trace.json")
        blob = json.dumps(doc)
        with open(path, "w", encoding="utf-8") as f:
            f.write(blob)
        meta["path"] = path
        meta["bytes"] = len(blob)
        ring = int(_flag("FLAGS_profile_ring", 8))
        stale: List[dict] = []
        with self._lock:
            self._artifacts.append(dict(meta))
            while len(self._artifacts) > max(ring, 1):
                stale.append(self._artifacts.pop(0))
        for old in stale:
            try:
                os.remove(old["path"])
            except OSError:
                pass
        self._c_captures.labels(reason=reason).inc()
        return meta, doc

    @staticmethod
    def _read_jax_trace(tdir: str) -> List[dict]:
        """Chrome events out of jax.profiler's dump (the
        ``*.trace.json.gz`` files under plugins/profile/<ts>/)."""
        events: List[dict] = []
        for root, _dirs, files in os.walk(tdir):
            for fn in files:
                if not fn.endswith(".trace.json.gz"):
                    continue
                try:
                    with gzip.open(os.path.join(root, fn), "rt",
                                   encoding="utf-8") as f:
                        doc = json.load(f)
                    evs = doc.get("traceEvents", doc) or []
                    events.extend(e for e in evs
                                  if isinstance(e, dict))
                except Exception:  # noqa: BLE001 - a malformed dump
                    pass           # loses its events, nothing else
        return events

    @staticmethod
    def _window_spans(start_unix_ns: int) -> List[dict]:
        """Flight-recorder spans that started inside the capture
        window, as chrome events — so a capture is informative even on
        backends whose profiler yields nothing."""
        try:
            from . import tracing
            payload = tracing.tracez_payload(limit=200)
            spans = [s for t in payload.get("traces", [])
                     for s in t.get("spans", [])
                     if s.get("start_unix_ns", 0) >= start_unix_ns]
            return tracing.chrome_trace_events(spans)
        except Exception:  # noqa: BLE001
            return []

    # ------------------------------------------------------- anomaly
    def trigger_anomaly(self, trace_id: Optional[str],
                        env: Optional[dict] = None
                        ) -> Optional[threading.Thread]:
        """Arm-gated, rate-limited background capture for a stepprof
        straggler. The rate-limit slot is claimed HERE (synchronously)
        so an anomaly burst spawns exactly one capture thread; the
        capture itself runs off the step path."""
        if not bool(_flag("FLAGS_profile_on_anomaly", False)):
            return None
        if not self._try_begin(time.monotonic()):
            self._c_rate_limited.inc()
            return None
        duration = float(_flag("FLAGS_profile_anomaly_ms", 500.0))

        def run():
            try:
                self._run_capture(duration, "anomaly", trace_id)
            except Exception:  # noqa: BLE001 - a capture bug must not
                pass           # leak into the profiler thread
            finally:
                with self._lock:
                    self._in_flight = False

        t = threading.Thread(target=run, name="profilez-anomaly",
                             daemon=True)
        with self._lock:
            self._threads.append(t)
            self._threads = [th for th in self._threads
                             if th.is_alive() or th is t]
        t.start()
        return t

    def wait_captures(self, timeout: float = 10.0):
        """Join outstanding background captures (tests)."""
        deadline = time.monotonic() + timeout
        with self._lock:
            threads = list(self._threads)
        for t in threads:
            t.join(timeout=max(0.0, deadline - time.monotonic()))

    # --------------------------------------------------------- views
    def artifacts(self) -> List[dict]:
        with self._lock:
            return [dict(a) for a in self._artifacts]

    def profilez_payload(self) -> dict:
        return {
            "dir": self.directory(),
            "artifacts": self.artifacts(),
            "armed_on_anomaly": bool(_flag("FLAGS_profile_on_anomaly",
                                           False)),
            "min_interval_s": float(_flag("FLAGS_profile_min_interval_s",
                                          30.0)),
            "max_ms": float(_flag("FLAGS_profile_max_ms", 2000.0)),
            "anomaly_ms": float(_flag("FLAGS_profile_anomaly_ms",
                                      500.0)),
        }

    def clear(self):
        with self._lock:
            arts, self._artifacts = self._artifacts, []
            self._last_capture_t = None
        for a in arts:
            try:
                os.remove(a.get("path", ""))
            except OSError:
                pass


# ----------------------------------------------------- module surface
_default_lock = threading.Lock()
_default_registry: Optional[ExecRegistry] = None
_default_ring: Optional[ProfileRing] = None


def default_exec_registry() -> ExecRegistry:
    global _default_registry
    with _default_lock:
        if _default_registry is None:
            _default_registry = ExecRegistry()
        return _default_registry


def default_profile_ring() -> ProfileRing:
    global _default_ring
    with _default_lock:
        if _default_ring is None:
            _default_ring = ProfileRing()
        return _default_ring


def reset_for_tests():
    """Fresh registry + ring state (tests); artifacts on disk for the
    old ring are removed."""
    global _default_registry, _default_ring
    with _default_lock:
        reg, _default_registry = _default_registry, None
        ring, _default_ring = _default_ring, None
    if reg is not None:
        reg.clear()
    if ring is not None:
        ring.clear()


def register_executable(site: str, signature: tuple, **kw
                        ) -> Optional[ExecEntry]:
    """Compile-site entry point; no-op (None) when xstats is off. Never
    raises — a registry bug must not break a compile site."""
    if not enabled():
        return None
    try:
        return default_exec_registry().register(site, signature, **kw)
    except Exception:  # noqa: BLE001
        return None


def note_dispatch(entry: Optional[ExecEntry]):
    if entry is None or not enabled():
        return
    try:
        default_exec_registry().note_dispatch(entry)
    except Exception:  # noqa: BLE001 - hot path, never raise
        pass


def on_step_envelope(env: dict):
    """stepprof join hook: called once per recorded step envelope."""
    if not enabled():
        return
    try:
        default_exec_registry().on_step_envelope(env)
    except Exception:  # noqa: BLE001 - hot path, never raise
        pass


def on_anomaly(env: dict, trace_id: Optional[str]):
    """stepprof straggler hook: maybe trigger the anomaly capture."""
    if not enabled():
        return
    try:
        default_profile_ring().trigger_anomaly(trace_id, env)
    except Exception:  # noqa: BLE001
        pass


def execz_payload(compute: bool = True) -> dict:
    return default_exec_registry().execz_payload(compute=compute)


def profilez_payload() -> dict:
    return default_profile_ring().profilez_payload()


def capture_profile(duration_ms: float, *, reason: str = "manual",
                    trace_id: Optional[str] = None):
    return default_profile_ring().capture(duration_ms, reason=reason,
                                          trace_id=trace_id)


def wait_captures(timeout: float = 10.0):
    default_profile_ring().wait_captures(timeout)
