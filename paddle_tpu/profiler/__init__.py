"""Profiler (reference: /root/reference/python/paddle/profiler/profiler.py:344
+ platform/profiler/ C++ tracers). TPU-native: host spans are recorded by a
lightweight in-process tracer (chrome-trace export), device activity comes
from jax.profiler (XPlane/xprof) when a trace dir is given — the analog of the
reference's HostTracer + CudaTracer pair.
"""
from __future__ import annotations

import contextlib
import gc
import json
import os
import threading
import time
from enum import Enum
from typing import Callable, Iterable, Optional

import jax


class ProfilerTarget(Enum):
    CPU = 0
    GPU = 1
    TPU = 2
    CUSTOM_DEVICE = 3


class ProfilerState(Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


class SummaryView(Enum):
    DeviceView = 0
    OverView = 1
    ModelView = 2
    DistributedView = 3
    KernelView = 4
    OperatorView = 5
    MemoryView = 6
    MemoryManipulationView = 7
    UDFView = 8


class _HostTracer:
    """In-process span recorder (analog of reference HostTracer,
    /root/reference/paddle/fluid/platform/profiler/host_tracer.h:26)."""

    def __init__(self):
        self.events = []
        self._lock = threading.Lock()
        self.enabled = False
        self._native = None  # lazily resolved C tracer (native/host_tracer.cc)

    def _native_lib(self):
        if self._native is None:
            try:
                from ..native import lib
                self._native = lib() or False
            except Exception:
                self._native = False
        return self._native or None

    def start(self):
        self.enabled = True
        self.events = []
        n = self._native_lib()
        if n is not None:
            n.host_tracer_start()

    def add(self, name, start_ns, end_ns, tid, args=None):
        if not self.enabled:
            return
        n = self._native_lib()
        # the native recorder's ABI is (name, start, end) — spans that
        # carry args metadata are recorded Python-side instead and
        # spliced into the native export (see _merge_python_events)
        if n is not None and n.host_tracer_enabled() and not args:
            n.host_tracer_record(name.encode(), start_ns, end_ns)
            return
        ev = {"name": name, "ph": "X", "ts": start_ns / 1e3,
              "dur": (end_ns - start_ns) / 1e3, "pid": os.getpid(),
              "tid": tid}
        if args:
            ev["args"] = dict(args)   # chrome-trace per-span metadata
        with self._lock:
            self.events.append(ev)

    def export_chrome_tracing(self, path):
        n = self._native_lib()
        if n is not None and n.host_tracer_event_count() > 0:
            n.host_tracer_stop(path.encode())
            if self.events:
                self._merge_python_events(path)
            return
        with open(path, "w") as f:
            json.dump({"traceEvents": self.events}, f)

    def _merge_python_events(self, path):
        """Splice Python-side (args-carrying) spans into a native
        chrome-trace export so one file shows both."""
        try:
            with open(path) as f:
                data = json.load(f)
            with self._lock:
                extra = list(self.events)
            if isinstance(data, list):
                data.extend(extra)
            elif isinstance(data, dict):
                data.setdefault("traceEvents", []).extend(extra)
            else:
                return
            with open(path, "w") as f:
                json.dump(data, f)
        except Exception:
            pass  # the native trace stays usable; args spans are additive


_tracer = _HostTracer()

# Optional span sink: when set (observability.mirror_profiler_spans),
# every RecordEvent duration is ALSO fed to it — the bridge that keeps
# chrome-trace span timing and scraped /metrics histograms in agreement.
_span_sink = None


def set_span_sink(fn):
    """``fn(name, duration_ms)`` called at every RecordEvent end (None
    to detach). The sink runs outside the tracer's enabled gate: spans
    mirror into metrics whether or not a profiler session is recording."""
    global _span_sink
    _span_sink = fn


class RecordEvent:
    """Span marker usable as context manager or begin/end pair — same surface
    as paddle.profiler.RecordEvent. The span goes to two sinks: the
    in-process ``_HostTracer`` (chrome-trace export, the span sink), and a
    ``jax.profiler.TraceAnnotation``, which while a jax profiler session
    runs lands on the calling thread's line of the ``/host:CPU`` plane of
    the same ``.xplane.pb`` as the device's ``XLA Ops``, on one clock;
    while none runs it costs a flag check. ``args`` (a shallow dict, e.g.
    the serving layer's ``{"rows": 8, "padded": 8}``) lands in the
    chrome-trace event's ``args`` field and in the annotation's stats, and
    can be extended during the span via ``set_arg`` — the serving
    pipeline stamps measured stage times onto its spans this way. After
    ``end`` ``elapsed_s`` is the span's length by the same two clock
    readings the host tracer records, for a caller that counts it too."""

    def __init__(self, name, event_type=None, args=None):
        self.name = name
        self.args = dict(args) if args else None
        self._annotation = None
        self._start = None
        self.elapsed_s = 0.0

    def set_arg(self, key, value):
        if self.args is None:
            self.args = {}
        self.args[key] = value
        if self._annotation is not None:
            try:
                self._annotation.set_metadata(**{key: value})
            except Exception:  # noqa: BLE001 - as in begin()
                pass

    def begin(self):
        self._start = time.perf_counter_ns()
        try:
            self._annotation = jax.profiler.TraceAnnotation(
                self.name, **(self.args or {}))
            self._annotation.__enter__()
        except Exception:  # noqa: BLE001 - telemetry must never fail
            self._annotation = None   # the instrumented code path

    def end(self):
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
            self._annotation = None
        if self._start is not None:
            end = time.perf_counter_ns()
            self.elapsed_s = (end - self._start) / 1e9
            _tracer.add(self.name, self._start, end,
                        threading.get_ident(), self.args)
            sink = _span_sink
            if sink is not None:
                try:
                    sink(self.name, (end - self._start) / 1e6)
                except Exception:  # noqa: BLE001 - telemetry must never
                    pass           # fail the instrumented code path

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False


class _FullCollections:
    """``gc.callbacks`` hook: a ``python::gc`` annotation round each
    collection of the oldest generation, on the collecting thread's line
    of the trace; the younger generations return at once. A bare
    ``TraceAnnotation``, not a ``RecordEvent``: a collection runs between
    any two bytecodes, also where this thread holds the host tracer's or
    a metric's lock, and the annotation takes no Python lock.
    Collections never overlap, so one open annotation is all there is."""

    def __init__(self):
        self._annotation = None

    def __call__(self, phase, info):
        if info["generation"] != 2:
            return
        try:
            if phase == "start":
                self._begin()
            else:
                self._end()
        except Exception:  # noqa: BLE001 - telemetry must never fail a
            self._annotation = None    # collection

    def _begin(self):
        self._annotation = jax.profiler.TraceAnnotation("python::gc")
        self._annotation.__enter__()

    def _end(self):
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
            self._annotation = None


_on_gc = _FullCollections()


def trace_full_collections():
    """Register ``_on_gc`` once a process (every ``GenerationServer``
    asks)."""
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)


def make_scheduler(*, closed: int, ready: int, record: int, repeat: int = 0,
                   skip_first: int = 0) -> Callable[[int], ProfilerState]:
    def scheduler(step: int) -> ProfilerState:
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        cycle = closed + ready + record
        if repeat and s >= cycle * repeat:
            return ProfilerState.CLOSED
        pos = s % cycle
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == cycle - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return scheduler


def export_chrome_tracing(dir_name: str, worker_name: Optional[str] = None):
    os.makedirs(dir_name, exist_ok=True)

    def handler(prof):
        name = worker_name or f"worker_{os.getpid()}"
        _tracer.export_chrome_tracing(
            os.path.join(dir_name, f"{name}_{int(time.time())}.json"))

    return handler


def export_protobuf(dir_name: str, worker_name: Optional[str] = None):
    return export_chrome_tracing(dir_name, worker_name)


class Profiler:
    def __init__(self, *, targets: Optional[Iterable] = None, scheduler=None,
                 on_trace_ready=None, record_shapes=False, profile_memory=False,
                 timer_only=False, emit_nvtx=False, custom_device_types=None,
                 with_flops=False):
        self._scheduler = scheduler if callable(scheduler) else (
            make_scheduler(closed=0, ready=0, record=scheduler[1] - scheduler[0],
                           skip_first=scheduler[0])
            if isinstance(scheduler, (tuple, list)) else None)
        self._on_trace_ready = on_trace_ready
        self._step = 0
        self._jax_trace_dir = None
        self.timer_only = timer_only
        self._step_times = []
        self._last_step_t = None

    def start(self):
        _tracer.start()
        self._last_step_t = time.perf_counter()
        if not self.timer_only:
            self._jax_trace_dir = os.environ.get(
                "PADDLE_TPU_TRACE_DIR", "/tmp/paddle_tpu_trace")
            try:
                jax.profiler.start_trace(self._jax_trace_dir)
            except Exception:
                self._jax_trace_dir = None

    def stop(self):
        _tracer.enabled = False
        if self._jax_trace_dir is not None:
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass
            self._jax_trace_dir = None
        if self._on_trace_ready is not None:
            self._on_trace_ready(self)

    def step(self, num_samples=None):
        now = time.perf_counter()
        if self._last_step_t is not None:
            self._step_times.append(now - self._last_step_t)
        self._last_step_t = now
        self._step += 1

    def step_info(self, unit=None):
        if not self._step_times:
            return ""
        import numpy as np
        ts = np.asarray(self._step_times[-10:])
        return (f"avg step time {ts.mean()*1000:.2f} ms "
                f"(min {ts.min()*1000:.2f}, max {ts.max()*1000:.2f})")

    def export(self, path, format=None):  # noqa: A002
        _tracer.export_chrome_tracing(path)

    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms", views=None):
        from collections import defaultdict
        agg = defaultdict(lambda: [0.0, 0])
        for e in _tracer.events:
            agg[e["name"]][0] += e["dur"]
            agg[e["name"]][1] += 1
        lines = ["name\ttotal_us\tcalls"]
        for name, (dur, calls) in sorted(agg.items(), key=lambda kv: -kv[1][0]):
            lines.append(f"{name}\t{dur:.1f}\t{calls}")
        table = "\n".join(lines)
        print(table)
        return table

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False


class SortedKeys(Enum):
    """Summary-table sort keys (reference profiler_statistic.py:49).
    GPU* members name the accelerator columns — device time on this
    stack."""

    CPUTotal = 0
    CPUAvg = 1
    CPUMax = 2
    CPUMin = 3
    GPUTotal = 4
    GPUAvg = 5
    GPUMax = 6
    GPUMin = 7


class _LoadedProfilerResult:
    """Events loaded back from an exported chrome trace."""

    def __init__(self, events):
        self.events = events

    def time_range_summary(self):
        total = sum(e.get("dur", 0.0) for e in self.events)
        return {"total_us": total, "n_events": len(self.events)}


def load_profiler_result(filepath):
    """Read a chrome-trace json written by export_chrome_tracing back
    into a result object (reference profiler.py load_profiler_result
    reads its protobuf dump)."""
    import json

    with open(filepath) as f:
        data = json.load(f)
    if isinstance(data, list):       # bare-array chrome trace form
        events = data
    else:
        events = data.get("traceEvents", [])
    return _LoadedProfilerResult(
        [e for e in events if isinstance(e, dict)])
