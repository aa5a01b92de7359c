"""Native (C++) runtime components, loaded via ctypes.

The reference implements its runtime layer natively (SURVEY §2.2: memory,
platform, distributed bootstrap, profiler, data feed are C++). Here the
XLA-facing compute path is jax; the host runtime pieces that survive XLA are
C++ in csrc/ and built on first use with the in-tree toolchain (g++ —
pybind11 is unavailable, so the ABI is plain C + ctypes):

- tcp_store.cc   — rendezvous KV store (reference tcp_store.cc)
- host_tracer.cc — profiler span recorder + chrome-trace export
- shm_ring.cc    — shared-memory DataLoader batch transport

`lib()` returns the loaded CDLL or None (callers degrade to their
pure-Python fallbacks so the framework works without a compiler) — never
in silence: why it is None is logged once and kept in `status()`.
"""
from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading

logger = logging.getLogger(__name__)

_lock = threading.Lock()
_lib = None
_tried = False
# how the last lib() call ended: "built from csrc/", "loaded (built
# earlier from the same csrc/)", or why there is no library
_status = "not loaded yet"

_SRC = ("tcp_store.cc", "host_tracer.cc", "shm_ring.cc")


def _src_digest(srcs) -> str:
    h = hashlib.sha256()
    for s in srcs:
        with open(s, "rb") as f:
            h.update(f.read())
    return h.hexdigest()


def _build(src_dir: str, out_path: str) -> str:
    """Compile csrc/ into ``out_path``; returns "" or why it failed."""
    srcs = [os.path.join(src_dir, s) for s in _SRC]
    cmd = ["g++", "-O2", "-fPIC", "-shared", "-std=c++17", "-pthread",
           "-o", out_path] + srcs + ["-lrt"]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=120)
    except FileNotFoundError:
        return "g++ is not installed"
    except subprocess.TimeoutExpired:
        return "g++ did not finish in 120 s"
    if proc.returncode != 0:
        return f"g++ failed: {proc.stderr.strip()[-300:]}"
    return ""


def status() -> str:
    """One line on the native library after ``lib()``: built from the
    committed csrc/, loaded from an earlier build, or why it is absent
    and the pure-Python fallbacks (TCPStore, host tracer, DataLoader
    transport) are in use."""
    return _status


def lib():
    """Load (building if needed) the native library; None if unavailable."""
    global _lib, _tried, _status
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        _lib, _status = _load()
        if _lib is None:
            logger.warning("native library unavailable (%s): the "
                           "pure-Python fallbacks are in use", _status)
        return _lib


def _load():
    """``(cdll, status)``; cdll is None where status says why."""
    here = os.path.dirname(os.path.abspath(__file__))
    src_dir = os.path.join(here, "csrc")
    out = os.path.join(here, "libpaddle_tpu_native.so")
    srcs = [os.path.join(src_dir, s) for s in _SRC]
    # staleness is keyed on a content hash of the sources (mtimes are
    # not preserved by git checkout); the .so is never committed.
    stamp = out + ".sha256"
    try:
        digest = _src_digest(srcs)
    except OSError as e:
        return None, f"csrc/ sources missing: {e}"
    stale = not os.path.exists(out)
    if not stale:
        try:
            with open(stamp) as f:
                stale = f.read().strip() != digest
        except OSError:
            stale = True
    if stale:
        why = _build(src_dir, out)
        if why:
            return None, why
        with open(stamp, "w") as f:
            f.write(digest)
    try:
        cdll = ctypes.CDLL(out)
    except OSError as e:
        return None, f"dlopen failed: {e}"
    _configure(cdll)
    return cdll, ("built from csrc/" if stale else
                  "loaded (built earlier from the same csrc/)")


def build_capi() -> str | None:
    """Build libpaddle_inference_c.so — the C serving ABI (reference
    capi_exp PD_* surface) over the Python Predictor via an embedded
    CPython interpreter (csrc/pd_capi.cc). Returns the .so path or None.

    Separate from the main native lib because it links libpython; host
    apps dlopen it, include csrc/pd_inference_c.h, and must export
    PYTHONPATH so `import paddle_tpu` resolves inside the embedded
    interpreter."""
    import sysconfig

    here = os.path.dirname(os.path.abspath(__file__))
    src = os.path.join(here, "csrc", "pd_capi.cc")
    header = os.path.join(here, "csrc", "pd_inference_c.h")
    out = os.path.join(here, "libpaddle_inference_c.so")
    stamp = out + ".sha256"
    try:
        digest = _src_digest([src, header])
    except OSError:
        return None
    if os.path.exists(out):
        try:
            with open(stamp) as f:
                if f.read().strip() == digest:
                    return out
        except OSError:
            pass
    inc = sysconfig.get_path("include")
    libdir = sysconfig.get_config_var("LIBDIR")
    pyver = sysconfig.get_config_var("LDVERSION") or \
        sysconfig.get_config_var("VERSION")
    cmd = ["g++", "-O2", "-fPIC", "-shared", "-std=c++17", "-pthread",
           f"-I{inc}", "-o", out, src, f"-L{libdir}",
           f"-Wl,-rpath,{libdir}", f"-lpython{pyver}"]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=180)
        if proc.returncode != 0:
            return None
    except Exception:
        return None
    with open(stamp, "w") as f:
        f.write(digest)
    return out


def _configure(l):
    c = ctypes
    l.tcp_store_server_start.restype = c.c_void_p
    l.tcp_store_server_start.argtypes = [c.c_int]
    l.tcp_store_server_port.restype = c.c_int
    l.tcp_store_server_port.argtypes = [c.c_void_p]
    l.tcp_store_server_stop.argtypes = [c.c_void_p]
    l.tcp_store_client_connect.restype = c.c_void_p
    l.tcp_store_client_connect.argtypes = [c.c_char_p, c.c_int, c.c_int]
    l.tcp_store_client_close.argtypes = [c.c_void_p]
    l.tcp_store_set.restype = c.c_int
    l.tcp_store_set.argtypes = [c.c_void_p, c.c_char_p, c.c_char_p, c.c_int]
    l.tcp_store_delete.restype = c.c_int
    l.tcp_store_delete.argtypes = [c.c_void_p, c.c_char_p]
    l.tcp_store_get.restype = c.c_int
    l.tcp_store_get.argtypes = [c.c_void_p, c.c_char_p, c.c_char_p, c.c_int]
    l.tcp_store_add.restype = c.c_longlong
    l.tcp_store_add.argtypes = [c.c_void_p, c.c_char_p, c.c_longlong]
    l.tcp_store_wait.restype = c.c_int
    l.tcp_store_wait.argtypes = [c.c_void_p, c.c_char_p, c.c_int, c.c_char_p,
                                 c.c_int]
    l.host_tracer_start.argtypes = []
    l.host_tracer_stop.restype = c.c_int
    l.host_tracer_stop.argtypes = [c.c_char_p]
    l.host_tracer_record.argtypes = [c.c_char_p, c.c_uint64, c.c_uint64]
    l.host_tracer_now.restype = c.c_uint64
    l.host_tracer_enabled.restype = c.c_int
    l.host_tracer_event_count.restype = c.c_int
    l.shm_ring_open.restype = c.c_void_p
    l.shm_ring_open.argtypes = [c.c_char_p, c.c_int, c.c_uint64, c.c_uint64]
    l.shm_ring_push.restype = c.c_int
    l.shm_ring_push.argtypes = [c.c_void_p, c.c_char_p, c.c_uint64]
    l.shm_ring_pop.restype = c.c_longlong
    l.shm_ring_pop.argtypes = [c.c_void_p, c.c_char_p, c.c_uint64, c.c_int]
    l.shm_ring_size.restype = c.c_uint64
    l.shm_ring_size.argtypes = [c.c_void_p]
    l.shm_ring_close.argtypes = [c.c_void_p]
    l.shm_ring_free.argtypes = [c.c_void_p]
