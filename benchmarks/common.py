"""What both runners share: where files are, the table of peaks, the
compile counter, the caches' placement and the small arithmetic
(percentiles) every metric reader uses.

Nothing here imports jax at import time: ``run.py`` has to be able to
fail before the backend starts.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import math
import os
import shutil
import statistics

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def chip_peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind`` from ``peaks.json``. A device that
    is not in the table is an error, never a default."""
    table = load_json(os.path.join(HERE, "peaks.json"))["device_kinds"]
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in "
            f"benchmarks/peaks.json (known: {sorted(table)})")
    return table[device_kind]


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (q in 0..100) of ``values``: the smallest
    value with at least q% of the samples at or below it. No
    interpolation, so a tail is always a sample that happened."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no samples")
    rank = max(1, math.ceil(q / 100.0 * len(xs)))
    return float(xs[rank - 1])


def median(values) -> float:
    return float(statistics.median(values))


class CompileCounter:
    """What jax compiled and what its persistent cache served, counted
    from jax.monitoring (copied from chip_smoke.py, PR 21): a window
    in which ``backend_compiles`` moved compiled something."""

    def __init__(self):
        import jax
        self.hits = self.misses = self.backend_compiles = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(
            self._duration)

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, name, secs, **_):
        # jax times compile_or_get_cached: a persistent-cache load is
        # counted too, which is right here — neither may happen inside
        # a measured window
        if name == "/jax/core/compile/backend_compile_duration":
            self.backend_compiles += 1
            self.compile_s += secs

    def snapshot(self) -> dict:
        return {"hits": self.hits, "misses": self.misses,
                "backend_compiles": self.backend_compiles,
                "compile_s": self.compile_s}

    def since(self, snap: dict) -> dict:
        now = self.snapshot()
        return {k: now[k] - snap[k] for k in now}


def place_caches() -> dict:
    """JAX's persistent cache and the program's AOT tier, both on, at
    ``JAX_COMPILATION_CACHE_DIR`` where that is set and else under
    ``<checkout>/.cache``: a fixed path, because the path is part of
    the cache's key. Call before the first use of jax."""
    from paddle_tpu.compile_cache import aot_cache_dir, place_jax_cache
    jax_dir = place_jax_cache()
    import paddle_tpu as paddle
    # unbounded: the flag's default of 1 GiB evicts least-recently-used
    # entries, and one serve cell's 25 executables thrash it (every
    # second run of serve-medium-backlog compiled all of them again, PR
    # 23); what a checkout caches is bounded by its cells
    paddle.set_flags({"FLAGS_compile_cache_dir": aot_cache_dir(),
                      "FLAGS_compile_cache_max_bytes": 0})
    return {"jax": jax_dir, "aot": aot_cache_dir()}


def scratch_dir(name: str) -> str:
    """``<checkout>/.cache/bench/<name>``: traces and summaries of a
    run, inside the checkout and ignored by git."""
    path = os.path.join(ROOT, ".cache", "bench", name)
    os.makedirs(path, exist_ok=True)
    return path


def start_trace(cell_name: str) -> str:
    """Start the profiler into ``<scratch>/<cell>/trace`` (emptied
    first), host annotations on and the Python tracer off: it slows the
    host and nothing here reads it. Returns the directory."""
    import jax
    trace_dir = os.path.join(scratch_dir(cell_name), "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    return trace_dir


def device_record(memory_peak_bytes=None) -> dict:
    """The device as jax reports it, with the peak bytes of the
    fullest chip where the backend counts them."""
    import jax
    devs = jax.local_devices()
    rec = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(jax.devices())}
    if memory_peak_bytes is None:
        stats = [d.memory_stats() for d in devs]
        if all(s is not None for s in stats):
            memory_peak_bytes = max(
                int(s.get("peak_bytes_in_use", s.get("bytes_in_use", 0)))
                for s in stats)
    rec["memory_peak_bytes"] = int(memory_peak_bytes or 0)
    return rec


def load_module(path: str, name: str):
    """The Python file at ``path`` as a module of its own called
    ``name``: how a metric's reader and a configuration's reference are
    found by file and not by import path."""
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def resolve(spec: str, key: str):
    """``"<module>:<name>"`` -> the object, for the configuration key
    ``key``; what cannot be found is an error that names both."""
    module, _, attr = str(spec).partition(":")
    if not module or not attr:
        raise ValueError(f"{key} = {spec!r} is not \"<module>:<name>\"")
    try:
        found = importlib.import_module(module)
    except ImportError as e:
        raise ImportError(f"{key} = {spec!r}: no module {module!r} "
                          f"({e})") from e
    if not hasattr(found, attr):
        raise ImportError(
            f"{key} = {spec!r}: module {module!r} has no {attr!r}")
    return getattr(found, attr)


# what a configuration file has to state (a ``train`` or ``serve`` key
# where it has that section): none of these has a default in the code
REQUIRED_KEYS = ("reference", "model.class", "model.preset",
                 "train.criterion", "serve.weights_dtype")


def check_config_keys(config: dict, path: str):
    """A configuration (read from ``path``) that lacks one of
    ``REQUIRED_KEYS`` is an error before any work, and the message
    names each."""
    missing = []
    for dotted in REQUIRED_KEYS:
        section, _, key = dotted.rpartition(".")
        if section in ("train", "serve") and section not in config:
            continue
        if key not in (config.get(section, {}) if section else config):
            missing.append(dotted)
    if missing:
        raise ValueError(f"{path} does not state {', '.join(missing)} "
                         f"(benchmarks/README.md, \"A configuration\")")


def build_model(config: dict, section: str):
    """``(cfg, model)``: ``model.class`` built over what
    ``model.preset`` returns for the file's ``model.kwargs`` with the
    section's ``model_kwargs`` (``stacked``, ``recompute``) on top.
    The caller seeds the program first; a name that cannot be found, or
    a size that differs from the file's, is an error."""
    spec = config["model"]
    cls = resolve(spec["class"], "model.class")
    preset = resolve(spec["preset"], "model.preset")
    cfg = preset(**{**spec.get("kwargs", {}),
                    **config[section].get("model_kwargs", {})})
    model = cls(cfg)
    check_model_config(cfg, model, config)
    return cfg, model


def check_model_config(cfg, model, config_file: dict):
    """The sizes the file states are the sizes that run. Each key of
    ``sizes`` is compared with what the program reports under that
    name: an attribute of its config object, else an entry of the
    model's ``kv_cache_spec()``. A key neither has is an error: a size
    nobody checks is not stated."""
    spec = model.kv_cache_spec() if hasattr(model, "kv_cache_spec") else {}
    for key, want in config_file["sizes"].items():
        if hasattr(cfg, key):
            got = getattr(cfg, key)
        elif key in spec:
            got = spec[key]
        else:
            raise KeyError(
                f"configuration file states sizes.{key}={want}, and the "
                f"program reports no {key!r}: neither "
                f"{type(cfg).__name__} nor {type(model).__name__}"
                f".kv_cache_spec() has it")
        if got != want:
            raise ValueError(
                f"configuration file says {key}={want}, the program "
                f"reports {got}")
