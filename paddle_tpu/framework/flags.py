"""Typed global flag registry.

The reference centralizes ~90 gflags in /root/reference/paddle/phi/core/flags.cc
and exposes them through ``paddle.set_flags/get_flags`` with ``FLAGS_*`` env-var
overrides (/root/reference/python/paddle/fluid/framework.py:7764). This is the
TPU-native equivalent: a single typed registry, env-var override at definition
time, same Python surface.
"""
from __future__ import annotations

import os
from typing import Any, Dict, List, Union


class _Flag:
    __slots__ = ("name", "value", "default", "type_", "help")

    def __init__(self, name, default, help_=""):
        self.name = name
        self.default = default
        self.type_ = type(default)
        self.help = help_
        env = os.environ.get(name)
        self.value = self._parse(env) if env is not None else default

    def _parse(self, s: str):
        if self.type_ is bool:
            return s.lower() in ("1", "true", "yes", "on")
        try:
            return self.type_(s)
        except (TypeError, ValueError) as e:
            # the bare int("two") ValueError names neither the flag nor
            # where the bad value came from — the env var IS the flag
            # name, so say all three
            raise ValueError(
                f"flag {self.name}: cannot parse {s!r} from environment "
                f"variable {self.name} as {self.type_.__name__} "
                f"(default: {self.default!r})") from e

    def set(self, v):
        if self.type_ is bool and isinstance(v, str):
            v = self._parse(v)
        try:
            self.value = self.type_(v)
        except (TypeError, ValueError) as e:
            raise ValueError(
                f"flag {self.name}: cannot coerce {v!r} to "
                f"{self.type_.__name__} (default: {self.default!r})"
            ) from e


_REGISTRY: Dict[str, _Flag] = {}

# Monotonic epoch bumped by every set_flags call. In-process memos
# derived from flag values (e.g. the per-signature AOT-executable memos
# at the compile-cache sites) key on this, so a flag flip or a
# repointed FLAGS_compile_cache_dir can never keep serving a stale
# memoized executable.
_GENERATION = 0


def flags_generation() -> int:
    return _GENERATION


def define_flag(name: str, default, help_: str = ""):
    if not name.startswith("FLAGS_"):
        name = "FLAGS_" + name
    if name not in _REGISTRY:
        _REGISTRY[name] = _Flag(name, default, help_)
    return _REGISTRY[name]


def get_flags(flags: Union[str, List[str]]) -> Dict[str, Any]:
    if isinstance(flags, str):
        flags = [flags]
    out = {}
    for f in flags:
        key = f if f.startswith("FLAGS_") else "FLAGS_" + f
        if key not in _REGISTRY:
            raise ValueError(f"Unknown flag: {f}")
        out[f] = _REGISTRY[key].value
    return out


def set_flags(flags: Dict[str, Any]):
    global _GENERATION
    for k, v in flags.items():
        key = k if k.startswith("FLAGS_") else "FLAGS_" + k
        if key not in _REGISTRY:
            raise ValueError(f"Unknown flag: {k}")
        _REGISTRY[key].set(v)
    _GENERATION += 1


def flag_value(name: str):
    key = name if name.startswith("FLAGS_") else "FLAGS_" + name
    return _REGISTRY[key].value


def flag_ref(name: str) -> _Flag:
    """The live registry object for a flag. Hot paths bind this once
    and read ``.value`` directly — same liveness as ``flag_value``
    (``set_flags`` mutates the object in place) without paying a
    registry lookup per call."""
    key = name if name.startswith("FLAGS_") else "FLAGS_" + name
    return _REGISTRY[key]


def flags_snapshot() -> Dict[str, Dict[str, Any]]:
    """Every registered flag with its live value, default, type name
    and help text — the bulk export pdlint's ``--dump-flags`` and
    debugging sessions use instead of reaching into ``_REGISTRY``."""
    return {name: {"value": f.value, "default": f.default,
                   "type": f.type_.__name__, "help": f.help}
            for name, f in sorted(_REGISTRY.items())}


# Core flags (the subset of the reference's flags.cc that has TPU meaning;
# others are accepted as inert toggles so reference scripts don't break).
define_flag("FLAGS_use_autotune", True, "kernel block-size autotuning (phi/kernels/autotune analog)")
define_flag("FLAGS_check_nan_inf", False, "check outputs for nan/inf after every op")
define_flag("FLAGS_benchmark", False, "synchronize after every op (for timing)")
define_flag("FLAGS_eager_op_jit_cache", True, "cache per-op compiled executables in eager mode")
define_flag("FLAGS_fraction_of_gpu_memory_to_use", 0.92, "accepted for compat; XLA manages HBM")
define_flag("FLAGS_allocator_strategy", "auto_growth", "compat; XLA BFC allocator is used")
define_flag("FLAGS_cudnn_deterministic", False, "compat; maps to XLA deterministic ops")
define_flag("FLAGS_use_stream_safe_cuda_allocator", True, "compat no-op")
define_flag("FLAGS_new_executor_serial_run", False, "run static programs op-serially (debug)")
define_flag("FLAGS_enable_pir_api", False, "compat no-op")
define_flag("FLAGS_log_memory_stats", False, "log live/peak buffer stats on allocation")
define_flag("FLAGS_tpu_matmul_precision", "default", "jax matmul precision: default|high|highest")
define_flag("FLAGS_selected_tpus", 0,
            "local TPU ordinal for this worker (the selected-gpus "
            "analog); the launcher exports it per rank, "
            "distributed.env reads it back as dev_id")
define_flag("FLAGS_flash_min_seqlen", 2048,
            "below this query length attention uses the XLA softmax path "
            "(faster end-to-end, PERF.md); the Pallas flash kernel kicks "
            "in at/above it where O(S^2) memory stops fitting")
define_flag("FLAGS_flash_block_q", 0,
            "flash-attention q block size override (0 = autotune/default); "
            "applies when the call is traced and no autotune cache entry "
            "exists for the shape")
define_flag("FLAGS_flash_block_k", 0,
            "flash-attention k block size override (0 = autotune/default)")

# Serving knobs (paddle_tpu.serving — the dynamic-batching layer).
define_flag("FLAGS_serving_max_batch_size", 8,
            "rows coalesced into one device batch before dispatch")
define_flag("FLAGS_serving_max_wait_ms", 2.0,
            "coalescing window: a batch dispatches when full or this "
            "many ms after its oldest request, whichever first")
define_flag("FLAGS_serving_queue_capacity", 64,
            "bounded request queue; submit raises QueueFullError beyond "
            "this (backpressure)")
define_flag("FLAGS_serving_default_timeout_ms", 0.0,
            "per-request deadline applied when submit() passes none "
            "(0 = no deadline); expired requests are dropped unrun")
define_flag("FLAGS_serving_pad_batch_pow2", True,
            "pad coalesced batches up to power-of-two row buckets so "
            "the XLA compile cache stays bounded under variable load")
define_flag("FLAGS_serving_capi_batching", False,
            "route PD_* C-ABI predictors through a shared "
            "InferenceServer so C hosts get request coalescing")
define_flag("FLAGS_serving_latency_window", 2048,
            "latency samples kept for the serving p50/p95/p99 metrics")
define_flag("FLAGS_serving_pipeline_depth", 2,
            "batches allowed in flight between dispatch and completion: "
            "the worker assembles batch N+1 while batch N computes on "
            "device (0 = synchronous execute, the pre-pipeline path)")
define_flag("FLAGS_serving_telemetry_port", -1,
            "HTTP telemetry endpoint (/metrics /healthz /statusz) the "
            "InferenceServer attaches on construction: -1 disabled, "
            "0 ephemeral port, >0 fixed port; one shared endpoint per "
            "process")
define_flag("FLAGS_serving_donate_inputs", True,
            "donate device input buffers to the jitted serving dispatch "
            "so XLA reuses them for outputs (effective on accelerator "
            "backends; CPU has no donation and falls back silently)")

# Decode serving knobs (paddle_tpu.serving.generation — the
# continuous-batching autoregressive decode engine).
define_flag("FLAGS_decode_max_batch", 8,
            "in-flight decode batch width: the decode step compiles "
            "ONCE at [max_batch, 1] and dead lanes are slot-masked, so "
            "this bounds both concurrency and the compiled shape")
define_flag("FLAGS_decode_page_size", 16,
            "tokens per KV-cache page; sequences hold pages of the "
            "preallocated per-layer pool via int32 block tables "
            "(PagedAttention layout), so cache memory scales with live "
            "tokens rather than max_seq_len x batch")
define_flag("FLAGS_decode_kv_pages", 0,
            "total pages per layer pool incl. the reserved trash page "
            "(0 = auto: enough for max_batch sequences at the model's "
            "max_seq_len)")
define_flag("FLAGS_decode_queue_capacity", 64,
            "bounded generation request queue; submit_generate raises "
            "QueueFullError beyond this (backpressure, matching submit)")
define_flag("FLAGS_decode_default_timeout_ms", 0.0,
            "scheduling deadline applied when submit_generate passes "
            "none (0 = no deadline); like serving submit, an expired "
            "request is dropped before prefill, never mid-stream")
define_flag("FLAGS_decode_prefix_cache", True,
            "shared-prefix KV reuse: keep finished sequences' FULL "
            "pages in a radix index keyed by token content, so a "
            "request whose prompt matches a cached prefix maps those "
            "pages into its block table (refcounted, copy-on-write at "
            "the divergence page) and prefills only its unique suffix; "
            "unreferenced cached pages are LRU-evicted under pool "
            "pressure")
define_flag("FLAGS_decode_spec_k", 0,
            "speculative decoding: tokens proposed per step by the "
            "draft model (GenerationServer(draft_model=...)); the "
            "target model verifies all k in one fixed-shape "
            "[max_batch, k+1] step with accept-and-resample, so "
            "output distribution matches non-speculative sampling "
            "(0 = off; ignored without a draft model)")
define_flag("FLAGS_decode_kv_dtype", "",
            "KV pool storage dtype for serving: '' = model dtype, "
            "'float32', 'bfloat16', or 'int8' (symmetric absmax "
            "quantization with per-slot-per-head f32 scales stored "
            "alongside the pools; quantize-on-write, dequantize-on-read "
            "in both the Pallas tiles and the pure-JAX gather). int8 "
            "shrinks pool bytes ~3.5-4x, and auto pool sizing "
            "(FLAGS_decode_kv_pages=0) grants sub-f32 dtypes 2x pages "
            "= ~2x resident sequences per chip. Read once at server "
            "construction and pinned for the server's lifetime")
define_flag("FLAGS_decode_warmup_from_manifest", False,
            "pre-compile a constructed GenerationServer's decode step "
            "and recorded prefill buckets from its persisted warmup "
            "manifest under FLAGS_compile_cache_dir")
define_flag("FLAGS_serving_mesh_mp", 1,
            "tensor-parallel degree of ONE serving replica: the "
            "replica spans a {'mp': N} device mesh, weights shard by "
            "the shard.py rule tables, paged KV pools shard along the "
            "heads ([pages, page_size, heads/mp * head_dim]), and "
            "the prefill/chunked/verify/decode entry points run GSPMD-"
            "partitioned across all N chips (serving/mesh.py). <=1 = "
            "single-shard (today's exact behavior: same fingerprints, "
            "no recompiles). num_heads must divide evenly or "
            "construction fails fast. Read once at server/backend "
            "construction and pinned for its lifetime")

# Persistent compile cache (paddle_tpu.compile_cache — cold-start
# amortization across processes).
define_flag("FLAGS_compile_cache_dir", "",
            "directory for the persistent AOT compile cache (serialized "
            "executables keyed by function/shape/mesh/flag/version "
            "fingerprints); empty = disabled. A warm cache lets a "
            "restarted process skip trace+XLA-compile at every wired "
            "compile site (jit, TrainStep, serving warmup/dispatch). "
            "TRUSTED PATH ONLY: entries are unpickled on load, so a "
            "writer to this directory can execute code in every reader "
            "— it is created 0o700 and must never be shared or "
            "group-writable")
define_flag("FLAGS_compile_cache_max_bytes", 1 << 30,
            "size bound for FLAGS_compile_cache_dir: least-recently-"
            "used entries are evicted past this many bytes (0 = "
            "unbounded)")
define_flag("FLAGS_serving_warmup_from_manifest", False,
            "pre-warm a constructed InferenceServer from its persisted "
            "warmup manifest (the batch signatures a previous process "
            "actually compiled) when one exists under "
            "FLAGS_compile_cache_dir — the restart-storm fast path")

# Observability knobs (paddle_tpu.observability — the telemetry layer).
define_flag("FLAGS_training_telemetry", False,
            "auto-inject the TrainingTelemetryCallback into Model.fit "
            "(step time, examples/sec, loss into the metric registry)")
define_flag("FLAGS_profiler_span_metrics", False,
            "mirror profiler RecordEvent span durations into the "
            "paddle_profiler_span_ms histogram so chrome traces and "
            "scraped /metrics agree")

# Goodput ledger + continuous step profiler + SLO monitor
# (paddle_tpu.observability.{goodput,stepprof,slo}).
define_flag("FLAGS_goodput_tolerance", 0.02,
            "goodput_report() accounting tolerance: the report's "
            "'closes' bit requires categories (incl. derived idle) to "
            "sum to elapsed wall-clock within this fraction")
define_flag("FLAGS_stepprof_window", 512,
            "bound of the continuous step profiler's per-step "
            "envelope ring (oldest envelopes are evicted past this)")
define_flag("FLAGS_stepprof_anomaly_k", 6.0,
            "straggler threshold: a step slower than "
            "ewma + k * 1.4826 * MAD of its kind is flagged and "
            "promoted into the trace flight recorder as an error span")
define_flag("FLAGS_stepprof_min_samples", 32,
            "step samples per kind before the straggler detector "
            "arms (the EWMA/MAD baseline warm-up)")
define_flag("FLAGS_slo_eval_interval_s", 10.0,
            "cadence of the background SLO evaluator thread "
            "(SLOMonitor.start(); explicit evaluate() calls are "
            "always allowed)")

# Executable cost & roofline observability + on-demand device
# profiling (paddle_tpu.observability.xstats — the /execz registry
# and the /profilez capture ring).
define_flag("FLAGS_xstats_enable", True,
            "populate the process-wide executable registry at every "
            "compile site (XLA cost/memory analysis per signature, "
            "the /execz page, and the paddle_mfu{kind=} join with the "
            "continuous step profiler); off = every hook is a no-op")
define_flag("FLAGS_xstats_max_entries", 512,
            "bound of the executable registry: least-recently-"
            "registered entries are evicted past this many (counted "
            "in paddle_exec_evicted_total)")
define_flag("FLAGS_device_peak_flops", 0.0,
            "per-chip peak FLOP/s for MFU and roofline computation; "
            "0 = use the built-in per-platform table (TPU v5e bf16 "
            "default). CPU CI sets this explicitly — no table entry "
            "pretends to know a host CPU's peak")
define_flag("FLAGS_device_peak_bytes_per_s", 0.0,
            "per-chip peak memory bandwidth (bytes/s) for the "
            "bandwidth-utilization gauge and the roofline ridge "
            "point; 0 = per-platform table, same contract as "
            "FLAGS_device_peak_flops")
define_flag("FLAGS_profile_dir", "",
            "directory of the bounded on-disk profile-capture ring "
            "(/profilez artifacts); empty = a per-process directory "
            "under the system temp dir")
define_flag("FLAGS_profile_ring", 8,
            "max retained capture artifacts in the /profilez ring "
            "(oldest artifact files are deleted past this)")
define_flag("FLAGS_profile_max_ms", 2000.0,
            "hard bound on one /profilez capture duration — a "
            "larger duration_ms query parameter is clamped here so a "
            "scrape can never stall a replica for long")
define_flag("FLAGS_profile_min_interval_s", 30.0,
            "rate limit between profile captures (manual or "
            "anomaly-triggered); captures inside the interval are "
            "refused and counted in paddle_profile_rate_limited_total")
define_flag("FLAGS_profile_on_anomaly", False,
            "arm anomaly-triggered capture: a stepprof straggler "
            "kicks off one rate-limited background device-profile "
            "capture whose artifact records the straggler span's "
            "trace id (see FLAGS_profile_anomaly_ms)")
define_flag("FLAGS_profile_anomaly_ms", 500.0,
            "duration of an anomaly-triggered capture (bounded by "
            "FLAGS_profile_max_ms like every capture)")

# Distributed request tracing (paddle_tpu.observability.tracing —
# router->worker->engine spans + the /tracez flight recorder).
define_flag("FLAGS_trace_sample_rate", 0.0,
            "head-sampling rate for distributed request traces "
            "(0 = tracing off, 1 = every request). The decision is "
            "made once at ingress, deterministically from the trace "
            "id, and propagated in the traceparent header; errored/"
            "shed/deadline requests are tail-promoted into the "
            "recorder regardless of the coin flip")
define_flag("FLAGS_trace_buffer_spans", 4096,
            "bound of the in-process span flight recorder (/tracez): "
            "oldest spans are evicted past this many")
define_flag("FLAGS_trace_max_spans_per_trace", 256,
            "per-trace span cap in the flight recorder AND on the "
            "unsampled pending list, so one long decode stream "
            "cannot evict every other trace (excess spans are "
            "counted as dropped)")

# Numerics & silent-data-corruption observability
# (paddle_tpu.observability.numerics — NaN/Inf tripwires, sampled
# shadow-verification against the pure-JAX oracle, device canary
# sweeps, and the /numericsz surface). FLAGS_check_nan_inf (defined
# with the core flags above) arms the tripwires at 100% duty; these
# knobs give the fleet a cheaper sampled regime.
define_flag("FLAGS_numerics_sample_rate", 0.0,
            "fraction of train/decode steps whose output health stats "
            "(finite fraction, max-abs, argmax-entropy, grad norm) are "
            "published; FLAGS_check_nan_inf=true overrides this to "
            "every step. The device reductions are fixed-shape and "
            "their host read is deferred one step, so sampling costs "
            "no extra device sync")
define_flag("FLAGS_numerics_shadow_rate", 0.0,
            "duty cycle of decode/chunked/verify shadow-verification: "
            "a sampled dispatch is re-executed through the pure-JAX "
            "oracle (use_pallas=False, non-donating) and max-abs "
            "logit divergence is published as "
            "paddle_numerics_shadow_divergence{kind,dtype}")
define_flag("FLAGS_numerics_canary_period_s", 0.0,
            "period of the per-worker deterministic checksum canary "
            "sweep (SDC detection); 0 disables. The sweep also runs "
            "on not-ready -> ready transitions; a failing canary "
            "quarantines the replica (readiness flip + breaker open)")

# Serving-fleet knobs (paddle_tpu.serving.fleet — router + N replica
# worker processes with rolling hot weight swap).
define_flag("FLAGS_serving_ready_requires_warmup", False,
            "gate readiness (/readyz, InferenceServer.ready, "
            "GenerationServer.ready) on warmup: the server reports "
            "not-ready until warmup()/warmup_from_manifest() completes. "
            "Fleet workers enable this so the router never routes "
            "traffic to a replica that would compile on the request "
            "path; liveness (/healthz) is unaffected")
define_flag("FLAGS_fleet_replicas", 2,
            "default replica count a ReplicaSupervisor spawns when the "
            "caller does not pass one")
define_flag("FLAGS_fleet_retries", 2,
            "router retry budget per batch: a dispatch shed with "
            "QueueFullError (HTTP 429) or refused by a not-ready "
            "replica is retried on another replica this many times "
            "before the batch fails with QueueFullError")
define_flag("FLAGS_fleet_health_interval_ms", 200.0,
            "router readiness-poll cadence: every interval each known "
            "replica's /readyz is probed and the routable set updated")
define_flag("FLAGS_fleet_restart_backoff_ms", 200.0,
            "supervisor respawn backoff after a replica process exits "
            "unexpectedly (doubles per consecutive crash of the same "
            "replica, capped at 30x)")
define_flag("FLAGS_fleet_request_timeout_s", 120.0,
            "router-side HTTP timeout for one forwarded batch or "
            "generation stream read; a replica that blows it fails "
            "only the in-flight requests riding that connection")
define_flag("FLAGS_fleet_drain_timeout_s", 30.0,
            "rolling-swap drain bound: max seconds swap_weights waits "
            "for one draining replica's outstanding requests to reach "
            "zero before the swap aborts (remaining replicas keep the "
            "old weights — never a half-broken fleet)")

# Fleet resilience knobs (paddle_tpu.serving.fleet.resilience —
# deadline propagation, per-replica circuit breakers, hedged
# requests, retry backoff, and the device-wedge watchdog).
define_flag("FLAGS_fleet_retry_backoff_ms", 10.0,
            "base of the router's exponential retry backoff: retry N "
            "sleeps uniform[0, min(cap, base * 2^N)] (full jitter, so "
            "a fleet-wide brownout does not trigger a synchronized "
            "retry storm); 0 = immediate retries (the pre-resilience "
            "behavior)")
define_flag("FLAGS_fleet_retry_backoff_max_ms", 500.0,
            "cap of the router's exponential retry backoff sleep")
define_flag("FLAGS_fleet_breaker_window", 16,
            "per-replica circuit-breaker rolling outcome window: the "
            "last N dispatch outcomes drive the open/close decision")
define_flag("FLAGS_fleet_breaker_failure_ratio", 0.5,
            "circuit-breaker open threshold: the breaker opens when "
            "failures / window samples reaches this ratio (with at "
            "least FLAGS_fleet_breaker_min_samples outcomes seen)")
define_flag("FLAGS_fleet_breaker_min_samples", 4,
            "minimum outcomes in the rolling window before the "
            "failure ratio can open a breaker (no opening on the "
            "first blip)")
define_flag("FLAGS_fleet_breaker_open_ms", 1000.0,
            "circuit-breaker cooldown: an open breaker sheds all "
            "traffic from its replica for this long, then moves to "
            "half-open and admits ONE probe request; the probe's "
            "outcome closes or re-opens it")
define_flag("FLAGS_fleet_breaker_latency_ms", 0.0,
            "slow-but-alive threshold: a SUCCESSFUL dispatch slower "
            "than this counts as a breaker failure, so a replica "
            "serving 100x latency while /readyz-green still gets "
            "drained (0 = latency never trips the breaker)")
define_flag("FLAGS_fleet_hedge_ms", 0.0,
            "request hedging floor: when a submit/submit_many "
            "dispatch is still pending after max(this, the replica "
            "latency window's FLAGS_fleet_hedge_quantile), a hedge "
            "fires to a SECOND replica and the first response wins "
            "(idempotent batch path only — submit_generate never "
            "hedges); 0 = hedging off")
define_flag("FLAGS_fleet_hedge_quantile", 0.95,
            "latency quantile of the primary replica's rolling window "
            "used as the adaptive hedge trigger (bounded below by "
            "FLAGS_fleet_hedge_ms)")
define_flag("FLAGS_fleet_wedge_timeout_ms", 0.0,
            "device-wedge watchdog: a worker dispatch in flight "
            "longer than this flips /readyz to not-ready, fails "
            "waiting requests with ReplicaWedgedError and asks the "
            "supervisor for a restart (worker processes exit; the "
            "respawn is a warm start). 0 = watchdog off")

# ---- multi-tenant scheduling (serving/scheduling/) ----
define_flag("FLAGS_sched_policy_file", "",
            "JSON tenant-policy file (rate/burst/weight/priority per "
            "tenant); hot-reloaded on mtime change, like /reload. "
            "Empty = flags-only policy")
define_flag("FLAGS_sched_default_rate", 0.0,
            "default tenant token-bucket refill rate in tokens/s "
            "(admission cost is 1 token per request at the worker, "
            "prompt+max_new tokens at the generation engine); "
            "0 = unlimited")
define_flag("FLAGS_sched_default_burst", 64.0,
            "default tenant token-bucket depth (burst allowance)")
define_flag("FLAGS_sched_default_weight", 1.0,
            "default tenant weighted-fair-queuing weight (a weight-4 "
            "tenant drains 4x the token volume of a weight-1 tenant "
            "under contention)")
define_flag("FLAGS_sched_default_priority", "standard",
            "default tenant priority class: realtime | standard | "
            "batch (admission prefers realtime; page-pressure "
            "preemption evicts batch first and never touches a "
            "higher class)")

# ---- SLO-driven autoscaling (serving/scheduling/autoscaler.py) ----
define_flag("FLAGS_autoscale_min_replicas", 1,
            "autoscaler floor: never scale the fleet below this")
define_flag("FLAGS_autoscale_max_replicas", 8,
            "autoscaler ceiling: never scale the fleet above this")
define_flag("FLAGS_autoscale_cooldown_s", 30.0,
            "minimum seconds between scale actions in either "
            "direction (hysteresis against flapping)")
define_flag("FLAGS_autoscale_scale_in_quiet_s", 120.0,
            "scale IN only after this long with no burn-rate rule "
            "firing and queue/occupancy low (asymmetric hysteresis: "
            "out fast, in slow)")
define_flag("FLAGS_autoscale_queue_high", 16.0,
            "router/worker queue depth above which the autoscaler "
            "scales out")
define_flag("FLAGS_autoscale_occupancy_high", 0.85,
            "decode-slot occupancy fraction above which the "
            "autoscaler scales out")
define_flag("FLAGS_autoscale_interval_s", 5.0,
            "autoscaler control-loop evaluation period in seconds")

# ---- runtime lockdep sanitizer (analysis/sanitizer.py) ----
define_flag("FLAGS_lockdep", False,
            "instrument threading.Lock/RLock/Condition constructed by "
            "repo code with the lockdep sanitizer: per-thread "
            "acquisition stacks, an observed lock-order graph, and an "
            "error the FIRST time an AB/BA order inversion is "
            "observed (not only when it deadlocks). Installed by the "
            "tier-1 pytest fixture when set; opt-in because every "
            "guarded acquire pays a bookkeeping tax")
define_flag("FLAGS_lockdep_hold_warn_ms", 100.0,
            "lockdep flags any instrumented lock held longer than "
            "this many milliseconds (a long hold under traffic is a "
            "convoy; holding across I/O is the static LD002 rule's "
            "runtime twin). 0 disables hold-time tracking")
define_flag("FLAGS_lockdep_raise", True,
            "raise LockdepViolation in the acquiring thread on the "
            "first observed inversion per lock pair (False = record "
            "in sanitizer.report() only — crash-averse production "
            "canaries)")
