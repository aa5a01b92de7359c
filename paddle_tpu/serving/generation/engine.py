"""GenerationServer: continuous-batching autoregressive decode serving.

Orca-style iteration-level scheduling (Yu et al., OSDI '22) over the
paged KV cache: the in-flight decode batch is re-formed EVERY step —
new sequences join as soon as a slot and pages free up, finished ones
are evicted the step they finish — instead of the reap-and-dispatch
barrier the batch-predict server uses. There is exactly one compiled
decode shape, ``[max_batch, 1]`` with dead lanes slot-masked, so the
whole decode lattice is two signatures (prefill buckets + the decode
step) and a warm PR 5 compile cache makes it cold-start free.

Flow per worker iteration:

1. **admit**: pop FIFO requests while a batch slot AND their full page
   reservation are available; drop expired ones
   (``DeadlineExceededError``, matching ``submit`` semantics — a
   deadline gates scheduling, never an in-flight stream). Admission
   consults the shared-prefix radix index (prefix_cache.py) first:
   matched full pages are mapped into the block table (refcounted
   sharing), shrinking the reservation AND the prefill window.
2. **prefill**: admitted prompts run one forward at their (pow2-row,
   seq-bucket) shape — the PR 1/2 bucket lattice — writing prompt K/V
   into their pages and choosing the first token. Prefix hits run the
   CHUNKED suffix prefill instead (attention reaches the cached
   prefix through the block tables), then every prompt's full pages
   are published to the index.
3. **decode**: one fixed-shape step for every live lane. The program
   chooses each lane's next token (greedy, or sampled at the uniform
   this loop drew from the request's own RNG) and the step fetches
   ``[max_batch]`` token ids, never the logits; they stream out through
   each request's ``StreamingFuture``. The loop runs ONE STEP AHEAD:
   each lane's last token stays on the device, so step i+1 is enqueued
   (from what the host knows without step i's tokens: positions,
   tables, temperatures, uniforms) before step i is harvested, and the
   dispatch, the fetch, the emission, the finish checks and the next
   admission all run beside a program instead of between two
   (``_decode_iteration``). With a draft model configured, each
   iteration is instead draft-propose-k + ONE fixed-shape
   ``[max_batch, k+1]`` verify step with accept-and-resample
   (speculative decoding; output distribution unchanged).
4. **evict**: eos / length / cancelled sequences release pages
   immediately (KV page eviction), freeing admission capacity for the
   next iteration; completed sequences' full pages stay behind in the
   prefix index (refcount 1) until pool pressure LRU-evicts them.

Backpressure mirrors ``InferenceServer.submit``: a bounded queue
raising ``QueueFullError``, ``ServerClosedError`` after shutdown, and
a fault barrier that fails only the affected requests, never the
worker.
"""
from __future__ import annotations

import concurrent.futures
import threading
import time
import weakref
from collections import deque
from types import SimpleNamespace
from typing import (Callable, Dict, List, NamedTuple, Optional, Sequence,
                    Tuple)

import numpy as np

from ...observability import tracing
from ...profiler import RecordEvent, trace_full_collections
from ..bucketing import ShapeBucketPolicy
from ..request import (DeadlineExceededError, QueueFullError,
                       QuotaExceededError, ServerClosedError)
from .kv_cache import PagedKVCache
from .model_fns import CachedDecoder, supports_cached_decode
from .prefix_cache import PrefixCache
from .runner import SITES, Enqueued, ProgramRunner
from .spec_decode import accept_tokens, softmax

__all__ = ["GenerationServer", "StreamingFuture", "DecodeMetrics",
           "engines_statusz"]

# live-engine registry for /statusz (weak: a dropped engine vanishes)
_ENGINES_LOCK = threading.Lock()
_ENGINES: "weakref.WeakSet" = weakref.WeakSet()


def engines_statusz() -> dict:
    """``/statusz`` section: every live engine's prefix-cache,
    speculative and page-accounting state (incl. the refcount-leak
    check)."""
    with _ENGINES_LOCK:
        engines = list(_ENGINES)
    return {e.metrics.name: e.statusz() for e in engines}


def _flag(name, default):
    from ...framework.flags import flag_value
    try:
        v = flag_value(name)
    except KeyError:
        return default
    return v


class StreamingFuture:
    """A generation request's result handle: tokens land one by one as
    the engine emits them.

    Consumer surface: iterate (``for tok in fut``) to stream, or
    ``result(timeout)`` to block for the complete generated-token list;
    ``tokens()`` snapshots what has landed so far; ``cancel()`` asks
    the engine to evict the sequence at its next step. A failed
    request raises its exception from ``result()``/iteration.
    """

    def __init__(self):
        self._cond = threading.Condition()
        self._toks: List[int] = []
        self._exc: Optional[BaseException] = None
        self._done = False
        self._finish_reason: Optional[str] = None
        self._cancel_requested = False
        self._on_cancel = None

    # ---- consumer ----
    def __iter__(self):
        i = 0
        while True:
            with self._cond:
                while len(self._toks) <= i and not self._done:
                    self._cond.wait()
                if i < len(self._toks):
                    tok = self._toks[i]
                    i += 1
                else:
                    if self._exc is not None:
                        raise self._exc
                    return
            yield tok       # outside the lock: consumer code may block

    def result(self, timeout: Optional[float] = None) -> List[int]:
        """Block until the stream finishes; returns ALL generated token
        ids (eos included when one was produced)."""
        with self._cond:
            if not self._cond.wait_for(lambda: self._done, timeout):
                raise TimeoutError("generation still streaming")
            if self._exc is not None:
                raise self._exc
            return list(self._toks)

    def tokens(self) -> List[int]:
        with self._cond:
            return list(self._toks)

    def done(self) -> bool:
        with self._cond:
            return self._done

    def exception(self) -> Optional[BaseException]:
        with self._cond:
            return self._exc

    @property
    def finish_reason(self) -> Optional[str]:
        """"eos" | "length" | "cancelled" | "error" | deadline/shutdown
        reasons; None while streaming."""
        with self._cond:
            return self._finish_reason

    def cancel(self) -> bool:
        """Request eviction; returns False when already finished. The
        engine honors it at its next harvest — tokens already emitted
        stay available. A registered cancel hook (the fleet router's
        socket-close propagation) fires outside the lock, so a routed
        stream's cancellation reaches the replica instead of only
        stopping client-side iteration."""
        with self._cond:
            if self._done:
                return False
            self._cancel_requested = True
            hook = self._on_cancel
        if hook is not None:
            try:
                hook()
            except Exception:  # noqa: BLE001 - propagation is best-
                pass           # effort; local cancel already holds
        return True

    def _set_cancel_hook(self, hook):
        """Install/clear the propagation hook; when cancellation was
        already requested, fire immediately (the cancel raced the
        hook installation)."""
        with self._cond:
            self._on_cancel = hook
            fire = hook is not None and self._cancel_requested \
                and not self._done
        if fire:
            try:
                hook()
            except Exception:  # noqa: BLE001 - as above
                pass

    def cancelled(self) -> bool:
        with self._cond:
            return self._finish_reason == "cancelled"

    # ---- engine side ----
    def _emit(self, tok: int):
        with self._cond:
            self._toks.append(int(tok))
            self._cond.notify_all()

    def _finish(self, reason: str):
        with self._cond:
            if self._done:
                return
            self._done = True
            self._finish_reason = reason
            self._cond.notify_all()

    def _fail(self, exc: BaseException, reason: str = "error"):
        with self._cond:
            if self._done:
                return
            self._exc = exc
            self._done = True
            self._finish_reason = reason
            self._cond.notify_all()


class _Request:
    __slots__ = ("prompt", "max_new", "temperature", "rng", "future",
                 "submit_t", "deadline", "hard_deadline", "trace",
                 "t_wall_ns", "tenant", "prio_rank", "n_done", "cost")

    def __init__(self, prompt: np.ndarray, max_new: int,
                 temperature: float, seed: Optional[int],
                 timeout_ms: Optional[float], trace=None,
                 deadline_ms: Optional[float] = None,
                 tenant: str = "default", prio_rank: int = 1,
                 n_done: int = 0):
        self.prompt = prompt
        self.max_new = int(max_new)
        self.temperature = float(temperature)
        self.rng = np.random.RandomState(seed)
        self.future = StreamingFuture()
        self.submit_t = time.monotonic()
        self.deadline = (self.submit_t + timeout_ms / 1e3
                         if timeout_ms else None)
        # the HARD end-to-end budget (fleet deadline propagation): an
        # in-flight stream past it is evicted at batch re-form, unlike
        # the scheduling-only ``deadline`` above
        self.hard_deadline = (self.submit_t + deadline_ms / 1e3
                              if deadline_ms else None)
        # trace identity (tracing.TraceContext child whose span id is
        # the generate::request root span); warmup never builds a
        # _Request, so warmup traffic is structurally untraced
        self.trace = trace
        self.t_wall_ns = time.time_ns() if trace is not None else 0
        # multi-tenant scheduling: the WFQ cost is token-denominated
        # (prompt + generation budget); n_done counts tokens already
        # streamed before a park/resume cycle, so TTFT and max_new
        # accounting survive preemption
        self.tenant = tenant
        self.prio_rank = int(prio_rank)
        self.n_done = int(n_done)
        self.cost = float(len(prompt) + self.max_new)

    def expired(self, now: float) -> bool:
        if self.deadline is not None and now > self.deadline:
            return True
        return self.hard_expired(now)

    def hard_expired(self, now: float) -> bool:
        return self.hard_deadline is not None and \
            now > self.hard_deadline


class _ActiveSeq:
    """One live lane of the in-flight decode batch."""

    __slots__ = ("req", "slot", "pages", "window_pages", "state_slot",
                 "ctx", "max_total", "last_token", "n_generated",
                 "last_emit_t", "prefix_len", "history", "draft_ctx",
                 "published")

    def __init__(self, req: _Request, slot: int, pages: List[int],
                 max_total: int, prefix_len: int = 0,
                 window_pages: Sequence[int] = (), state_slot: int = 0):
        self.req = req
        self.slot = slot
        self.pages = pages              # prefix pages first, private after
        # the ring of the window layers (kv_cache.py): its own, unshared
        self.window_pages = list(window_pages)
        # and the slot of the state layers' pools (0: the model has none)
        self.state_slot = int(state_slot)
        self.ctx = len(req.prompt)      # tokens whose K/V is cached
        self.max_total = max_total      # prompt + generation budget
        self.last_token = -1
        self.n_generated = 0
        self.last_emit_t = 0.0
        self.prefix_len = int(prefix_len)   # cached tokens reused
        # full token history (prompt + emitted) — spec-decode draft
        # catch-up and publish-on-completion both key pages by content
        self.history: List[int] = [int(t) for t in req.prompt]
        self.draft_ctx = len(req.prompt)    # draft-pool cached tokens
        self.published = False              # prompt pages in the index


class _Step(NamedTuple):
    """A decode step enqueued and not yet harvested."""
    enqueued: Enqueued          # its outputs, on the device
    seqs: List[_ActiveSeq]      # whose lanes it computes, when enqueued


class _Ran(NamedTuple):
    """What ``GenerationServer._dispatch`` hands back."""
    # the first runner's choice a row, fetched (None from a verify
    # step), and its logits: a device array, unless the caller asked
    # for them on the host
    tokens: Optional[np.ndarray]
    logits: object
    ms: float               # host time of the calls and their fetches
    t_wall: int             # time_ns at their start
    fresh: List[bool]       # per runner: a signature seen first now


_EVENTS = ("submitted", "completed", "rejected", "timed_out",
           "cancelled", "failed", "parked", "preempted", "resumed")

# the loop thread's timeline: every moment of GenerationServer._loop
# falls in exactly one of these, each an ``engine::<phase>``
# RecordEvent and a cumulative sum in DecodeMetrics
PHASES = ("admit", "prefill", "decode_feeds", "decode_call",
          "sample_emit", "bookkeeping", "wait")

# tokens (rows x sequence bucket) one prefill dispatch may hold: the
# smallest power of two that leaves the benchmark's GPT servers'
# dispatches as they were (they reach 16 x 1024 and 32 x 768)
PREFILL_TOKEN_BUDGET = 32768


def _tail_buckets_ms(lo: float = 0.05, hi: float = 60e3,
                     ratio: float = 1.05) -> tuple:
    """Geometric bucket bounds fine enough to read a tail from the
    difference of two cumulative snapshots: a quantile is then known
    to within ``ratio``."""
    n = int(np.ceil(np.log(hi / lo) / np.log(ratio)))
    return tuple(float(lo * ratio ** i) for i in range(n + 1))


class DecodeMetrics:
    """Decode-serving metric families on the PR 3 registry, plus
    bounded windows for the JSON snapshot percentiles."""

    def __init__(self, name: str, max_batch: int, page_capacity: int,
                 window: int = 2048, registry=None):
        from ...observability.registry import (PercentileWindow,
                                               default_registry)
        self.name = name
        self._lock = threading.Lock()
        reg = registry or default_registry()
        occ_buckets = sorted({1, 2, 4, 8, 16, 32, 64, 128,
                              max(1, int(max_batch))})
        self._f_events = reg.counter(
            "paddle_decode_requests_total",
            "generation request lifecycle events per engine",
            ("server", "event"))
        self._f_tokens = reg.counter(
            "paddle_decode_tokens_total",
            "tokens emitted by the decode engine", ("server",))
        self._f_inter = reg.histogram(
            "paddle_decode_inter_token_ms",
            "latency between consecutive streamed tokens of a sequence",
            ("server",))
        self._f_step = reg.histogram(
            "paddle_decode_step_ms",
            "device step durations by stage (prefill batch / decode "
            "iteration)", ("server", "stage"))
        self._f_occ = reg.histogram(
            "paddle_decode_batch_occupancy",
            "live lanes per decode iteration (continuous-batching "
            "utilization of the fixed [max_batch, 1] step)",
            ("server",), buckets=occ_buckets)
        self._f_pages = reg.gauge(
            "paddle_decode_kv_pages",
            "KV-cache page occupancy by state", ("server", "state"))
        self._f_pool_bytes = reg.gauge(
            "paddle_decode_kv_pool_bytes",
            "resident device bytes of the paged K/V pools (quantized "
            "pools include their scale planes)", ("server", "dtype"))
        self._f_evict = reg.counter(
            "paddle_decode_kv_page_evictions_total",
            "pages released by finished/cancelled sequences",
            ("server",))
        self._f_compile = reg.counter(
            "paddle_decode_compile_total",
            "decode-engine dispatch signatures by compile-cache result",
            ("server", "result"))
        self._f_ttft = reg.histogram(
            "paddle_decode_ttft_ms",
            "time to first token: submit to first streamed token "
            "(prefix-cache hits collapse the prefill share)",
            ("server",))
        self._f_pfx_hits = reg.counter(
            "paddle_decode_prefix_hits_total",
            "admissions whose prompt reused cached prefix pages",
            ("server",))
        self._f_pfx_reused = reg.counter(
            "paddle_decode_prefix_tokens_reused_total",
            "prompt tokens served from the prefix cache instead of "
            "prefill", ("server",))
        self._f_spec_prop = reg.counter(
            "paddle_decode_spec_proposed_tokens_total",
            "draft-model tokens proposed to the verify step",
            ("server",))
        self._f_spec_acc = reg.counter(
            "paddle_decode_spec_accepted_tokens_total",
            "proposed tokens the target model accepted", ("server",))
        tail = _tail_buckets_ms()
        self._f_stall = reg.histogram(
            "paddle_decode_stream_stall_ms",
            "what every running stream waited beyond a decode step: "
            "end of one iteration's sample_emit to the start of the "
            "next decode call (admissions and prefill groups in it)",
            ("server",), buckets=tail)
        self._f_qwait = reg.histogram(
            "paddle_decode_queue_wait_ms",
            "submit to slot: how long a request waited to be admitted",
            ("server",), buckets=tail)
        for fam in (self._f_events, self._f_tokens, self._f_inter,
                    self._f_step, self._f_occ, self._f_pages,
                    self._f_pool_bytes,
                    self._f_evict, self._f_compile, self._f_ttft,
                    self._f_pfx_hits, self._f_pfx_reused,
                    self._f_spec_prop, self._f_spec_acc,
                    self._f_stall, self._f_qwait):
            fam.clear(server=name)
        self._events = {e: self._f_events.labels(server=name, event=e)
                        for e in _EVENTS}
        self._c_tokens = self._f_tokens.labels(server=name)
        self._h_inter = self._f_inter.labels(server=name)
        self._h_step = {s: self._f_step.labels(server=name, stage=s)
                        for s in ("prefill", "decode")}
        self._h_occ = self._f_occ.labels(server=name)
        self._g_used = self._f_pages.labels(server=name, state="used")
        self._g_free = self._f_pages.labels(server=name, state="free")
        self._c_evict = self._f_evict.labels(server=name)
        self._c_hit = self._f_compile.labels(server=name, result="hit")
        self._c_miss = self._f_compile.labels(server=name,
                                              result="miss")
        self._h_ttft = self._f_ttft.labels(server=name)
        self._c_pfx_hits = self._f_pfx_hits.labels(server=name)
        self._c_pfx_reused = self._f_pfx_reused.labels(server=name)
        self._c_spec_prop = self._f_spec_prop.labels(server=name)
        self._c_spec_acc = self._f_spec_acc.labels(server=name)
        self._h_stall = self._f_stall.labels(server=name)
        self._h_qwait = self._f_qwait.labels(server=name)
        self._w_inter = PercentileWindow(int(window))
        self._w_ttft = PercentileWindow(int(window))
        self._w_step = {s: PercentileWindow(int(window))
                        for s in ("prefill", "decode")}
        self._occ_sum = 0
        self._occ_n = 0
        self._page_capacity = int(page_capacity)
        self._pool_bytes = 0
        self._pool_dtype = "model"
        # ---- the loop thread's timeline and what it dispatched, all
        # cumulative since the server started: the difference of two
        # snapshots is exact for any window
        self._loop_s = dict.fromkeys(PHASES, 0.0)
        # the loop thread's CPU time by phase: under the wall time by
        # what it spent waiting for the GIL, a core or a lock
        self._loop_cpu_s = dict.fromkeys(PHASES, 0.0)
        self._phase: Optional[str] = None     # the open phase
        self._phase_t0 = 0.0                  # perf_counter at its start
        self._phase_cpu0 = 0.0                # thread_time at its start
        self._cpu_clock = None                # the loop thread's clock
        self._prefill = {"prompt_tokens": 0, "padded_tokens": 0,
                         "split_groups": 0}
        self._prefill_by_shape: Dict[str, int] = {}
        self._prefill_call_s_by_shape: Dict[str, float] = {}
        # an expert layer's counters (model_fns._aux_out), cumulative;
        # None until a program of a model with experts has run
        self._moe: Optional[Dict[str, int]] = None
        # the cache manager's numbers by kind of layer
        # (``PagedKVCache.by_kind``), as of the last admission, release
        # or decode step
        self._kv_by_kind: Dict[str, dict] = {}
        # what the runners brought to the host for traffic's programs
        self._fetch_bytes = 0
        # the target's programs of traffic by kind: how many were
        # enqueued and harvested, and the host's seconds in each half
        # (``launch_s`` the executable's call inside the enqueue)
        self._dispatch: Dict[str, Dict[str, float]] = {}
        # how the loop ran ahead: decode programs enqueued while their
        # predecessor was unharvested / on an empty pipe, and lane-steps
        # computed for a lane that had ended by the time of the harvest
        self._run_ahead = {"ahead": 0, "drained": 0, "late_lanes": 0}

    def switch_phase(self, phase: Optional[str], now: float, cpu: float):
        """The loop thread leaves its open phase at ``now`` (a
        ``perf_counter`` reading) and ``cpu`` (its ``thread_time``) and
        enters ``phase``; None closes the timeline (the loop ended)."""
        with self._lock:
            if self._phase is not None:
                self._loop_s[self._phase] += now - self._phase_t0
                self._loop_cpu_s[self._phase] += cpu - self._phase_cpu0
            elif phase is not None:
                # the timeline opens on the loop thread: its CPU clock,
                # for a snapshot taken on another thread
                self._cpu_clock = time.pthread_getcpuclockid(
                    threading.get_ident())
            self._phase, self._phase_t0, self._phase_cpu0 = phase, now, cpu

    def observe_prefill_dispatch(self, padded_rows: int, seq_bucket: int,
                                 prompt_tokens: int, call_ms: float):
        """One prefill group of the padded shape ``padded_rows`` x
        ``seq_bucket`` whose decoder call took ``call_ms`` (what
        ``step_ms["prefill"]`` holds)."""
        shape = f"{int(padded_rows)}x{int(seq_bucket)}"
        with self._lock:
            p = self._prefill
            p["prompt_tokens"] += int(prompt_tokens)
            p["padded_tokens"] += int(padded_rows) * int(seq_bucket)
            self._prefill_by_shape[shape] = \
                self._prefill_by_shape.get(shape, 0) + 1
            self._prefill_call_s_by_shape[shape] = \
                self._prefill_call_s_by_shape.get(shape, 0.0) \
                + call_ms / 1e3

    def observe_prefill_split(self):
        """A prefill group held more rows than one dispatch may."""
        with self._lock:
            self._prefill["split_groups"] += 1

    def observe_moe(self, aux: dict):
        """What one prefill or decode program's expert layers counted
        (host whole numbers, each summed over its layers), added to
        the cumulative ``engine.moe``: assignments, those of them the
        experts held here computed (all, where a program counts none
        apart: every expert is held), held experts that got a row, and
        the rows of each layer's fullest held expert; where the layers
        hold a share, also the layers whose grouped products were
        handed the capacity's rows alone (``narrow_calls``) or all of
        them (``wide_calls``)."""
        with self._lock:
            m = self._moe
            if m is None:
                m = self._moe = {"assignments": 0, "local_assignments": 0,
                                 "experts_touched": 0, "max_expert_load": 0}
                if "moe_narrow_calls" in aux:
                    m.update(narrow_calls=0, wide_calls=0)
            for key in m:
                m[key] += int(aux.get("moe_" + key,
                                      aux["moe_assignments"]))

    def observe_fetch(self, nbytes: int):
        """One program run fetched ``nbytes``."""
        with self._lock:
            self._fetch_bytes += int(nbytes)

    def _dispatch_of(self, kind: str) -> Dict[str, float]:
        return self._dispatch.setdefault(kind, {
            "enqueued": 0, "enqueue_s": 0.0, "launch_s": 0.0,
            "harvested": 0, "harvest_s": 0.0})

    def observe_enqueue(self, kind: str, enqueue_s: float,
                        launch_s: float):
        """One of the target's ``kind`` programs was enqueued in
        ``enqueue_s``, ``launch_s`` of it the executable's call."""
        with self._lock:
            d = self._dispatch_of(kind)
            d["enqueued"] += 1
            d["enqueue_s"] += enqueue_s
            d["launch_s"] += launch_s

    def observe_harvest(self, kind: str, harvest_s: float):
        """One of the target's ``kind`` programs was harvested after a
        wait of ``harvest_s``."""
        with self._lock:
            d = self._dispatch_of(kind)
            d["harvested"] += 1
            d["harvest_s"] += harvest_s

    def observe_run_ahead(self, key: str, n: int = 1):
        """``n`` more of ``engine.run_ahead[key]``."""
        with self._lock:
            self._run_ahead[key] += int(n)

    def set_kv_by_kind(self, by_kind: dict):
        with self._lock:
            self._kv_by_kind = by_kind

    def observe_queue_wait(self, waits_s: Sequence[float]):
        self._h_qwait.observe_many([w * 1e3 for w in waits_s])

    def observe_stream_stall(self, ms: float):
        self._h_stall.observe(float(ms))

    @staticmethod
    def _cumulative(child) -> dict:
        pairs = child.buckets()
        return {"le": [ub for ub, _ in pairs[:-1]],   # +Inf is implied
                "counts": [int(c) for _, c in pairs]}

    def _engine_snapshot(self) -> dict:
        """The ``"engine"`` section (lock held)."""
        loop_s, loop_cpu_s = dict(self._loop_s), dict(self._loop_cpu_s)
        if self._phase is not None:
            # the open phase so far: the phases then sum to the loop
            # thread's wall and CPU time at this very moment
            loop_s[self._phase] += time.perf_counter() - self._phase_t0
            loop_cpu_s[self._phase] += time.clock_gettime(
                self._cpu_clock) - self._phase_cpu0
        out = {"loop_s": loop_s, "loop_cpu_s": loop_cpu_s,
               "prefill": dict(self._prefill,
                               by_shape=dict(self._prefill_by_shape),
                               call_s_by_shape=dict(
                                   self._prefill_call_s_by_shape)),
               "kv": self._kv_by_kind,
               "fetch_bytes": self._fetch_bytes,
               "dispatch": {k: dict(v) for k, v in self._dispatch.items()},
               "run_ahead": dict(self._run_ahead),
               "stream_stall_ms": self._cumulative(self._h_stall),
               "queue_wait_ms": self._cumulative(self._h_qwait)}
        if self._moe is not None:
            out["moe"] = dict(self._moe)
        return out

    def count(self, event: str, n: int = 1):
        self._events[event].inc(n)

    def observe_tokens(self, n: int):
        self._c_tokens.inc(n)

    def observe_inter_token(self, ms_list: Sequence[float]):
        ms_list = [float(m) for m in ms_list]
        if not ms_list:
            return
        with self._lock:
            self._w_inter.extend(ms_list)
        self._h_inter.observe_many(ms_list)

    def observe_step(self, stage: str, ms: float):
        with self._lock:
            self._w_step[stage].observe(float(ms))
        self._h_step[stage].observe(float(ms))

    def observe_occupancy(self, n_active: int):
        with self._lock:
            self._occ_sum += int(n_active)
            self._occ_n += 1
        self._h_occ.observe(n_active)

    def set_kv_pages(self, used: int, free: int):
        self._g_used.set(used)
        self._g_free.set(free)

    def set_kv_pool_bytes(self, nbytes: int, dtype: str):
        self._pool_bytes = int(nbytes)
        self._pool_dtype = dtype or "model"
        self._f_pool_bytes.labels(
            server=self.name, dtype=self._pool_dtype).set(int(nbytes))

    def observe_evictions(self, n_pages: int):
        self._c_evict.inc(n_pages)

    def observe_compile(self, hit: bool):
        (self._c_hit if hit else self._c_miss).inc()

    def observe_ttft(self, ms: float):
        with self._lock:
            self._w_ttft.observe(float(ms))
        self._h_ttft.observe(float(ms))

    def observe_prefix_hit(self, tokens_reused: int):
        self._c_pfx_hits.inc()
        self._c_pfx_reused.inc(int(tokens_reused))

    def observe_spec(self, proposed: int, accepted: int):
        self._c_spec_prop.inc(int(proposed))
        self._c_spec_acc.inc(int(accepted))

    def snapshot(self) -> dict:
        with self._lock:
            occ = (self._occ_sum / self._occ_n) if self._occ_n else 0.0
            return {
                "server": self.name,
                "counters": {e: int(c.value)
                             for e, c in self._events.items()},
                "tokens_total": int(self._c_tokens.value),
                "ttft_ms": self._w_ttft.snapshot(),
                "inter_token_ms": self._w_inter.snapshot(),
                "step_ms": {s: w.snapshot()
                            for s, w in self._w_step.items()},
                "batch_occupancy": {"mean": occ, "steps": self._occ_n},
                "engine": self._engine_snapshot(),
                "kv_pages": {"capacity": self._page_capacity,
                             "used": int(self._g_used.value),
                             "free": int(self._g_free.value),
                             "evicted_total": int(self._c_evict.value),
                             "pool_bytes": self._pool_bytes,
                             "pool_dtype": self._pool_dtype},
                "compile_cache": {"hits": int(self._c_hit.value),
                                  "misses": int(self._c_miss.value)},
                "prefix": {
                    "hits": int(self._c_pfx_hits.value),
                    "tokens_reused": int(self._c_pfx_reused.value)},
                "spec": {
                    "proposed": int(self._c_spec_prop.value),
                    "accepted": int(self._c_spec_acc.value),
                    "acceptance_rate": (
                        int(self._c_spec_acc.value)
                        / max(1, int(self._c_spec_prop.value)))},
            }


class GenerationServer:
    """Continuous-batching decode engine over one cache-capable
    causal-LM Layer (``GPTForCausalLM`` or anything matching
    ``model_fns.supports_cached_decode``).

    ``submit_generate(prompt, ...) -> StreamingFuture`` with bounded-
    queue backpressure and scheduling deadlines; parameters default to
    the ``FLAGS_decode_*`` knobs. The model is snapshot at construction
    (weight updates after construction are not picked up) and put in
    eval mode.
    """

    def __init__(self, model, *, max_batch: Optional[int] = None,
                 page_size: Optional[int] = None,
                 num_pages: Optional[int] = None,
                 max_seq_len: Optional[int] = None,
                 seq_buckets: Optional[Sequence[int]] = None,
                 queue_capacity: Optional[int] = None,
                 default_timeout_ms: Optional[float] = None,
                 eos_token_id: Optional[int] = None,
                 pad_token_id: int = 0,
                 donate: Optional[bool] = None,
                 name: str = "generate",
                 telemetry_port: Optional[int] = None,
                 prefix_cache: Optional[bool] = None,
                 draft_model=None,
                 spec_k: Optional[int] = None,
                 scheduler=None,
                 mesh=None,
                 use_pallas: Optional[bool] = None,
                 prefill_token_budget: Optional[int] = None,
                 start: bool = True):
        model.eval()
        self.model = model
        spec = model.kv_cache_spec()
        # tensor-parallel replica mesh (serving/mesh.py): None defers
        # to FLAGS_serving_mesh_mp, read ONCE here like the other
        # decode knobs. The mesh is threaded EXPLICITLY through the
        # decoder and pools — the engine's worker thread never sees a
        # caller's thread-local global mesh.
        from ..mesh import ServingMesh, serving_mesh_from_flags
        if mesh is None:
            self.serving_mesh = serving_mesh_from_flags()
        else:
            self.serving_mesh = mesh if isinstance(mesh, ServingMesh) \
                else ServingMesh(mesh)
        # the pools shard by K/V heads, which a model may have fewer of
        nh = int(spec.get("num_kv_heads", spec["num_heads"]))
        self.serving_mesh.validate_heads(nh)
        self.max_batch = int(max_batch if max_batch is not None
                             else _flag("FLAGS_decode_max_batch", 8))
        self.page_size = int(page_size if page_size is not None
                             else _flag("FLAGS_decode_page_size", 16))
        self.max_seq_len = int(max_seq_len if max_seq_len is not None
                               else spec["max_seq_len"])
        self.eos_token_id = eos_token_id
        self.pad_token_id = int(pad_token_id)
        # quantized-pool knob: read ONCE here and pinned for the
        # engine's lifetime (it joins the decoder's geometry
        # fingerprint, so warmup manifests and the persistent compile
        # cache never mix executables across a flag flip). Who attends
        # (``use_pallas``) is no knob: None lets the decoder take the
        # fused paged kernels on a TPU and the pure-JAX body elsewhere;
        # tests name one explicitly
        from ...ops.paged_attention import kv_pool_bytes, resolve_kv_dtype
        self.kv_dtype = str(_flag("FLAGS_decode_kv_dtype", "") or "")
        resolve_kv_dtype(self.kv_dtype)   # fail fast on a typo'd dtype
        hd = spec["head_dim"]
        f32_tok = kv_pool_bytes(1, 1, nh, hd, None)
        cur_tok = kv_pool_bytes(1, 1, nh, hd, self.kv_dtype or None)
        # sub-f32 pools grant extra resident sequences for the SAME
        # device budget: int8 (~3.8x smaller) and bf16 (2x) both size
        # to 2x pages ≈ 2x concurrently-resident sequences
        self.kv_capacity_factor = max(1, min(2, f32_tok // max(cur_tok,
                                                               1)))
        if num_pages is None:
            num_pages = int(_flag("FLAGS_decode_kv_pages", 0))
        if not num_pages:
            num_pages = 1 + (self.max_batch
                             * -(-self.max_seq_len // self.page_size)
                             * self.kv_capacity_factor)
        self.default_timeout_ms = default_timeout_ms \
            if default_timeout_ms is not None \
            else (_flag("FLAGS_decode_default_timeout_ms", 0.0) or None)
        cap = queue_capacity if queue_capacity is not None \
            else _flag("FLAGS_decode_queue_capacity", 64)
        self.queue_capacity = int(cap)
        if seq_buckets is None:
            seq_buckets, b = [], 8
            while b < self.max_seq_len:
                seq_buckets.append(b)
                b <<= 1
            seq_buckets.append(self.max_seq_len)
        self.policy = ShapeBucketPolicy(
            max_batch_size=self.max_batch, pad_batch=True,
            seq_buckets=seq_buckets, seq_axis=1)
        # a prefill dispatch holds a bounded number of tokens: a group
        # of more rows than the budget buys at the largest bucket is
        # split (one cap for every bucket, so the programs a server
        # can dispatch are rows <= cap x the buckets)
        self.prefill_token_budget = int(
            prefill_token_budget if prefill_token_budget is not None
            else PREFILL_TOKEN_BUDGET)
        self.prefill_rows_cap = max(
            1, self.prefill_token_budget // max(self.policy.seq_buckets))
        self.kv = PagedKVCache(model, num_pages=int(num_pages),
                               page_size=self.page_size,
                               dtype=self.kv_dtype or None,
                               mesh=self.serving_mesh,
                               max_batch=self.max_batch)
        # columns of a sequence's block-table row: the cache manager's
        # to say (the full layers' table, then the window layers' ring)
        self.pages_per_seq = self.kv.table_width(self.max_seq_len)
        self.decoder = CachedDecoder(
            model, max_batch=self.max_batch, page_size=self.page_size,
            pages_per_seq=self.pages_per_seq, donate=donate,
            max_positions=self.max_seq_len,
            use_pallas=use_pallas, kv_dtype=self.kv_dtype,
            mesh=self.serving_mesh)
        self.use_pallas = self.decoder.use_pallas
        # every program runs through a runner (runner.py): the target's
        # pools stay on ``self.kv``, where callers read them
        self._runners: Tuple[ProgramRunner, ...] = (
            ProgramRunner(self.decoder, self.kv),)
        # ---- shared-prefix KV reuse (radix index over full pages)
        if prefix_cache and self.kv.window is not None:
            raise ValueError(
                "prefix_cache=True with a model whose window layers "
                "keep a ring a sequence: a ring cannot be shared (what "
                "it held of the prefix is overwritten)")
        if self.kv.state_columns and (prefix_cache or draft_model
                                      is not None):
            raise ValueError(
                f"{'prefix_cache=True' if prefix_cache else 'a draft model'}"
                f" with a model whose layers keep a recurrent state a "
                f"sequence (the 'state' kind of kv_cache_spec()): a "
                f"state slot holds the state after a sequence's last "
                f"token alone, so no shared prefix can be resumed from "
                f"it and no verify window rolled back")
        if self.kv.latent and (prefix_cache or draft_model is not None):
            raise ValueError(
                f"{'prefix_cache=True' if prefix_cache else 'a draft model'}"
                f" with a latent attention (the 'latent' of "
                f"kv_cache_spec()): a prefix hit and a verify window "
                f"attend a window of positions over the cache, and no "
                f"such attention is built over a latent pool")
        if prefix_cache is None:
            prefix_cache = self.kv.window is None \
                and not self.kv.state_columns and not self.kv.latent \
                and bool(_flag("FLAGS_decode_prefix_cache", True))
        self.prefix = PrefixCache(self.kv) if prefix_cache else None
        # ---- speculative decoding (draft proposes, target verifies)
        self.spec_k = int(spec_k if spec_k is not None
                          else _flag("FLAGS_decode_spec_k", 0))
        if draft_model is None:
            self.spec_k = 0
        self.draft: Optional[CachedDecoder] = None
        if self.spec_k:
            if not supports_cached_decode(draft_model):
                raise TypeError("draft_model must support KV-cached "
                                "decode (forward(cache=) + "
                                "init_kv_pools)")
            dspec = draft_model.kv_cache_spec()
            if dspec["max_seq_len"] < self.max_seq_len:
                raise ValueError(
                    f"draft model max_seq_len={dspec['max_seq_len']} "
                    f"is shorter than the engine's "
                    f"max_seq_len={self.max_seq_len}")
            draft_model.eval()
            # the draft shares the target's block tables 1:1 (its own
            # pools, same page geometry), so prefix-cache hits reuse
            # draft K/V for free and rollback is the same truncation
            self.draft = CachedDecoder(
                draft_model, max_batch=self.max_batch,
                page_size=self.page_size,
                pages_per_seq=self.pages_per_seq, donate=donate,
                max_positions=self.max_seq_len,
                use_pallas=self.use_pallas, kv_dtype=self.kv_dtype,
                mesh=self.serving_mesh)
            dk, dv = self.serving_mesh.place_pools(
                *draft_model.init_kv_pools(
                    self.kv.num_pages, self.page_size,
                    self.kv_dtype or None))
            # the second runner owns the draft's pools: a prefill is
            # mirrored through it, a proposal step runs on it alone
            self._runners += (ProgramRunner(
                self.draft, SimpleNamespace(k=dk, v=dv)),)
        self.metrics = DecodeMetrics(name, self.max_batch,
                                     self.kv.capacity)
        self.metrics.set_kv_pages(0, self.kv.capacity)
        self.metrics.set_kv_by_kind(self.kv.by_kind())
        self.metrics.set_kv_pool_bytes(self.kv.pool_bytes(),
                                       self.kv_dtype)
        # ---- multi-tenant admission (scheduling subsystem): an
        # AdmissionController adds per-tenant token-bucket quotas,
        # weighted-fair queue ordering, and priority-aware
        # page-pressure preemption; None = classic FIFO engine
        self.scheduler = scheduler
        if scheduler is not None:
            from ..scheduling.schedz import register_controller
            register_controller(scheduler)
        # ONE Condition is both the engine lock and the wakeup channel
        self._lock = threading.Condition()
        self._queue: "deque[_Request]" = deque()
        self._slots: List[Optional[_ActiveSeq]] = [None] * self.max_batch
        self._tables = np.zeros((self.max_batch, self.pages_per_seq),
                                np.int32)
        self._closed = False
        self._abort = False
        self._loop_running = False
        self._worker: Optional[threading.Thread] = None
        self._steps = 0
        self._span: Optional[RecordEvent] = None   # the open phase's
        trace_full_collections()       # python::gc spans, once a process
        # the decode step enqueued and not yet harvested: the loop
        # thread's alone, like _span and _steps (the loop ends with
        # nothing in flight: it drains before it aborts)
        self._inflight: Optional[_Step] = None
        self._loop_thread: Optional[threading.Thread] = None
        # what other threads asked the loop to do between two steps
        self._posted: List[tuple] = []
        # readiness gate (mirrors InferenceServer): not-ready until a
        # warmup pass completes, so a fleet router skips cold engines
        self._ready_gate = bool(
            _flag("FLAGS_serving_ready_requires_warmup", False))
        self._warmed = threading.Event()
        self.telemetry = self._attach_telemetry(telemetry_port, name)
        self._manifest_recorded = set()
        self._manifest = self._init_manifest(name)
        if self._manifest is not None and len(self._manifest) and \
                bool(_flag("FLAGS_decode_warmup_from_manifest", False)):
            self.warmup_from_manifest()
        with _ENGINES_LOCK:
            _ENGINES.add(self)
        if start:
            self.start()

    # ------------------------------------------------------ plumbing
    def _attach_telemetry(self, telemetry_port, name):
        port = telemetry_port if telemetry_port is not None \
            else _flag("FLAGS_serving_telemetry_port", -1)
        if port is None or int(port) < 0:
            return None
        from ... import observability
        srv = observability.start_telemetry_server(port=int(port))
        observability.add_health_check(f"decode:{name}", self._health)
        observability.add_readiness_check(f"decode:{name}",
                                          self._readiness)
        return srv

    def _init_manifest(self, name):
        if not str(_flag("FLAGS_compile_cache_dir", "") or ""):
            return None
        try:
            from ...compile_cache import WarmupManifest, default_cache
            cache = default_cache()
            if cache is None:
                return None
            return WarmupManifest(WarmupManifest.default_path(
                cache.directory, f"decode-{name}",
                self.decoder.fingerprint()))
        except Exception:  # noqa: BLE001 - optimization artifact only
            return None

    @property
    def warmup_manifest(self):
        return self._manifest

    def _health(self):
        if self._closed:
            return False, "shut down"
        w = self._worker
        if w is not None and not w.is_alive() and not self._loop_running:
            return False, "worker thread died"
        return True, {"queue_depth": self.queue_depth,
                      "active_sequences": self.active_sequences}

    @property
    def ready(self) -> bool:
        """Traffic-readiness (see InferenceServer.ready): live, and —
        when the ``FLAGS_serving_ready_requires_warmup`` gate is on —
        warmed up."""
        if self._closed:
            return False
        return self._warmed.is_set() or not self._ready_gate

    def mark_ready(self):
        self._warmed.set()

    def _readiness(self):
        return self.ready, {"warmed": self._warmed.is_set(),
                            "gated": self._ready_gate}

    def refresh_params(self):
        """Re-snapshot the model's live parameters into the decode
        engine (no recompile — params are call operands). The fleet's
        in-process hot-swap path: update the model's weights, then
        ``refresh_params()``; subsequent prefills/decodes use the new
        weights while in-flight sequences keep streaming. Cached
        prefix pages hold K/V computed with the OLD weights, so the
        index is cleared — serving them to new-weight requests would
        be silent staleness. The swap happens between two steps, with
        no program in flight (``_between_steps``): every program runs
        on one set of weights."""
        def swap():
            self.decoder.refresh_params()
            if self.draft is not None:
                self.draft.refresh_params()
            self._clear_prefix()
        self._between_steps(swap)

    def clear_prefix_cache(self) -> int:
        """Drop every unpinned cached prefix page back to the free
        list (pages shared with in-flight sequences stay until those
        finish), between two steps. Returns the number of pages
        freed."""
        return self._between_steps(self._clear_prefix)

    def _clear_prefix(self) -> int:
        if self.prefix is None:
            return 0
        with self._lock:
            n = self.prefix.clear()
            if n:
                self.metrics.set_kv_pages(self.kv.used_pages,
                                          self.kv.free_pages)
            return n

    def _between_steps(self, fn: Callable[[], object]):
        """``fn()`` with no program in flight, and its result: on the
        loop thread at the top of its next iteration, once it has
        harvested what it had enqueued (the caller waits); inline where
        no loop runs, or on the loop thread itself."""
        post = None
        with self._lock:
            on_loop = threading.current_thread() is self._loop_thread
            if self._loop_running and not on_loop:
                post = (fn, concurrent.futures.Future())
                self._posted.append(post)
                self._lock.notify_all()
        if on_loop:
            self._drain("admit")
        if post is None:
            return fn()
        while not concurrent.futures.wait(post[1:], 0.05).done:
            with self._lock:
                # the loop ended before it came round: run it here
                if not self._loop_running and post in self._posted:
                    self._posted.remove(post)
                    return fn()
        return post[1].result()

    def _run_posted(self):
        """The loop thread, at the top of an iteration: what other
        threads posted, after a drain."""
        if not self._posted:
            return
        self._drain("admit")
        while True:
            with self._lock:
                # taken one by one: what a dying loop leaves here, its
                # poster runs itself
                if not self._posted:
                    return
                fn, result = self._posted.pop(0)
            try:
                result.set_result(fn())
            except BaseException as e:  # noqa: BLE001 - the poster's
                result.set_exception(e)

    @property
    def queue_depth(self) -> int:
        with self._lock:
            return len(self._queue)

    @property
    def active_sequences(self) -> int:
        with self._lock:
            return sum(1 for s in self._slots if s is not None)

    def metrics_snapshot(self) -> dict:
        snap = self.metrics.snapshot()
        with self._lock:
            if self.prefix is not None:
                snap["prefix"].update(self.prefix.stats())
            snap["spec"]["k"] = self.spec_k
            snap["kv_leak_check"] = self.kv.leak_check()
        return snap

    def statusz(self) -> dict:
        """One engine's /statusz section: page accounting (with the
        refcount-leak tripwire), prefix-cache and speculative state."""
        with self._lock:
            out = {
                "closed": self._closed,
                "queue_depth": len(self._queue),
                "active_sequences": sum(
                    1 for s in self._slots if s is not None),
                "kv_leak_check": self.kv.leak_check(),
                "spec_k": self.spec_k,
            }
            if self.serving_mesh.live:
                out["serving_mesh"] = self.serving_mesh.statusz(
                    kv_pool_bytes=self.kv.pool_bytes(),
                    num_heads=int(self.model.kv_cache_spec()
                                  ["num_heads"]))
            if self.prefix is not None:
                out["prefix_cache"] = self.prefix.stats()
            if self.scheduler is not None:
                depths: Dict[str, int] = {}
                for q in self._queue:
                    depths[q.tenant] = depths.get(q.tenant, 0) + 1
                out["tenant_queue_depth"] = depths
        return out

    # ------------------------------------------------------ lifecycle
    def start(self):
        with self._lock:
            if self._closed:
                raise ServerClosedError("engine already shut down")
            if self._worker is None or not self._worker.is_alive():
                self._worker = threading.Thread(
                    target=self._loop,
                    name=f"decode-{self.metrics.name}", daemon=True)
                self._worker.start()
        return self

    def shutdown(self, drain: bool = True,
                 timeout: Optional[float] = None):
        """Stop accepting requests; ``drain`` (default) lets queued and
        in-flight sequences finish, otherwise both are failed with
        ServerClosedError. Idempotent."""
        with self._lock:
            self._closed = True
            if not drain:
                self._abort = True
            self._lock.notify_all()
        w = self._worker
        if w is not None and w.is_alive() and \
                w is not threading.current_thread():
            w.join(timeout)
        elif not self._loop_running:
            # never-started engine (start=False): run the loop inline so
            # queued requests still drain (or abort) instead of hanging
            # their futures forever
            self._loop()
        if self.telemetry is not None:
            from ...observability import (remove_health_check,
                                          remove_readiness_check)
            remove_health_check(f"decode:{self.metrics.name}")
            remove_readiness_check(f"decode:{self.metrics.name}")

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.shutdown(drain=exc[0] is None)
        return False

    # ------------------------------------------------------ submission
    def submit_generate(self, prompt, max_new_tokens: int = 32,
                        temperature: float = 0.0,
                        timeout_ms: Optional[float] = None,
                        seed: Optional[int] = None,
                        deadline_ms: Optional[float] = None,
                        tenant: Optional[str] = None
                        ) -> StreamingFuture:
        """Enqueue one prompt; returns the token stream. ``timeout_ms``
        is a SCHEDULING deadline (like ``InferenceServer.submit``): a
        request still queued past it fails with DeadlineExceededError;
        once prefilled, the stream always runs to completion.
        ``deadline_ms`` is the HARD end-to-end budget (fleet deadline
        propagation): a stream still decoding past it is EVICTED at
        the next batch re-form — its pages return to the free list and
        its future fails with DeadlineExceededError (tokens already
        emitted stay available) — instead of burning decode steps on
        an answer nobody is waiting for. ``tenant`` selects the
        multi-tenant envelope when the engine has a scheduler
        (untagged maps to ``default``): over-quota submissions raise
        the typed per-tenant QuotaExceededError. Raises QueueFullError
        at capacity, ServerClosedError after shutdown, ValueError for
        prompts that leave no room to generate."""
        if self._closed:
            raise ServerClosedError("engine is shut down")
        prompt = np.asarray(
            prompt.numpy() if hasattr(prompt, "numpy") else prompt
        ).astype(np.int64).reshape(-1)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        if prompt.size >= self.max_seq_len:
            raise ValueError(
                f"prompt length {prompt.size} leaves no room to "
                f"generate within max_seq_len={self.max_seq_len}")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        ctx = tracing.request_context()
        prio_rank, tname = 1, "default"
        if self.scheduler is not None:
            pol = self.scheduler.policy.lookup(tenant)
            tname, prio_rank = pol.tenant, pol.rank
            # token-denominated quota: one submission spends
            # prompt + generation budget from the tenant's bucket
            cost = float(prompt.size + max_new_tokens)
            if not self.scheduler.try_admit(tname, cost):
                self.metrics.count("rejected")
                err = QuotaExceededError(
                    f"tenant {tname!r} exceeded its token quota "
                    f"({pol.rate:g}/s, burst {pol.burst:g})",
                    tenant=tname)
                if ctx is not None:
                    tracing.record_span(
                        ctx.child(), "generate::shed", stage="shed",
                        start_unix_ns=time.time_ns(), duration_ms=0.0,
                        status="error",
                        attrs={"server": self.metrics.name,
                               "tenant": tname,
                               "error": "QuotaExceededError"},
                        root=True)
                raise err
        req = _Request(prompt, max_new_tokens, temperature, seed,
                       timeout_ms if timeout_ms is not None
                       else self.default_timeout_ms,
                       trace=ctx.child() if ctx is not None else None,
                       deadline_ms=deadline_ms,
                       tenant=tname, prio_rank=prio_rank)
        with self._lock:
            if self._closed:
                raise ServerClosedError("engine is shut down")
            if len(self._queue) >= self.queue_capacity:
                self.metrics.count("rejected")
                if req.trace is not None:
                    tracing.record_span(
                        req.trace, "generate::shed", stage="shed",
                        start_unix_ns=req.t_wall_ns, duration_ms=0.0,
                        status="error",
                        attrs={"server": self.metrics.name,
                               "error": "QueueFullError"}, root=True)
                raise QueueFullError(
                    f"generation queue at capacity "
                    f"({self.queue_capacity})")
            self._queue.append(req)
            self.metrics.count("submitted")
            self._lock.notify_all()
        return req.future

    def generate(self, prompt, max_new_tokens: int = 32,
                 temperature: float = 0.0,
                 timeout_ms: Optional[float] = None,
                 seed: Optional[int] = None) -> List[int]:
        """Synchronous convenience: submit and block for the full
        generated-token list."""
        return self.submit_generate(
            prompt, max_new_tokens, temperature, timeout_ms,
            seed).result()

    # ------------------------------------------------------ warmup
    def warmup(self, seq_buckets: Optional[Sequence[int]] = None,
               batch_buckets: Optional[Sequence[int]] = None) -> int:
        """Pre-compile the decode lattice: the single decode-step shape
        (plus the verify step under speculation) and every (pow2-row,
        seq-bucket) prefill shape admission can dispatch — continuous
        batching prefills PARTIAL row groups as slots churn, so the
        row ladder matters, not just max_batch. With the prefix cache
        on, the chunked (suffix-prefill) lattice is warmed alongside,
        and a draft model's mirror signatures ride every warm. Returns
        the number of fresh signatures."""
        fresh = self._warm("decode", self.max_batch)
        if self.spec_k:
            fresh += self._warm("verify", self.max_batch, self.spec_k + 1)
        seqs = list(seq_buckets if seq_buckets is not None
                    else (self.policy.seq_buckets or []))
        if batch_buckets is None:
            batch_buckets, r = [], 1
            while r < self.max_batch:
                batch_buckets.append(r)
                r <<= 1
            batch_buckets.append(self.max_batch)
        for s in seqs:
            for r in batch_buckets:
                fresh += self._warm("prefill", int(r), int(s))
                if self.prefix is not None:
                    fresh += self._warm("prefill_chunked", int(r), int(s))
        self._warmed.set()
        return fresh

    def _warm(self, kind: str, rows: int, seq: int = 0) -> int:
        """Zeros of ``kind``'s shapes (``rows`` lanes; ``seq`` token
        columns, for every kind but decode) through the runners traffic
        of that kind takes, unrecorded: the site stays tagged in the
        manifest by traffic alone, so a restarted engine replays what
        was observed. Returns the number of fresh signatures. A verify
        step warms the target alone: a draft never verifies. A decode
        step is run with its tokens on the host and on the device, the
        two forms of its one signature."""
        def vec(dtype):
            return np.zeros(rows, dtype)
        tables = np.zeros((rows, self.pages_per_seq), np.int32)
        if kind == "decode":
            feeds = (vec(np.int32), vec(np.int32), vec(bool),
                     vec(np.int32), tables)
        else:
            feeds = (np.zeros((rows, seq), np.int64), vec(np.int32))
            if kind != "prefill":
                feeds += (vec(np.int32),)   # a start beside the lengths
            feeds += (tables,)
        if kind != "verify":
            feeds += (vec(np.float32), vec(np.float32))     # all greedy
        runners = self._runners[:1] if kind == "verify" else self._runners
        fresh = sum(self._dispatch(kind, feeds, (), runners, record=False,
                                   host_logits=kind == "verify").fresh)
        if kind == "decode" and self.draft is None:
            # the other forms of the tokens that traffic's steps take
            # (``_decode_iteration``): as a step left them on the
            # device, and with the host's written over them there
            target = self._runners[0]
            step = target.enqueue(kind, feeds)
            tokens = self.decoder.lane_tokens(
                step.tokens, np.full(rows, -1, np.int32))
            fresh += target.run(kind, (tokens,) + feeds[1:]).fresh
        return fresh

    def warmup_from_manifest(self, path: Optional[str] = None) -> int:
        """Replay the persisted decode/prefill signatures a previous
        process dispatched — each a persistent-cache load when
        ``FLAGS_compile_cache_dir`` is warm. Returns the fresh-compile
        count; 0 when no manifest exists."""
        if path is not None:
            from ...compile_cache import WarmupManifest
            manifest = WarmupManifest(path)
        else:
            manifest = self._manifest
        if manifest is None:
            return 0
        fresh = 0
        for kind in ("prefill", "prefill_chunked"):
            for spec in manifest.specs(site=SITES[kind]):
                (rows, seq) = spec["feeds"][0][0]
                if rows > self.max_batch or seq > self.max_seq_len:
                    continue
                fresh += self._warm(kind, int(rows), int(seq))
        if manifest.specs(site=SITES["decode"]):
            fresh += self._warm("decode", self.max_batch)
        if self.spec_k and any(
                spec["feeds"][0][0] == (self.max_batch, self.spec_k + 1)
                for spec in manifest.specs(site=SITES["verify"])):
            fresh += self._warm("verify", self.max_batch, self.spec_k + 1)
        self._warmed.set()
        return fresh

    def _note_dispatch(self, site: str, fresh: bool, feeds,
                       record: bool = True):
        """Compile accounting per dispatch; TRAFFIC dispatches (not
        warmup replays) persist their signature so a restarted engine
        pre-warms exactly the observed lattice."""
        self.metrics.observe_compile(hit=not fresh)
        if record and self._manifest is not None:
            key = (site, tuple(tuple(s) for s, _ in feeds))
            if key not in self._manifest_recorded:
                self._manifest_recorded.add(key)
                self._manifest.record(feeds, site=site)

    # ------------------------------------------------------ worker
    def _enter_phase(self, phase: Optional[str], **args) -> float:
        """The loop thread leaves its open phase and enters ``phase``
        (one of ``PHASES``; None when the loop ends): the
        ``engine::<phase>`` span closes and the next opens at one
        clock reading, which is returned, so every moment of the loop
        is in exactly one span and one cumulative sum of
        ``metrics_snapshot()["engine"]["loop_s"]``."""
        if self._span is not None:
            self._span.end()
        now = time.perf_counter()
        self.metrics.switch_phase(phase, now, time.thread_time())
        self._span = None
        if phase is not None:
            self._span = RecordEvent("engine::" + phase, args=args)
            self._span.begin()
        return now

    def _note_kv_pages(self):
        """The page gauges, after the cache manager's books moved."""
        self.metrics.set_kv_pages(self.kv.used_pages, self.kv.free_pages)
        self.metrics.set_kv_by_kind(self.kv.by_kind())

    def _enter_decode_call(self, active: List[_ActiveSeq],
                           ctx_after: np.ndarray,
                           stall_t0: Optional[float]):
        """``engine::decode_call`` opens, and says on the span what
        the step's attention has to read: ``context_tokens`` cached
        positions over ``active`` live lanes (the work behind
        ``paged_attn_roofline``, on the trace's own clock), and for a
        model with window layers ``window_context_tokens``, the part of
        each lane's context such a layer reads (``min(ctx, window)``),
        for one with state layers ``state_slots_live``, the slots the
        step reads and writes. ``stall_t0`` is when this loop iteration
        began, if it began with a live stream (the end of the last
        iteration's
        sample_emit): what every running stream has waited since, the
        admissions and prefill groups of this iteration, is the
        stream stall."""
        args = {"active": len(active),
                "context_tokens": int(ctx_after.sum())}
        if self.kv.state_columns:
            # a live lane reads and writes its slot of every state layer
            args["state_slots_live"] = len(active)
        if self.kv.window is not None:
            args["window_context_tokens"] = int(
                np.minimum(ctx_after, self.kv.window).sum())
        now = self._enter_phase("decode_call", **args)
        if stall_t0 is not None:
            self.metrics.observe_stream_stall((now - stall_t0) * 1e3)

    def _loop(self):
        """The worker: admit and prefill, re-form the batch, one decode
        iteration; again. With plain decoding one decode step is in
        flight across iterations (``self._inflight``): iteration j
        enqueues step j+1, then harvests and emits step j, so the
        admission and the prefills of iteration j+1, too, run while
        step j+1 does (a prefill's program queues behind it on the
        device, and its pages are written after it). Whatever reads or
        rewrites a lane from the host drains first (``_drain``): a
        park, an abort, what ``_between_steps`` posts. A server with a
        draft harvests each program before it forms the next (the host
        judges the proposals): nothing is ever in flight there."""
        with self._lock:
            self._loop_running = True
            self._loop_thread = threading.current_thread()
        try:
            while True:
                # unlocked: nothing but this thread writes _slots
                now = self._enter_phase("admit")
                stall_t0 = now if any(
                    s is not None for s in self._slots) else None
                self._run_posted()
                self._admit_and_prefill()
                self._enter_phase("bookkeeping")
                with self._lock:
                    self._evict_expired_streams()
                    active = [s for s in self._slots if s is not None]
                    if self._abort:
                        self._do_abort()
                        return
                    if not active and self._inflight is None:
                        if self._closed and not self._queue:
                            return
                        self._enter_phase("wait")
                        self._lock.wait(0.05)
                        continue
                if self.draft is not None:
                    self._spec_iteration(active, stall_t0)
                else:
                    self._decode_iteration(active, stall_t0)
        finally:
            self._enter_phase(None)
            with self._lock:
                self._loop_running = False
                self._loop_thread = None

    def _evict_expired_streams(self):
        """Deadline check at batch re-form (lock held): an in-flight
        stream whose HARD budget expired is evicted now — its future
        fails with DeadlineExceededError (emitted tokens stay
        readable), its pages return to the free list, and its lane
        frees up for the admission pass — instead of spending further
        decode steps on a request whose caller has already given up."""
        now = time.monotonic()
        for seq in list(self._slots):
            if seq is None or not seq.req.hard_expired(now):
                continue
            seq.req.future._fail(
                DeadlineExceededError(
                    f"deadline budget expired after "
                    f"{seq.n_generated} generated token(s); stream "
                    f"evicted"), reason="deadline")
            self._release(seq, "timed_out")
            self._trace_finish([seq], "error",
                               error="DeadlineExceededError",
                               finish_reason="deadline")

    def _preempt_for_pages(self, rank: int, need: int) -> bool:
        """Priority-aware page pressure (lock held): park in-flight
        streams of a STRICTLY lower priority class (higher rank
        number) — lowest class first, youngest first within a class —
        until ``need`` pages are free. Generalizes the expired-stream
        eviction above: pages go back to the free list (leak_check
        stays clean), but the stream is re-queued to RESUME from its
        full token history instead of failing. Returns True when the
        reservation now fits; equal-or-higher classes are never
        touched."""
        if need > self.kv.free_pages + sum(
                len(s.pages) for s in self._slots
                if s is not None and s.req.prio_rank > rank):
            return False        # not even parking everyone would fit
        # a parked stream resumes from its history: the token of the
        # step in flight has to be in it (and the harvest may end a
        # stream, so the victims are chosen after it)
        self._drain("admit")
        victims = [s for s in self._slots
                   if s is not None and s.req.prio_rank > rank]
        victims.sort(key=lambda s: (-s.req.prio_rank,
                                    -s.req.submit_t))
        for seq in victims:
            if self.kv.free_pages >= need:
                break
            self._park(seq)
        return self.kv.free_pages >= need

    def _park(self, seq: _ActiveSeq):
        """Preempt ONE in-flight stream (lock held): free its pages
        and lane, then re-queue it to resume — the resumed request's
        prompt is the full token history, so a later prefill (prefix
        cache permitting, a cheap one) reconstructs the K/V and the
        SAME future keeps streaming where it left off. When resume is
        impossible (engine closing, queue full, or the history already
        fills max_seq_len) the stream fails with the typed per-tenant
        QuotaExceededError instead of hanging."""
        self._release(seq, "parked")
        r = seq.req
        history = list(seq.history)     # prompt + every emitted token
        resumable = (not self._closed
                     and len(self._queue) < self.queue_capacity
                     and len(history) < self.max_seq_len
                     and r.max_new - seq.n_generated >= 1)
        if not resumable:
            self.metrics.count("preempted")
            r.future._fail(
                QuotaExceededError(
                    f"stream preempted by a higher priority class "
                    f"after {seq.n_generated} token(s); resume "
                    f"unavailable", tenant=r.tenant),
                reason="preempted")
            self._trace_finish([seq], "error",
                               error="QuotaExceededError",
                               finish_reason="preempted")
            return
        nr = _Request(np.asarray(history, np.int64),
                      r.max_new - seq.n_generated, r.temperature,
                      None, None, trace=r.trace,
                      tenant=r.tenant, prio_rank=r.prio_rank,
                      n_done=r.n_done + seq.n_generated)
        # the resumed request IS the original request: same future,
        # same RNG stream, same deadlines, same submit time (so the
        # scheduling deadline keeps covering the whole stream)
        nr.future = r.future
        nr.rng = r.rng
        nr.submit_t = r.submit_t
        nr.deadline = r.deadline
        nr.hard_deadline = r.hard_deadline
        nr.t_wall_ns = r.t_wall_ns
        self._queue.append(nr)

    def _do_abort(self):
        """drain=False shutdown: fail everything still live (lock
        held), after the harvest of what is in flight."""
        self._drain("bookkeeping")
        err = ServerClosedError("engine shut down before completion")
        for req in self._queue:
            req.future._fail(err, reason="shutdown")
            self.metrics.count("failed")
        self._queue.clear()
        for seq in list(self._slots):
            if seq is not None:
                seq.req.future._fail(err, reason="shutdown")
                self._release(seq, "failed")
                self._trace_finish([seq], "error",
                                   error="ServerClosedError")

    # ---- admission + prefill ----
    def _admit_and_prefill(self):
        admitted: List[_ActiveSeq] = []
        now = time.monotonic()
        with self._lock:
            # deadline sweep over the whole queue (it is bounded)
            live = deque()
            for req in self._queue:
                if req.expired(now):
                    self.metrics.count("timed_out")
                    req.future._fail(
                        DeadlineExceededError(
                            "deadline passed before the request could "
                            "be scheduled"), reason="timed_out")
                    if req.trace is not None:
                        tracing.record_span(
                            req.trace, "generate::queue",
                            stage="queue",
                            start_unix_ns=req.t_wall_ns,
                            duration_ms=(now - req.submit_t) * 1e3,
                            status="error",
                            attrs={"server": self.metrics.name,
                                   "error": "DeadlineExceededError"},
                            root=True)
                else:
                    live.append(req)
            self._queue = live
            free_slots = [i for i, s in enumerate(self._slots)
                          if s is None]
            while self._queue and free_slots:
                # weighted-fair pick across tenants (priority classes
                # first) when a scheduler is attached; FIFO otherwise
                idx = 0
                if self.scheduler is not None and len(self._queue) > 1:
                    sel = self.scheduler.select(self._queue)
                    idx = sel if sel is not None else 0
                req = self._queue[idx]
                max_total = min(len(req.prompt) + req.max_new,
                                self.max_seq_len)
                # admission consults the prefix index FIRST: matched
                # full pages are shared (retained), only the remainder
                # of the reservation comes from the free list
                matched, shared = (0, [])
                if self.prefix is not None:
                    matched, shared = self.prefix.match(req.prompt)
                need = self.kv.pages_for(max_total) - len(shared)
                pages = self.kv.alloc(need)
                if pages is None and self.prefix is not None:
                    # pool pressure: reclaim LRU cache-only pages,
                    # then retry once
                    if self.prefix.evict(need - self.kv.free_pages):
                        pages = self.kv.alloc(need)
                if pages is None and self.scheduler is not None:
                    # priority-aware page pressure: park strictly
                    # LOWER-priority in-flight streams (batch before
                    # standard; realtime is never touched) until the
                    # reservation fits, then retry once
                    if self._preempt_for_pages(req.prio_rank, need):
                        pages = self.kv.alloc(need)
                if pages is None:
                    break       # head-of-line until pages free up
                # the window layers' ring: sized for every lane, so it
                # is there whenever a slot is
                try:
                    ring = self.kv.alloc_window(max_total)
                except BaseException:
                    self.kv.release(pages)
                    raise
                if ring is None:
                    self.kv.release(pages)
                    break
                # and the state layers' slot, sized for every lane too
                try:
                    state_slot = self.kv.alloc_state()
                except BaseException:
                    self.kv.release(pages)
                    self.kv.release_window(ring)
                    raise
                if state_slot is None:
                    self.kv.release(pages)
                    self.kv.release_window(ring)
                    break
                # exception barrier (pdlint RP001): between taking the
                # reservation and publishing it into self._slots no
                # failure may keep the references — a leaked page never
                # returns to the free list and admission wedges once
                # the pool drains
                try:
                    self.kv.retain(shared)
                except BaseException:
                    self.kv.release(pages)
                    self.kv.release_window(ring)
                    self.kv.release_state(state_slot)
                    raise
                try:
                    if self.prefix is not None:
                        self.prefix.note_admission(matched)
                        if matched:
                            self.metrics.observe_prefix_hit(matched)
                    del self._queue[idx]
                    slot = free_slots.pop(0)
                    seq = _ActiveSeq(req, slot, shared + pages,
                                     max_total, prefix_len=matched,
                                     window_pages=ring,
                                     state_slot=state_slot)
                    self._slots[slot] = seq
                except BaseException:
                    self.kv.release(shared + pages)
                    self.kv.release_window(ring)
                    self.kv.release_state(state_slot)
                    raise
                self.kv.fill_row(self._tables[slot], seq.pages,
                                 seq.window_pages, seq.state_slot)
                # its prompt's pages past the ring are never written
                self.kv.note_positions(0, len(req.prompt))
                admitted.append(seq)
            if admitted:
                self._note_kv_pages()
        if not admitted:
            return
        # submit to slot: the clock is read after the admission loop
        # (lock, deadline sweep, prefix lookup, page allocation), as
        # the generate::queue span below reads it. A parked stream
        # that resumes waited in a lane, not in the queue
        got_slot = time.monotonic()
        self.metrics.observe_queue_wait(
            [got_slot - seq.req.submit_t for seq in admitted
             if not seq.req.n_done])
        t_adm = time.time_ns()
        for seq in admitted:
            if seq.req.trace is not None:
                tracing.record_span(
                    seq.req.trace, "generate::queue", stage="queue",
                    start_unix_ns=seq.req.t_wall_ns,
                    duration_ms=max(
                        0.0, (t_adm - seq.req.t_wall_ns) / 1e6),
                    attrs={"server": self.metrics.name,
                           "slot": seq.slot,
                           "pages": len(seq.pages)})
        # prefill OUTSIDE the lock: cold prompts grouped by prompt seq
        # bucket (windowed causal attention), prefix hits grouped by
        # SUFFIX bucket (chunked attention over the cached prefix) —
        # the TTFT win is the suffix window being a fraction of the
        # prompt window
        cold: Dict[int, List[_ActiveSeq]] = {}
        hot: Dict[int, List[_ActiveSeq]] = {}
        for seq in admitted:
            n_suffix = len(seq.req.prompt) - seq.prefix_len
            bucket = min(self.policy.bucket_seq(n_suffix),
                         self.max_seq_len)
            (hot if seq.prefix_len else cold).setdefault(
                bucket, []).append(seq)
        cap = self.prefill_rows_cap
        for groups, kind in ((cold, "prefill"), (hot, "prefill_chunked")):
            for bucket, seqs in groups.items():
                if len(seqs) > cap:
                    self.metrics.observe_prefill_split()
                for i in range(0, len(seqs), cap):
                    self._prefill_group(seqs[i:i + cap], bucket, kind)

    # ---- the one way a decoder program runs ----
    def _dispatch(self, kind: str, feeds: tuple,
                  seqs: Sequence[_ActiveSeq],
                  runners: Sequence[ProgramRunner], *,
                  stage: Optional[str] = None, record: bool = True,
                  since: Optional[Tuple[int, float]] = None,
                  host_logits: bool = False) -> Optional[_Ran]:
        """Run ``kind``'s program over ``feeds`` on each of ``runners``
        in turn (``self._runners[:1]`` is the target, ``[1:]`` the
        draft, all of it a prefill with its draft mirror) and time the
        lot, from ``since`` (a ``(time_ns, perf_counter)`` pair read
        earlier: a verify step counts the proposal before it) or from
        now, to the last fetch. Each fetch brings the tokens the program
        chose and its expert counters; with ``host_logits`` the logits
        too, for a caller that selects on the host.

        The fault barrier: a failure fails the futures of ``seqs``,
        the sequences in the call, and theirs alone, returns their
        pages and lanes, and returns None; the worker survives. With
        ``stage``, ``engine::bookkeeping`` opens at the second clock
        reading and the time goes to ``step_ms[stage]``. ``record=False``
        is a warmup: it has no request to fail (the error is the
        caller's), enters no manifest and counts no expert, no fetch and
        no dispatch."""
        t_wall, t0 = since or (time.time_ns(), time.perf_counter())
        target, ran = self._runners[0], []
        try:
            for runner in runners:
                step = runner.enqueue(kind, feeds)
                if record:
                    self._observe_enqueue(kind, runner, step)
                run = runner.harvest(step, host_logits)
                ran.append(run)
                if record:
                    self._observe_run(kind, runner, run)
        except Exception as e:  # noqa: BLE001 - the fault barrier
            if not record:
                raise
            self._fail(seqs, e)
            return None
        ms = (time.perf_counter() - t0) * 1e3
        if stage is not None:
            self._enter_phase("bookkeeping")
            self.metrics.observe_step(stage, ms)
        for runner, run in zip(runners, ran):
            # the manifest is the target's lattice: a draft's programs
            # are counted, never recorded
            self._note_dispatch(SITES[kind], run.fresh, run.signature,
                                record=record and runner is target)
        return _Ran(ran[0].tokens, ran[0].logits, ms, t_wall,
                    [run.fresh for run in ran])

    def _observe_enqueue(self, kind: str, runner: ProgramRunner,
                         step: Enqueued):
        """What one enqueued program of traffic leaves in the counters:
        the target's enqueue time (``engine.dispatch``)."""
        if runner is self._runners[0]:
            self.metrics.observe_enqueue(kind, step.enqueue_s,
                                         step.launch_s)

    def _observe_run(self, kind: str, runner: ProgramRunner, run):
        """What one harvested program of traffic leaves in the
        counters: its fetch, and the target's wait for it and expert
        counts."""
        self.metrics.observe_fetch(run.fetched_bytes)
        if runner is not self._runners[0]:
            return
        self.metrics.observe_harvest(kind, run.harvest_s)
        if run.aux:
            self.metrics.observe_moe(run.aux)
            if kind == "decode":
                # what the harvested step's expert layers read, on the
                # open engine::decode_call span: the span of the step
                # enqueued after it, so one step late
                self._span.set_arg("experts_touched",
                                   int(run.aux["moe_experts_touched"]))

    def _fail(self, seqs: Sequence[_ActiveSeq], e: Exception):
        """The fault barrier's other side: the futures of ``seqs``
        fail with ``e``, their pages and lanes return (a sequence that
        had ended already is left as it ended)."""
        with self._lock:
            seqs = list({id(s): s for s in seqs
                         if self._slots[s.slot] is s}.values())
            for seq in seqs:
                seq.req.future._fail(e)
                self._release(seq, "failed")
        self._trace_finish(seqs, "error", error=f"{type(e).__name__}: {e}")

    def _account(self, ran: _Ran, seqs: Sequence[_ActiveSeq],
                 envelopes: Sequence[dict], span: str,
                 attrs_of: Callable[[_ActiveSeq], dict]):
        """What a traffic dispatch leaves for the observers: its
        stepprof ``envelopes`` (``record_step``'s keywords, one per
        step kind the dispatch stands for; a straggler becomes an error
        span in /tracez) and a ``generate::<span>`` request span over
        the dispatch for every traced sequence of ``seqs``, with
        ``attrs_of(seq)``."""
        try:
            from ...observability.stepprof import default_profiler
            for envelope in envelopes:
                default_profiler().record_step(step=self._steps,
                                               **envelope)
        except Exception:  # noqa: BLE001 - profiling is garnish on the
            pass           # hot path
        for seq in seqs:
            if seq.req.trace is not None:
                # long streams are bounded by the flight recorder's
                # per-trace cap, not here
                tracing.record_span(
                    seq.req.trace, "generate::" + span, stage=span,
                    start_unix_ns=ran.t_wall, duration_ms=ran.ms,
                    attrs={"server": self.metrics.name,
                           **attrs_of(seq)})

    def _decode_envelope(self, ms: float, n_active: int, **attrs) -> dict:
        """The stepprof envelope of one decode iteration (occupancy and
        KV pressure ride along)."""
        return dict(
            wall_ms=ms, kind="decode", occupancy=n_active,
            kv_pages_used=self.kv.used_pages,
            attrs=dict(attrs, prefix_tokens_reused=self.prefix.tokens_reused
                       if self.prefix is not None else 0))

    def _prefill_group(self, seqs: List[_ActiveSeq], seq_bucket: int,
                       kind: str):
        """One prefill dispatch. ``kind`` "prefill" runs whole prompts;
        "prefill_chunked" runs prefix-cache hits: the window holds only
        each prompt's unmatched tail, and attention reaches the shared
        prefix pages through the block tables. Both join the
        generate_<kind> executable for paddle_mfu{kind=prefill}: one
        MFU stream per step kind."""
        rows = len(seqs)
        padded = min(self.policy.bucket_batch(rows), self.max_batch)
        self._enter_phase("prefill")
        ids = np.full((padded, seq_bucket), self.pad_token_id, np.int64)
        start = np.zeros(padded, np.int32)
        lens = np.zeros(padded, np.int32)
        tables = np.zeros((padded, self.pages_per_seq), np.int32)
        for i, seq in enumerate(seqs):
            # nothing of a cold prompt is cached: its tail is all of it
            tail = seq.req.prompt[seq.prefix_len:]
            ids[i, :len(tail)] = tail
            start[i] = seq.prefix_len
            lens[i] = len(tail)
            tables[i] = self._tables[seq.slot]
        feeds = ((ids, lens, tables) if kind == "prefill"
                 else (ids, start, lens, tables)) \
            + self._selection_feeds(seqs, range(rows), padded)
        ran = self._dispatch(kind, feeds, seqs, self._runners,
                             stage="prefill")
        if ran is None:
            return
        # the tokens this dispatch computed: the unmatched tails
        self.metrics.observe_prefill_dispatch(padded, seq_bucket,
                                              int(lens.sum()), ran.ms)
        self._account(
            ran, seqs,
            [dict(wall_ms=ran.ms, kind="prefill", occupancy=rows,
                  kv_pages_used=self.kv.used_pages)],
            "prefill",
            lambda seq: {"rows": rows, "seq_bucket": seq_bucket,
                         "prefix_hit": bool(seq.prefix_len),
                         "tokens_reused": seq.prefix_len,
                         "compile_miss": ran.fresh[0]})
        self._publish_prompts(seqs)
        self._enter_phase("sample_emit")
        self._emit_batch(seqs, [[t] for t in ran.tokens[:rows].tolist()])

    def _publish_prompts(self, seqs: List[_ActiveSeq]):
        """Index each prefilled prompt's FULL pages so later admissions
        (including in-flight concurrency) can share them. Runs only
        after the prefill that wrote the pages — and the draft mirror,
        when speculation is on — completed, so indexed pages always
        hold valid K/V in every pool."""
        if self.prefix is None:
            return
        with self._lock:
            for seq in seqs:
                self.prefix.publish(seq.req.prompt, seq.pages,
                                    n_tokens=len(seq.req.prompt))
                seq.published = True

    def _selection_feeds(self, seqs: Sequence[_ActiveSeq],
                         rows: Sequence[int], n: int) -> tuple:
        """``(temperature, uniform)``, float32 ``[n]``, of a program
        that chooses the next token of each of ``seqs`` in its entry of
        ``rows``: a sampled request's temperature and the next number
        of its own ``RandomState`` (so its stream is the same whoever
        shares the batch); 0 for a greedy one and every other row."""
        temperature = np.zeros(n, np.float32)
        uniform = np.zeros(n, np.float32)
        for seq, row in zip(seqs, rows):
            if seq.req.temperature > 0.0:
                temperature[row] = seq.req.temperature
                uniform[row] = seq.req.rng.random_sample()
        return temperature, uniform

    # ---- one decode iteration ----
    def _decode_feeds(self, seqs: List[_ActiveSeq], tokens: Sequence[int],
                      positions: Sequence[int]) -> tuple:
        """The decode program's feeds before its selection's: the lane
        of each of ``seqs`` is fed its entry of ``tokens`` at its entry
        of ``positions`` (the slot it writes; the context it reads ends
        one past it); every other lane is masked dead. The tables are
        a copy: a step may be in flight while ``_release`` and
        ``fill_row`` rewrite ``self._tables`` in place."""
        b = self.max_batch
        toks = np.zeros(b, np.int32)
        pos = np.zeros(b, np.int32)
        mask = np.zeros(b, bool)
        ctx_after = np.zeros(b, np.int32)
        # scalar writes: at 32 lanes they cost half of what four
        # fancy-index assignments from lists do (PERF.md §6, PR 31)
        for seq, token, position in zip(seqs, tokens, positions):
            slot = seq.slot
            toks[slot] = token
            pos[slot] = position
            mask[slot] = True
            ctx_after[slot] = position + 1
        return toks, pos, mask, ctx_after, self._tables.copy()

    def _decode_iteration(self, active: List[_ActiveSeq],
                          stall_t0: Optional[float] = None):
        """Enqueue the next decode step, THEN harvest the one in
        flight: with step i running, step i+1 is formed from what the
        host knows without step i's tokens and handed to the runtime,
        and only then are step i's tokens fetched, emitted and booked.

        *The next step's lanes* are those of ``active`` the host cannot
        yet rule out. A lane riding in the step in flight goes on
        unless that step's token ends it by length (``n_generated + 1``
        reaches ``max_new``, or the position after it leaves the
        reservation: what ``_emit_batch`` will find) or its caller has
        cancelled; it is fed at ``ctx + 1``, and its token is the one
        the step in flight will return, where it lies. A lane that is
        not riding (prefilled since, or the pipe is empty) is fed the
        token the host holds, through ``lane_tokens`` where the others'
        are on the device. What the host cannot know a step early is an
        ``eos``: such a lane runs one more position, inside its own
        reservation, whose token the harvest drops (``late_lanes``).

        *The harvest* emits to the sequences that still own their lane
        (a release since the enqueue, by eviction or a failed prefill
        neighbour, makes the lane late as an ``eos`` does), advances
        their ``ctx`` and runs the finish checks. An error on either
        side fails the sequences of both steps: the successor consumed
        the pools its predecessor returned.

        ``engine::decode_call`` brackets "enqueue i+1 ... step i's fetch
        returned" and carries the arguments of the step it enqueues
        (none where it enqueues nothing): that program starts when its
        predecessor ends, inside this span. ``step_ms["decode"]`` is
        that span's time, for every step harvested. The runner's
        ``runner::enqueue`` and ``runner::harvest`` spans split it, and
        ``engine.dispatch["decode"]`` sums their seconds."""
        self._enter_phase("decode_feeds")
        target, flying = self._runners[0], self._inflight
        riding = {id(s) for s in flying.seqs} if flying else ()
        lanes = [s for s in active if id(s) not in riding or (
            s.n_generated + 1 < s.req.max_new
            and s.ctx + 2 <= s.max_total
            and not s.req.future._cancel_requested)]
        feeds = None
        if lanes:
            feeds = self._decode_feeds(
                lanes, [s.last_token for s in lanes],
                [s.ctx + (id(s) in riding) for s in lanes]) \
                + self._selection_feeds(lanes, [s.slot for s in lanes],
                                        self.max_batch)
            # the context the enqueued step's attention reads: each
            # lane's cached positions, the one it writes among them
            self._enter_decode_call(lanes, feeds[3], stall_t0)
        else:
            self._enter_phase("decode_call")
        t_wall, t0 = time.time_ns(), time.perf_counter()
        self._inflight = run = None
        try:
            if lanes:
                tokens = feeds[0]
                if flying is not None:
                    # the riding lanes' last tokens are on the device;
                    # the others', which the host holds, are written
                    # over that vector there
                    tokens = flying.enqueued.tokens
                    held = [s for s in lanes if id(s) not in riding]
                    if held:
                        fresh = np.full(self.max_batch, -1, np.int32)
                        for s in held:
                            fresh[s.slot] = s.last_token
                        tokens = self.decoder.lane_tokens(tokens, fresh)
                step = target.enqueue("decode", (tokens,) + feeds[1:])
                self._inflight = _Step(step, lanes)
                self._observe_enqueue("decode", target, step)
                self.metrics.observe_run_ahead(
                    "drained" if flying is None else "ahead")
            if flying is not None:
                run = target.harvest(flying.enqueued)
                self._observe_run("decode", target, run)
        except Exception as e:  # noqa: BLE001 - the fault barrier
            self._inflight = None
            self._fail((flying.seqs if flying else []) + lanes, e)
            return
        if run is None:
            return
        ms = (time.perf_counter() - t0) * 1e3
        self._enter_phase("bookkeeping")
        self.metrics.observe_step("decode", ms)
        self._note_dispatch(SITES["decode"], run.fresh, run.signature)
        self._steps += 1
        # the lanes the step computed, and those of them still owned
        # by the sequence it computed them for
        step, n = flying.seqs, len(flying.seqs)
        self.metrics.observe_occupancy(n)
        live = [s for s in step if self._slots[s.slot] is s]
        if len(live) < n:
            self.metrics.observe_run_ahead("late_lanes", n - len(live))
        self._account(
            _Ran(run.tokens, None, ms, t_wall, [run.fresh]), live,
            [self._decode_envelope(ms, n)], "decode_step",
            lambda seq: {"step": seq.n_generated, "occupancy": n})
        for seq in live:
            self.kv.note_positions(seq.ctx, seq.ctx + 1)
            seq.ctx += 1
        if self.kv.window is not None:
            self._note_kv_pages()
        self._enter_phase("sample_emit")
        self._emit_batch(
            live, [[t] for t in run.tokens[[s.slot for s in live]].tolist()])

    def _drain(self, resume: str):
        """Harvest and emit the step in flight, if there is one, and
        go back to phase ``resume`` (loop thread). After it the host
        holds every live lane's last token, history and ``ctx``."""
        if self._inflight is not None:
            self._decode_iteration([])
            self._enter_phase(resume)

    # ---- one speculative iteration: draft proposes, target verifies
    def _spec_iteration(self, active: List[_ActiveSeq],
                        stall_t0: Optional[float] = None):
        """Draft-then-verify (Leviathan et al.): the draft model
        proposes ``spec_k`` tokens per lane through its own paged pools
        (same block tables), then the target scores the whole
        ``[last_accepted, d_1..d_k]`` window in ONE fixed-shape
        ``[max_batch, k + 1]`` verify step. Accept-and-resample on the
        host keeps the output distribution identical to plain
        sampling; rejected tokens' K/V writes sit on the lane's
        already-reserved pages and are rolled back by truncating
        ``ctx``/``draft_ctx`` — the pool itself is never mutated."""
        b, k = self.max_batch, self.spec_k
        # draft proposal and verify are one engine::decode_call: what
        # step_ms["decode"] times under speculation
        # (the verify window reads each lane's context and its k + 1
        # new positions)
        self._enter_decode_call(
            active, np.asarray([s.ctx + k + 1 for s in active], np.int64),
            stall_t0)
        since = (time.time_ns(), time.perf_counter())
        proposal = self._draft_propose(active, k)
        if proposal is None:
            return      # a draft step failed, and the lanes with it
        draft_toks, draft_probs = proposal
        draft_ms = (time.perf_counter() - since[1]) * 1e3
        # ---- verify: one chunked window per lane
        ids = np.zeros((b, k + 1), np.int64)
        start = np.zeros(b, np.int32)
        seg = np.zeros(b, np.int32)
        for s in active:
            ids[s.slot, 0] = s.last_token
            ids[s.slot, 1:] = draft_toks[s.slot]
            start[s.slot] = s.ctx
            seg[s.slot] = k + 1
        ran = self._dispatch("verify", (ids, start, seg, self._tables),
                             active, self._runners[:1], stage="decode",
                             since=since, host_logits=True)
        if ran is None:
            return
        self._steps += 1
        self.metrics.observe_occupancy(len(active))
        # ---- accept-and-resample per lane (host)
        self._enter_phase("sample_emit")
        toks_lists: List[List[int]] = []
        spans: Dict[int, dict] = {}
        n_accepted = 0
        for s in active:
            remaining = min(s.req.max_new - s.n_generated,
                            s.max_total - s.ctx)
            emitted, acc = accept_tokens(
                ran.logits[s.slot], draft_toks[s.slot],
                draft_probs.get(s.slot), s.req.temperature, s.req.rng,
                max_emit=remaining,
                eos_token_id=self.eos_token_id)
            self.metrics.observe_spec(k, acc)
            n_accepted += acc
            s.ctx += len(emitted)
            # rollback-by-truncation: positions past the accepted
            # stream hold rejected garbage in both pools; the shrunken
            # ctx masks them and the next write overwrites in place
            s.draft_ctx = min(s.draft_ctx, s.ctx)
            toks_lists.append(emitted)
            spans[s.slot] = {"proposed": k, "accepted": acc,
                             "emitted": len(emitted),
                             "draft_ms": round(draft_ms, 3),
                             "occupancy": len(active)}
        self._enter_phase("bookkeeping")
        self._account(
            ran, active,
            [self._decode_envelope(ran.ms, len(active),
                                   spec_proposed=k * len(active),
                                   spec_accepted=n_accepted),
             # the verify window alone (iteration minus draft proposal)
             # as its own kind: joins with the generate_verify
             # executable for paddle_mfu{kind=verify}
             dict(wall_ms=max(ran.ms - draft_ms, 0.0), kind="verify",
                  occupancy=len(active),
                  attrs={"draft_ms": round(draft_ms, 4)})],
            "verify", lambda seq: spans[seq.slot])
        self._enter_phase("sample_emit")
        self._emit_batch(active, toks_lists)

    def _draft_propose(self, active: List[_ActiveSeq], k: int):
        """Run the draft model ``k`` single-token steps (same
        [max_batch, 1] signature each time), sampling each lane's
        proposal from the draft distribution with the request's own
        RNG. Lanes whose draft pool lags the target context (one
        position, after a fully-accepted round) catch up first with
        masked feed steps. Returns ``(draft_toks [B, k] int64,
        {slot: draft_probs [k, vocab]} for sampled lanes)``, or None
        when a draft step failed (the barrier of ``_dispatch`` has
        failed every lane of ``active`` then: none can be verified)."""
        draft = self._runners[1:]
        # a draft step's own choice is the argmax: a greedy lane's
        # proposal. A sampled lane's is drawn here from the step's
        # logits, whose distribution the verdict needs as well
        greedy = self._selection_feeds((), (), self.max_batch)
        host_logits = any(s.req.temperature > 0.0 for s in active)
        while True:
            lag = [s for s in active if s.draft_ctx < s.ctx]
            if not lag:
                break
            feeds = self._decode_feeds(
                lag, [s.history[s.draft_ctx] for s in lag],
                [s.draft_ctx for s in lag]) + greedy
            if self._dispatch("decode", feeds, active, draft) is None:
                return None
            for s in lag:
                s.draft_ctx += 1
        draft_toks = np.zeros((self.max_batch, k), np.int64)
        draft_probs: Dict[int, np.ndarray] = {}
        feed = [s.last_token for s in active]
        for j in range(k):
            ran = self._dispatch(
                "decode", self._decode_feeds(
                    active, feed, [s.draft_ctx for s in active]) + greedy,
                active, draft, host_logits=host_logits)
            if ran is None:
                return None
            for i, s in enumerate(active):
                if s.req.temperature > 0.0:
                    row = ran.logits[s.slot]
                    p = softmax(row, s.req.temperature)
                    probs = draft_probs.setdefault(
                        s.slot, np.zeros((k, row.shape[-1])))
                    probs[j] = p
                    cdf = np.cumsum(p)
                    tok = int(min(
                        np.searchsorted(
                            cdf, s.req.rng.random_sample() * cdf[-1],
                            side="right"),
                        row.shape[-1] - 1))
                else:
                    tok = int(ran.tokens[s.slot])
                draft_toks[s.slot, j] = tok
                feed[i] = tok
                s.draft_ctx += 1
        return draft_toks, draft_probs

    # ---- shared harvest: stream, evict ----
    def _emit_batch(self, seqs: List[_ActiveSeq],
                    toks_lists: List[List[int]]):
        """Stream each sequence's newly-selected tokens (one from a
        prefill/decode step, up to spec_k + 1 from a verify step),
        then run the finish checks. Callers updated ``seq.ctx`` first."""
        now = time.monotonic()
        inter = []
        total = sum(len(t) for t in toks_lists)
        self.metrics.observe_tokens(total)
        with self._lock:
            for seq, toks in zip(seqs, toks_lists):
                for tok in toks:
                    tok = int(tok)
                    seq.last_token = tok
                    seq.history.append(tok)
                    seq.n_generated += 1
                    if seq.n_generated == 1:
                        if seq.req.n_done:
                            # a parked stream came back: count the
                            # resume, don't re-observe TTFT (its first
                            # token happened before the preemption)
                            self.metrics.count("resumed")
                        else:
                            self.metrics.observe_ttft(
                                (now - seq.req.submit_t) * 1e3)
                    else:
                        inter.append((now - seq.last_emit_t) * 1e3)
                    seq.last_emit_t = now
                    seq.req.future._emit(tok)
                if not toks:
                    continue
                if seq.req.future._cancel_requested:
                    seq.req.future._finish("cancelled")
                    self._release(seq, "cancelled")
                    self._trace_finish([seq], "ok",
                                       finish_reason="cancelled")
                elif self.eos_token_id is not None and \
                        int(toks[-1]) == self.eos_token_id:
                    seq.req.future._finish("eos")
                    self._release(seq, "completed")
                    self._trace_finish([seq], "ok",
                                       finish_reason="eos")
                elif seq.n_generated >= seq.req.max_new or \
                        seq.ctx + 1 > seq.max_total:
                    # ctx + 1: emitting one more token would need a
                    # cache slot past this sequence's reservation
                    seq.req.future._finish("length")
                    self._release(seq, "completed")
                    self._trace_finish([seq], "ok",
                                       finish_reason="length")
        if inter:
            self.metrics.observe_inter_token(inter)

    def _trace_finish(self, seqs: List[_ActiveSeq], status: str,
                      finish_reason: Optional[str] = None,
                      error: Optional[str] = None):
        """Record each traced sequence's ``generate::request`` root
        span (the whole-stream envelope). Error status tail-promotes
        unsampled traces."""
        now = time.time_ns()
        for seq in seqs:
            r = seq.req
            if r.trace is None:
                continue
            attrs = {"server": self.metrics.name,
                     "prompt_tokens": len(r.prompt),
                     "tokens": seq.n_generated}
            if finish_reason:
                attrs["finish_reason"] = finish_reason
            if error:
                attrs["error"] = error
            tracing.record_span(
                r.trace, "generate::request", stage="request",
                start_unix_ns=r.t_wall_ns,
                duration_ms=max(0.0, (now - r.t_wall_ns) / 1e6),
                status=status, attrs=attrs, root=True)

    def _release(self, seq: _ActiveSeq, event: str):
        """Evict one sequence: drop its page references, free the slot
        (lock held). A COMPLETED sequence first publishes its full
        pages — prompt AND generated tokens — into the prefix index,
        so the pages stay cached (refcount 1, index-held) instead of
        returning to the free list; everything else (partial tail
        page, failed/cancelled streams) frees as refcounts hit zero."""
        if self._slots[seq.slot] is not seq:
            return
        if event == "completed" and self.prefix is not None \
                and seq.published:
            # history[:ctx] are the positions whose K/V is actually in
            # the pool (the final emitted token was never written);
            # under speculation, cap at what the DRAFT pool also holds
            # so shared pages are valid in both pools
            n_ok = seq.ctx if self.draft is None \
                else min(seq.ctx, seq.draft_ctx)
            self.prefix.publish(seq.history, seq.pages, n_tokens=n_ok)
        self._slots[seq.slot] = None
        self._tables[seq.slot, :] = 0
        freed = self.kv.release(seq.pages)
        self.kv.release_window(seq.window_pages)
        seq.window_pages = []
        self.kv.release_state(seq.state_slot)
        seq.state_slot = 0
        self.metrics.observe_evictions(freed)
        self.metrics.count(event)
        self._note_kv_pages()
        self._lock.notify_all()
