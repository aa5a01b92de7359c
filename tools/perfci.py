"""perfci — the committed-perf-record regression gate (ROADMAP item 5).

Every bench in this repo emits one JSON record; the committed copies
(``BENCH_*.json``, ``TRACE_r01.json``, ``ELASTIC_r01.json``,
``GOODPUT_r01.json``) are the perf trajectory. This tool loads them
and enforces tolerance gates — decode/serving throughput and tail
latency, fleet QPS, cold-start ratio, tracing overhead,
elastic-recovery invariants, goodput accounting closure and always-on
observability overhead — so every speed claim is enforced, not
anecdotal.

A record with ``"skipped": true`` (or a crashed ``rc != 0`` wrapper
with no parsed measurement) is "no measurement", NOT "measured zero" —
each gate evaluates the LATEST MEASURED record for its metric and
reports newer unmeasured rounds as stale-measurement diagnostics. No
tool of this repo writes a skip record any more (a bench with no chip
fails); the class stays for wrappers written around a run.

The "recorded sweeps that did NOT win" list from PERF.md ships here as
machine-readable do-not-retry annotations (``--do-not-retry`` /
``do_not_retry_for()``), so automation can refuse to re-run a sweep
that was already measured as a loss.

Usage:

    python tools/perfci.py                 # gate the committed records
    python tools/perfci.py --json          # machine-readable report
    python tools/perfci.py --records DIR   # gate a different record dir
    python tools/perfci.py --do-not-retry  # dump the sweep annotations

Exit codes: 0 = every gate passes or is skipped-with-reason, 1 = a
measured record regressed past tolerance, 2 = usage/internal error.
The CI twin is tests/test_perfci.py.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import Any, Dict, List, Optional

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)


# ------------------------------------------------------------- gates
# op: "min" — value must stay >= baseline*(1-rel_tol);
#     "max" — value must stay <= baseline*(1+rel_tol);
#     "true" — value must be truthy (invariant, no tolerance).
GATES: List[Dict[str, Any]] = [
    {"name": "decode_tok_s", "metric": "decode_tokens_per_sec",
     "files": "BENCH_DECODE_r*.json", "path": ("value",),
     "op": "min", "baseline": 8534.9, "rel_tol": 0.10,
     "unit": "tokens/s",
     "why": "continuous-batching decode throughput (PR 7)"},
    {"name": "decode_p99_inter_token_ms",
     "metric": "decode_tokens_per_sec",
     "files": "BENCH_DECODE_r*.json",
     "path": ("engine_p99_inter_token_ms",),
     "op": "max", "baseline": 1.975, "rel_tol": 0.25, "unit": "ms",
     "why": "decode tail latency between tokens"},
    {"name": "kernels_decode_tok_s", "metric": "decode_kernels",
     "files": "BENCH_KERNELS_r*.json", "path": ("value",),
     "op": "min", "baseline": 929.5, "rel_tol": 0.50,
     "unit": "tokens/s",
     "why": "int8+Pallas serving decode throughput (PR 17); on a CPU "
            "record the kernel runs in interpret mode, so the wide "
            "envelope guards against structural slowdowns (extra "
            "dispatch, accidental dense gather), not kernel speed"},
    {"name": "kernels_ttft_ms", "metric": "decode_kernels",
     "files": "BENCH_KERNELS_r*.json",
     "path": ("variants", "int8_pallas", "ttft_ms"),
     "op": "max", "baseline": 1.7, "rel_tol": 0.50, "unit": "ms",
     "why": "time-to-first-token with quantize-on-write prefill must "
            "stay near the f32 path (r01: 1.69 vs 1.20 ms)"},
    {"name": "kernels_p99_inter_token_ms", "metric": "decode_kernels",
     "files": "BENCH_KERNELS_r*.json",
     "path": ("variants", "int8_pallas", "p99_inter_token_ms"),
     "op": "max", "baseline": 6.9, "rel_tol": 0.50, "unit": "ms",
     "why": "fused-kernel decode tail latency between streamed "
            "tokens (interpret-mode ceiling on CPU records)"},
    {"name": "kernels_capacity_ratio", "metric": "decode_kernels",
     "files": "BENCH_KERNELS_r*.json", "path": ("capacity_ratio",),
     "op": "min", "baseline": 1.8, "rel_tol": 0.0, "unit": "x",
     "why": "int8 KV pool must hold >= 1.8x the pages of the f32 "
            "pool under the same byte budget — the quantized-KV "
            "capacity claim (PR 17, r01: 2.0x at 38% fewer bytes)"},
    {"name": "kernels_greedy_parity", "metric": "decode_kernels",
     "files": "BENCH_KERNELS_r*.json", "path": ("greedy_parity",),
     "op": "true",
     "why": "every kernel/quantization variant (f32/int8 x "
            "reference/Pallas) must emit the IDENTICAL greedy stream "
            "— kernel routing is an optimization, never a model "
            "change (PR 17)"},
    {"name": "kernels_leaks_clean", "metric": "decode_kernels",
     "files": "BENCH_KERNELS_r*.json", "path": ("leaks_clean",),
     "op": "true",
     "why": "page accounting must close after every variant's "
            "trials — quantized pools share the refcounted "
            "allocator (PR 17)"},
    {"name": "prefix_ttft_speedup", "metric": "decode_prefix_spec",
     "files": "BENCH_PREFIX_r*.json",
     "path": ("prefix", "ttft_speedup"),
     "op": "min", "baseline": 3.0, "rel_tol": 0.0, "unit": "x",
     "why": "hot-prefix TTFT >= 3x cold for a 256-token shared "
            "preamble is the PR 12 acceptance floor (r01 measured "
            "10.3x; radix hits turn preamble prefill into block-table "
            "rows)"},
    {"name": "spec_decode_speedup", "metric": "decode_prefix_spec",
     "files": "BENCH_PREFIX_r*.json", "path": ("spec", "speedup"),
     "op": "min", "baseline": 1.5, "rel_tol": 0.0, "unit": "x",
     "why": "speculative single-stream tok/s >= 1.5x plain decode at "
            "the acceptance ceiling (r01 measured 1.73x at k=6, "
            "acceptance 1.0 by zero-residual construction)"},
    {"name": "spec_greedy_parity", "metric": "decode_prefix_spec",
     "files": "BENCH_PREFIX_r*.json", "path": ("spec", "greedy_parity"),
     "op": "true",
     "why": "accept-and-resample must keep speculative greedy output "
            "identical to non-speculative decoding (PR 12)"},
    {"name": "fleet_qps", "metric": "fleet_aggregate_qps",
     "files": "BENCH_FLEET_r*.json", "path": ("value",),
     "op": "min", "baseline": 2524.0, "rel_tol": 0.10, "unit": "req/s",
     "why": "4-replica router aggregate throughput (PR 8)"},
    {"name": "fleet_coldstart_ratio", "metric": "fleet_aggregate_qps",
     "files": "BENCH_FLEET_r*.json",
     "path": ("scale_out", "warm_speedup"),
     "op": "min", "baseline": 2.95, "rel_tol": 0.15, "unit": "x",
     "why": "warm scale-out vs cold replica start (PR 5 compile cache)"},
    {"name": "tp_decode_tok_s", "metric": "serving_tp_decode",
     "files": "BENCH_TP_r*.json", "path": ("value",),
     "op": "min", "baseline": 1359.1, "rel_tol": 0.50,
     "unit": "tokens/s",
     "why": "mp-sharded single-replica decode throughput (serving "
            "mesh). The CPU record's wide envelope guards structure "
            "(an accidental pool gather, a resharding collective per "
            "step), not speed — on the 8-way VIRTUAL device mesh the "
            "shards share one host's cores"},
    {"name": "tp_per_chip_kv_fraction", "metric": "serving_tp_decode",
     "files": "BENCH_TP_r*.json",
     "path": ("mesh", "sharded", "per_chip_kv_fraction"),
     "op": "max", "baseline": 0.125, "rel_tol": 0.0, "unit": "x",
     "why": "per-chip KV residency must be exactly 1/mp of the pool "
            "(heads-sharded layout; measured from the placed shards, "
            "not projected)"},
    {"name": "tp_greedy_parity", "metric": "serving_tp_decode",
     "files": "BENCH_TP_r*.json", "path": ("mesh", "greedy_parity"),
     "op": "true",
     "why": "the mp-sharded engine must emit the IDENTICAL greedy "
            "stream as the single-shard path — tensor parallelism is "
            "a layout, never a model change"},
    {"name": "trace_accounting", "metric": "fleet_trace_span_accounting",
     "files": "TRACE_r*.json",
     "path": ("accounting", "accounting_consistent"),
     "op": "true",
     "why": "distributed tracing must not lose spans (PR 9)"},
    {"name": "trace_overhead_pct", "metric": "fleet_trace_span_accounting",
     "files": "TRACE_r*.json", "path": ("overhead", "regression_pct"),
     "op": "max", "baseline": 0.0, "abs_tol": 5.0, "unit": "%",
     "why": "sampled tracing QPS cost stays under 5%"},
    {"name": "elastic_digest_equal", "metric": "__elastic__",
     "files": "ELASTIC_r*.json", "path": ("final_digest_equal",),
     "op": "true",
     "why": "kill -9 recovery restores bit-identical state (PR 6)"},
    {"name": "elastic_restore_ms", "metric": "__elastic__",
     "files": "ELASTIC_r*.json", "path": ("median_restore_ms",),
     "op": "max", "baseline": 5.7, "abs_tol": 50.0, "unit": "ms",
     "why": "checkpoint restore must stay interactive-fast"},
    {"name": "goodput_accounting", "metric": "goodput_ledger",
     "files": "GOODPUT_r*.json",
     "path": ("report", "accounting", "closes"),
     "op": "true",
     "why": "goodput categories (+derived idle) must sum to elapsed "
            "wall-clock within FLAGS_goodput_tolerance (PR 11)"},
    {"name": "goodput_fraction", "metric": "goodput_ledger",
     "files": "GOODPUT_r*.json", "path": ("value",),
     "op": "min", "baseline": 0.08, "abs_tol": 0.06, "unit": "fraction",
     "why": "the instrumented toy run must show real productive step "
            "time (wide envelope: the compile-dominated harness "
            "fraction tracks host speed)"},
    {"name": "goodput_overhead_pct", "metric": "goodput_ledger",
     "files": "GOODPUT_r*.json",
     "path": ("overhead", "serving", "regression_pct"),
     "op": "max", "baseline": 0.0, "abs_tol": 5.0, "unit": "%",
     "why": "always-on step profiler + live SLO evaluation must not "
            "tax bench_serving throughput (<2% claim, 5% gate for "
            "shared-box noise, same envelope as trace_overhead_pct)"},
    {"name": "xstats_overhead_pct", "metric": "xstats_overhead",
     "files": "XSTATS_r*.json",
     "path": ("overhead", "serving", "regression_pct"),
     "op": "max", "baseline": 0.0, "abs_tol": 5.0, "unit": "%",
     "why": "executable-registry registration + armed anomaly capture "
            "must not tax serving (PR 13; paired-trial trimmed mean, "
            "same envelope as the other observability overhead gates)"},
    {"name": "xstats_capture_loadable", "metric": "xstats_overhead",
     "files": "XSTATS_r*.json", "path": ("capture", "loadable"),
     "op": "true",
     "why": "a /profilez capture must produce an artifact "
            "load_profiler_result can read back (PR 13)"},
    {"name": "chaos_zero_lost", "metric": "fleet_chaos_resilience",
     "files": "CHAOS_r*.json",
     "path": ("invariants", "zero_non_riding_lost"),
     "op": "true",
     "why": "under crash/hang/slow/shed/deadline fault injection, "
            "only requests riding the failed dispatch may fail — "
            "everything else re-routes (PR 15)"},
    {"name": "chaos_recovery_bound", "metric": "fleet_chaos_resilience",
     "files": "CHAOS_r*.json",
     "path": ("watchdog", "recovered_within_bound"),
     "op": "true",
     "why": "a wedged device must be detected, drained and respawned "
            "within 2x FLAGS_fleet_wedge_timeout_ms — a silent hang "
            "is a bounded failure, not an outage (PR 15)"},
    {"name": "chaos_breaker_cycle", "metric": "fleet_chaos_resilience",
     "files": "CHAOS_r*.json", "path": ("breaker", "cycle_observed"),
     "op": "true",
     "why": "a slow-but-alive replica (readyz GREEN) must trip its "
            "circuit breaker open and be re-admitted through a "
            "half-open probe after recovery (PR 15)"},
    {"name": "chaos_hedge_p99", "metric": "fleet_chaos_resilience",
     "files": "CHAOS_r*.json", "path": ("hedge", "p99_improved"),
     "op": "true",
     "why": "hedged submit under an induced slow replica must beat "
            "un-hedged p99 (r01: 124 ms -> 30 ms) (PR 15)"},
    {"name": "chaos_hedge_accounting",
     "metric": "fleet_chaos_resilience",
     "files": "CHAOS_r*.json", "path": ("hedge", "accounting_closes"),
     "op": "true",
     "why": "duplicate-execution accounting must close: hedges won "
            "and wasted are both bounded by hedges fired (PR 15)"},
    {"name": "numerics_overhead_pct", "metric": "numerics_overhead",
     "files": "NUMERICS_r*.json",
     "path": ("overhead", "serving", "regression_pct"),
     "op": "max", "baseline": 0.0, "abs_tol": 3.0, "unit": "%",
     "why": "sampled NaN/Inf tripwires + shadow-verification at "
            "production duty cycle (2% / 0.5%) must not tax the "
            "decode hot path (PR 18; paired-trial trimmed mean, "
            "r01: 0.88%)"},
    {"name": "numerics_drill_detects", "metric": "numerics_overhead",
     "files": "NUMERICS_r*.json", "path": ("drill", "nan_detected"),
     "op": "true",
     "why": "a forced-NaN step must fire exactly one nonfinite "
            "anomaly with a promoted trace id while a healthy step "
            "fires none (PR 18)"},
    {"name": "numerics_drill_capture", "metric": "numerics_overhead",
     "files": "NUMERICS_r*.json", "path": ("drill", "anomaly_capture"),
     "op": "true",
     "why": "the anomaly must trigger exactly one rate-limited "
            "/profilez capture carrying the anomaly's trace id "
            "(PR 18)"},
    {"name": "numerics_canary_golden", "metric": "numerics_overhead",
     "files": "NUMERICS_r*.json", "path": ("canary", "golden_match"),
     "op": "true",
     "why": "the deterministic device canary checksum must match its "
            "numpy golden twin bit-exactly — a mismatch IS silent "
            "data corruption (PR 18)"},
    {"name": "chaos_sdc_nan_detected",
     "metric": "fleet_chaos_resilience",
     "files": "CHAOS_r*.json", "path": ("numerics", "nan_detected"),
     "op": "true",
     "why": "an injected NaN-producing replica must be caught by its "
            "canary, quarantined (readyz 503 + breaker forced open) "
            "and readmitted after restore (PR 18)"},
    {"name": "chaos_sdc_bitflip_detected",
     "metric": "fleet_chaos_resilience",
     "files": "CHAOS_r*.json",
     "path": ("numerics", "bitflip_detected"),
     "op": "true",
     "why": "a single flipped mantissa bit — silent to sums — must "
            "still be caught by the bit-exact canary round-trip and "
            "quarantine the replica (PR 18)"},
    {"name": "chaos_sdc_zero_lost", "metric": "fleet_chaos_resilience",
     "files": "CHAOS_r*.json", "path": ("numerics", "zero_lost"),
     "op": "true",
     "why": "quarantining a corrupt replica must not fail foreground "
            "traffic — the router re-routes around it (PR 18)"},
    {"name": "chaos_goodput", "metric": "fleet_chaos_resilience",
     "files": "CHAOS_r*.json", "path": ("value",),
     "op": "min", "baseline": 0.90, "rel_tol": 0.0,
     "unit": "fraction",
     "why": "background-load goodput across the whole chaos run "
            "(r01: 0.9995 — riding failures are the only loss)"},
    {"name": "sched_realtime_slo", "metric": "sched_control_loop",
     "files": "SCHED_r*.json", "path": ("value",),
     "op": "min", "baseline": 0.95, "rel_tol": 0.0,
     "unit": "fraction",
     "why": "realtime SLO attainment while the batch tenant floods — "
            "the noisy-neighbor claim: per-tenant token buckets shed "
            "the flood with the typed QuotaExceededError before it "
            "can queue ahead of realtime work (PR 16, r01: 1.0)"},
    {"name": "sched_fairness_floor", "metric": "sched_control_loop",
     "files": "SCHED_r*.json", "path": ("fairness", "jain_weighted"),
     "op": "min", "baseline": 0.80, "rel_tol": 0.0, "unit": "index",
     "why": "weighted Jain fairness index over per-tenant "
            "goodput/weight under tenant skew — admission must hold "
            "configured shares when one tenant floods "
            "(PR 16, r01: 0.985)"},
    {"name": "sched_scale_reaction", "metric": "sched_control_loop",
     "files": "SCHED_r*.json", "path": ("autoscale", "reaction_s"),
     "op": "max", "baseline": 15.0, "abs_tol": 0.0, "unit": "s",
     "why": "fleet-wide brownout -> fast-burn page -> scale_to "
            "decision within the reaction bound; the alert-sink path "
            "is the whole point of the autoscaler (PR 16, r01: 1.3s)"},
    {"name": "sched_scale_in_hysteresis",
     "metric": "sched_control_loop",
     "files": "SCHED_r*.json", "path": ("autoscale", "scaled_in"),
     "op": "true",
     "why": "after restore + sustained quiet the fleet must scale "
            "back in (cooldown + quiet-window hysteresis, never below "
            "min_replicas) — scale-out alone is just a leak (PR 16)"},
    {"name": "sched_page_leak_clean", "metric": "sched_control_loop",
     "files": "SCHED_r*.json",
     "path": ("invariants", "page_leak_clean"),
     "op": "true",
     "why": "priority preemption under KV pressure must return every "
            "page: parked stream resumes, kv.leak_check() stays "
            "clean (PR 16)"},
    {"name": "sched_zero_lost", "metric": "sched_control_loop",
     "files": "SCHED_r*.json", "path": ("invariants", "zero_lost"),
     "op": "true",
     "why": "across every loadgen scenario (ramp, skew, flash crowd, "
            "trickle, brownout) failures are typed sheds or typed "
            "deadline/quota errors — nothing is silently lost "
            "(PR 16)"},
    {"name": "lockdep_overhead_pct", "metric": "lockdep_overhead",
     "files": "LOCKDEP_r*.json",
     "path": ("overhead", "serving", "regression_pct"),
     "op": "max", "baseline": 0.0, "abs_tol": 5.0, "unit": "%",
     "why": "the runtime lockdep sanitizer (instrumented Lock/RLock/"
            "Condition, per-thread acquisition stacks, observed "
            "order graph) must tax the lock-heavy dynamic-batched "
            "serving path <= 5% (PR 19; paired-trial trimmed mean)"},
    {"name": "lockdep_drill_detects", "metric": "lockdep_overhead",
     "files": "LOCKDEP_r*.json",
     "path": ("drill", "inversion_detected"), "op": "true",
     "why": "an injected two-thread AB/BA lock-order inversion must "
            "be reported the first time it is OBSERVED, without "
            "deadlocking the drill (PR 19)"},
    {"name": "lockdep_static_ld_clean", "metric": "lockdep_overhead",
     "files": "LOCKDEP_r*.json", "path": ("pdlint", "ld_clean"),
     "op": "true",
     "why": "the static lock-order analyzer (LD001 inversion cycles, "
            "LD002 blocking under a lock, LD003 naked Condition."
            "wait) must be repo-clean with zero baseline entries — "
            "genuine findings get fixed, not baselined (PR 19)"},
]


# -------------------------------------------- do-not-retry annotations
# PERF.md "Recorded sweeps that did NOT win", machine-readable: an
# automation loop consults do_not_retry_for() before re-running a
# sweep; each entry records what was measured so the negative result
# is citable without re-paying for it.
DO_NOT_RETRY: List[Dict[str, str]] = [
    {"config": "gpt3_1p3b", "sweep": "flash-block sizes around 512x1024",
     "result": "256x1024 -> 10664, 512x512 -> 10813, 1024x1024 -> 10822 "
               "tok/s; all within ±2% noise of 10805",
     "verdict": "defaults kept", "source": "PERF.md round 3"},
    {"config": "gpt3_1p3b", "sweep": "batch=4 at s=2048",
     "result": "OOM", "verdict": "b=2 is the single-chip ceiling with "
     "f32 master params + bf16 moments + full remat",
     "source": "PERF.md round 3"},
    {"config": "gpt3_1p3b", "sweep": "recompute=dots / recompute=none",
     "result": "never compiled: the compile helper of that round's "
               "host crashed (HTTP 500, reproducible); untried on "
               "today's machine", "verdict": "full remat is the only "
     "1.3B policy that has compiled", "source": "PERF.md round 3"},
    {"config": "gpt3_1p3b", "sweep": "recompute=attn (save attention "
     "outputs only)", "result": "10381 tok/s, WORSE than full remat",
     "verdict": "save boundary costs more in lost fusion than the "
     "recompute saves; policy stays available for memory-shaped "
     "configs", "source": "PERF.md round 3"},
    {"config": "ernie10b_aot", "sweep": "latency-hiding scheduler off",
     "result": "UNIMPLEMENTED on the v5e-64 topology (async "
               "collective-permute routing limitation)",
     "verdict": "keep LHS on", "source": "PERF.md round 3"},
    {"config": "gpt2_medium", "sweep": "batch 24/32",
     "result": "OOM or slower", "verdict": "b=16 kept",
     "source": "PERF.md round 2"},
    {"config": "gpt2_774m+", "sweep": "recompute=dots",
     "result": "OOM or slower", "verdict": "full remat at 774M+",
     "source": "PERF.md round 2"},
    {"config": "gpt2_medium", "sweep": "bf16 optimizer moments",
     "result": "no speed win", "verdict": "kept only for memory-bound "
     "configs", "source": "PERF.md round 2"},
    {"config": "*", "sweep": "logsumexp cross-entropy rewrite",
     "result": "no win", "verdict": "dropped", "source": "PERF.md round 2"},
    {"config": "*", "sweep": "one-hot embedding backward",
     "result": "no win", "verdict": "dropped", "source": "PERF.md round 2"},
]


def do_not_retry_for(config: str, sweep: Optional[str] = None
                     ) -> List[Dict[str, str]]:
    """Annotations matching a config (and optionally a sweep
    substring) — consult before re-running a recorded sweep."""
    out = []
    for e in DO_NOT_RETRY:
        if e["config"] not in ("*", config):
            continue
        if sweep and sweep.lower() not in e["sweep"].lower():
            continue
        out.append(dict(e))
    return out


# ------------------------------------------------------------ records
_ROUND = re.compile(r"_r(\d+)\.json$")


def _round_of(path: str) -> int:
    m = _ROUND.search(os.path.basename(path))
    return int(m.group(1)) if m else 0


def normalize_record(path: str, doc: dict) -> dict:
    """One record, classified: ``{"file", "round", "record",
    "status"}`` with status "measured" | "skipped" | "crashed".
    Wrapper-style files (a driver's record of a run: ``cmd``, ``rc``,
    ``tail``) carry the measurement under "parsed"
    with the driver rc alongside."""
    rec = doc.get("parsed", doc)
    rc = doc.get("rc")
    if rec is None or (rc is not None and rc != 0 and "parsed" not in doc):
        status = "crashed"
        rec = {}
    elif rec.get("skipped"):
        status = "skipped"
    elif rc is not None and rc != 0:
        status = "crashed"
    else:
        status = "measured"
    return {"file": os.path.basename(path), "round": _round_of(path),
            "record": rec, "status": status}


def load_records(root: str, pattern: str) -> List[dict]:
    """All records matching the glob, newest round first."""
    out = []
    for path in glob.glob(os.path.join(root, pattern)):
        try:
            with open(path, encoding="utf-8") as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            out.append({"file": os.path.basename(path),
                        "round": _round_of(path),
                        "record": {}, "status": "crashed",
                        "error": str(e)})
            continue
        out.append(normalize_record(path, doc))
    return sorted(out, key=lambda r: -r["round"])


def _dig(rec: dict, path) -> Any:
    cur = rec
    for k in path:
        if not isinstance(cur, dict) or k not in cur:
            return None
        cur = cur[k]
    return cur


def evaluate_gate(gate: dict, records: List[dict]) -> dict:
    """One gate against its record series: the newest MEASURED record
    matching the gate's metric carries the value; newer skipped/crashed
    rounds are reported as staleness diagnostics."""
    matching = [r for r in records
                if gate["metric"] == "__elastic__"
                or r["record"].get("metric") == gate["metric"]
                or r["status"] != "measured"]
    measured = [r for r in matching if r["status"] == "measured"
                and (gate["metric"] == "__elastic__"
                     or r["record"].get("metric") == gate["metric"])]
    res = {"gate": gate["name"], "metric": gate["metric"],
           "why": gate["why"], "stale_rounds":
               [f"{r['file']}:{r['status']}" for r in matching
                if r["status"] != "measured"
                and r["round"] > (measured[0]["round"] if measured
                                  else -1)]}
    if not measured:
        res.update(status="skip", reason="no measured record committed")
        return res
    rec = measured[0]
    value = _dig(rec["record"], gate["path"])
    res["file"] = rec["file"]
    res["value"] = value
    if value is None:
        res.update(status="skip",
                   reason=f"field {'.'.join(gate['path'])} absent")
        return res
    op = gate["op"]
    if op == "true":
        ok = bool(value)
        res.update(status="pass" if ok else "fail",
                   reason=None if ok else "invariant is false")
        return res
    base = float(gate["baseline"])
    if "abs_tol" in gate:
        lo, hi = base - gate["abs_tol"], base + gate["abs_tol"]
    else:
        tol = float(gate.get("rel_tol", 0.1))
        lo, hi = base * (1 - tol), base * (1 + tol)
    value = float(value)
    if op == "min":
        ok = value >= lo
        res["threshold"] = lo
    else:
        ok = value <= hi
        res["threshold"] = hi
    res.update(status="pass" if ok else "fail",
               reason=None if ok else
               f"{value} {gate.get('unit', '')} vs baseline {base} "
               f"(threshold {res['threshold']:.4g}, op {op})")
    return res


def run(records_dir: str, gates: Optional[List[dict]] = None) -> dict:
    gates = gates if gates is not None else GATES
    results = []
    for gate in gates:
        records = load_records(records_dir, gate["files"])
        results.append(evaluate_gate(gate, records))
    counts = {"pass": 0, "fail": 0, "skip": 0}
    for r in results:
        counts[r["status"]] += 1
    return {"version": 1, "records_dir": records_dir,
            "results": results, "counts": counts,
            "do_not_retry": DO_NOT_RETRY}


# ----------------------------------------------------------------- cli
def build_parser():
    p = argparse.ArgumentParser(
        prog="perfci", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("--records", default=REPO_ROOT,
                   help="directory holding the committed *_r*.json "
                        "records (default: repo root)")
    p.add_argument("--json", action="store_true", dest="as_json")
    p.add_argument("--do-not-retry", action="store_true",
                   dest="dump_dnr",
                   help="print the machine-readable do-not-retry sweep "
                        "annotations and exit")
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    if args.dump_dnr:
        print(json.dumps(DO_NOT_RETRY, indent=1, sort_keys=True))
        return 0
    if not os.path.isdir(args.records):
        print(f"perfci: no such record dir: {args.records}",
              file=sys.stderr)
        return 2
    report = run(args.records)
    if args.as_json:
        print(json.dumps(report, indent=1, sort_keys=True))
        return 1 if report["counts"]["fail"] else 0
    for r in report["results"]:
        line = f"perfci[{r['gate']}]: {r['status'].upper()}"
        if "value" in r and r.get("value") is not None:
            line += f" value={r['value']}"
        if r.get("file"):
            line += f" ({r['file']})"
        if r.get("reason"):
            line += f" — {r['reason']}"
        if r.get("stale_rounds"):
            line += f" [stale: {', '.join(r['stale_rounds'])}]"
        print(line)
    c = report["counts"]
    print(f"perfci: {c['pass']} pass, {c['skip']} skip, "
          f"{c['fail']} fail over {len(report['results'])} gate(s)")
    return 1 if c["fail"] else 0


if __name__ == "__main__":
    sys.exit(main())
