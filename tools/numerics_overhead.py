"""numerics_overhead — the PR 18 acceptance gate: NaN/Inf tripwires
plus sampled shadow-verification must not tax serving.

Paired-trial measurement in the ``xstats_overhead.py`` style: a
CachedDecoder decode loop (the serving hot path the tripwires ride)
with numerics OFF vs ON at the PRODUCTION duty cycle —
``FLAGS_numerics_sample_rate`` tripwires plus the shadow-verification
oracle, sampled at 2% and 0.5%. (``FLAGS_check_nan_inf`` — the reference
debugger contract — arms every dispatch instead and is priced
separately as an informational number, not gated: full-rate health
reductions on a tiny CPU model cost far more than 3% by design.)
Trials interleave so box drift cancels; the committed record
(``NUMERICS_r01.json``) is gated by ``tools/perfci.py``: sampled-
regime regression must stay ≤3%.

The record also carries an injected-corruption DETECTION DRILL — the
gate that the observability actually observes: a forced-NaN step must
fire exactly one anomaly (promoted error span + trace id + rate-
limited /profilez capture), a healthy step must fire none, and the
device canary must match its host golden twin.

Usage:

    python tools/numerics_overhead.py --record NUMERICS_r01.json
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import tempfile
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

# Production duty cycles for the bench. The two probes price very
# differently: a tripwire step adds one fused on-device reduction
# (cheap), a shadow step pays a full oracle re-execution plus a
# divergence reduction (~2-3x a normal step) — so the shadow duty is
# 4x lower to keep the combined serving tax inside the 3% budget.
TRIPWIRE_RATE = 0.02
SHADOW_RATE = 0.005


def _build_decoder():
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForCausalLM, gpt_tiny
    from paddle_tpu.serving.generation.model_fns import CachedDecoder

    paddle.seed(0)
    cfg = gpt_tiny(use_flash_attention=False)
    m = GPTForCausalLM(cfg)
    m.eval()
    b, prompt, ps, pps = 4, 8, 4, 8
    dec = CachedDecoder(m, max_batch=b, page_size=ps,
                        pages_per_seq=pps, donate=False)
    k, v = m.init_kv_pools(1 + b * pps, ps)
    tables = (1 + np.arange(b * pps, dtype=np.int32)
              .reshape(b, pps))
    ids = np.random.RandomState(0).randint(
        0, cfg.vocab_size, (b, prompt)).astype("int64")
    _, last, k, v, _ = dec.prefill(
        ids, np.full(b, prompt, np.int32), tables, None, None, k, v)
    cur = np.asarray(last).argmax(-1)
    capacity = ps * pps
    return {"dec": dec, "k": k, "v": v, "tables": tables,
            "cur": cur, "b": b, "prompt": prompt,
            "capacity": capacity}


def _decode_loop(st, steps: int) -> float:
    """Greedy decode ``steps`` positions (cycling inside the page
    budget so shapes never change); returns steps/s."""
    import numpy as np
    b, prompt, cap = st["b"], st["prompt"], st["capacity"]
    dec, tables = st["dec"], st["tables"]
    k, v, cur = st["k"], st["v"], st["cur"]
    t0 = time.perf_counter()
    for i in range(steps):
        pos = prompt + (i % (cap - prompt - 1))
        _, logits, k, v, _ = dec.decode(
            cur, np.full(b, pos, np.int32), np.ones(b, bool),
            np.full(b, pos + 1, np.int32), tables, None, None, k, v)
        cur = np.asarray(logits).argmax(-1)
    dt = time.perf_counter() - t0
    st["k"], st["v"], st["cur"] = k, v, cur
    return steps / dt


def _bench_overhead(steps: int = 800, trials: int = 9) -> dict:
    from paddle_tpu.framework.flags import set_flags
    from paddle_tpu.observability import numerics

    st = _build_decoder()
    off, on = [], []

    def _arm(enabled):
        set_flags({
            "FLAGS_numerics_sample_rate":
                TRIPWIRE_RATE if enabled else 0.0,
            "FLAGS_numerics_shadow_rate":
                SHADOW_RATE if enabled else 0.0,
        })

    try:
        # warm both regimes (real jit, oracle jit, stats jit) before
        # any timed trial
        _arm(False)
        _decode_loop(st, 8)
        _arm(True)
        numerics.set_rng_for_tests(None)
        _decode_loop(st, max(8, int(2 / SHADOW_RATE)))
        numerics.drain()

        def run_off(trial):
            _arm(False)
            off.append(_decode_loop(st, steps))

        def run_on(trial):
            _arm(True)
            on.append(_decode_loop(st, steps))
            numerics.drain()

        for trial in range(trials):
            # alternate order so warmth credits neither regime
            first, second = (run_off, run_on) if trial % 2 == 0 \
                else (run_on, run_off)
            first(trial)
            second(trial)

        # informational only: FLAGS_check_nan_inf arms EVERY dispatch
        # (the reference debugger contract) — price it so the record
        # shows what full-rate costs, but don't gate it
        set_flags({"FLAGS_check_nan_inf": True,
                   "FLAGS_numerics_shadow_rate": 0.0})
        _decode_loop(st, 8)
        full = _decode_loop(st, steps)
        numerics.drain()
        set_flags({"FLAGS_check_nan_inf": False})
        _arm(False)
        base = _decode_loop(st, steps)
        full_pct = (base - full) / base * 100
    finally:
        set_flags({"FLAGS_numerics_sample_rate": 0.0,
                   "FLAGS_numerics_shadow_rate": 0.0})
    per_pair = sorted((b - i) / b * 100 for b, i in zip(off, on))
    trimmed = per_pair[1:-1] if len(per_pair) > 2 else per_pair
    payload = numerics.numericsz_payload()
    return {"steps": steps, "trials": trials,
            "tripwire_rate": TRIPWIRE_RATE,
            "shadow_rate": SHADOW_RATE,
            "off_steps_per_s": round(statistics.median(off), 1),
            "on_steps_per_s": round(statistics.median(on), 1),
            "per_pair_pct": [round(p, 2) for p in per_pair],
            "regression_pct": round(statistics.mean(trimmed), 2),
            "full_rate_regression_pct_info": round(full_pct, 2),
            "checks_noted": payload["serving"]
            .get("decode", {}).get("checks", 0),
            "shadow_checks": sum(
                s["count"] for s in payload["shadow"].values()),
            "anomalies_during_bench":
                payload["anomalies"]["total"]}


def _detection_drill() -> dict:
    """The observability must observe: forced NaN -> exactly one
    anomaly with a promoted trace id and a loadable /profilez
    capture; healthy -> none."""
    import numpy as np

    from paddle_tpu.framework.flags import set_flags
    from paddle_tpu.observability import numerics, xstats

    with tempfile.TemporaryDirectory() as d:
        set_flags({"FLAGS_check_nan_inf": True,
                   "FLAGS_profile_on_anomaly": True,
                   "FLAGS_profile_min_interval_s": 0.0,
                   "FLAGS_profile_anomaly_ms": 20.0,
                   "FLAGS_profile_dir": d})
        try:
            numerics.reset_for_tests()
            # healthy logits: no anomaly
            numerics.note_serving_logits(
                "decode", np.ones((2, 16), np.float32))
            numerics.drain()
            healthy = numerics.numericsz_payload()
            healthy_clean = healthy["anomalies"]["total"] == 0

            # poisoned logits: exactly one anomaly, trace id promoted
            bad = np.ones((2, 16), np.float32)
            bad[0, 0] = np.nan
            numerics.note_serving_logits("decode", bad)
            numerics.drain()
            after = numerics.numericsz_payload()
            last = after["anomalies"]["last"] or {}
            trace_id = last.get("trace_id")
            nan_detected = (after["anomalies"]["total"] == 1
                            and last.get("reason") == "nonfinite"
                            and bool(trace_id))

            # the anomaly capture: one artifact, reason=anomaly,
            # carrying the promoted trace id
            xstats.wait_captures(30.0)
            arts = [a for a in xstats.profilez_payload()["artifacts"]
                    if a.get("reason") == "anomaly"]
            captured = any(a.get("trace_id") == trace_id
                           for a in arts)
            return {"healthy_clean": bool(healthy_clean),
                    "nan_detected": bool(nan_detected),
                    "anomaly_trace_id": trace_id,
                    "anomaly_capture": bool(captured),
                    "anomaly_captures_seen": len(arts),
                    "finite_fraction": after["serving"]
                    .get("decode", {}).get("finite_fraction")}
        finally:
            numerics.reset_for_tests()
            set_flags({"FLAGS_check_nan_inf": False,
                       "FLAGS_profile_on_anomaly": False,
                       "FLAGS_profile_min_interval_s": 30.0,
                       "FLAGS_profile_anomaly_ms": 500.0,
                       "FLAGS_profile_dir": ""})


def _canary_check() -> dict:
    from paddle_tpu.observability import numerics
    res = numerics.run_device_canary(record=False)
    return {"golden_match": bool(res["ok"]),
            "checksum": res["got"], "ms": round(res["ms"], 2)}


def run_record(steps: int, trials: int) -> dict:
    overhead = _bench_overhead(steps=steps, trials=trials)
    drill = _detection_drill()
    canary = _canary_check()
    return {
        "metric": "numerics_overhead",
        "skipped": False,
        "value": overhead["regression_pct"],
        "unit": "%",
        "overhead": {"serving": overhead},
        "drill": drill,
        "canary": canary,
        "config": {"steps": steps, "trials": trials,
                   "tripwire_rate": TRIPWIRE_RATE,
                   "shadow_rate": SHADOW_RATE},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="numerics_overhead",
                                 description=__doc__)
    ap.add_argument("--record", default=None, metavar="OUT",
                    help="write the committed-record JSON to OUT")
    ap.add_argument("--steps", type=int, default=800)
    ap.add_argument("--trials", type=int, default=9)
    args = ap.parse_args(argv)
    doc = run_record(args.steps, args.trials)
    text = json.dumps(doc, indent=1, sort_keys=True)
    if args.record:
        with open(args.record, "w", encoding="utf-8") as f:
            f.write(text + "\n")
        ov = doc["overhead"]["serving"]
        print(f"numerics_overhead: wrote {args.record} "
              f"(regression {ov['regression_pct']}%, "
              f"drill nan_detected={doc['drill']['nan_detected']}, "
              f"capture={doc['drill']['anomaly_capture']}, "
              f"canary={doc['canary']['golden_match']})")
    else:
        print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
