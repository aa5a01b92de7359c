"""Numerics & silent-data-corruption observability (PR 18).

- sampling: injected-RNG determinism of the duty-cycle decisions
- tripwires: a forced-NaN logit batch fires exactly one nonfinite
  anomaly with a promoted trace id; a healthy batch fires none
- shadow verification: sampled decode steps re-execute through the
  pure-JAX oracle and publish divergence (exactly 0 on CPU, where the
  oracle IS the live path)
- int8 drift: quantized-pool scale summaries publish a baseline and
  drift-vs-baseline per kind
- canary: deterministic device checksum vs its numpy golden twin;
  CanaryRunner episodes fire on_corrupt exactly once
- fleet: a corrupt replica is quarantined through the real router
  (readyz 503 corrupt -> breaker forced open) and readmitted after
  restore; /numericsz merges fleet-wide
- records: NUMERICS_r01.json loads and its perfci gates hold
- pdlint: numerics.py is clean under the lock/metric discipline
  analyzers, and injected violations in numerics-shaped code flip
"""
import json
import os
import random
import textwrap
import urllib.error
import urllib.request

import numpy as np
import pytest

from paddle_tpu.framework.flags import flag_value, set_flags
from paddle_tpu.observability import numerics

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_FLAG_NAMES = (
    "FLAGS_check_nan_inf", "FLAGS_numerics_sample_rate",
    "FLAGS_numerics_shadow_rate", "FLAGS_numerics_canary_period_s",
    "FLAGS_profile_on_anomaly", "FLAGS_profile_min_interval_s",
    "FLAGS_profile_anomaly_ms", "FLAGS_profile_dir",
)

_OPENER = urllib.request.build_opener(urllib.request.ProxyHandler({}))


@pytest.fixture()
def fresh_numerics():
    """Fresh numerics state + restored flags and RNG per test."""
    saved = {n: flag_value(n) for n in _FLAG_NAMES}
    numerics.reset_for_tests()
    yield
    set_flags(saved)
    numerics.set_rng_for_tests(None)
    numerics.reset_for_tests()


def _get_json(url, timeout=10.0):
    with _OPENER.open(url, timeout=timeout) as r:
        return json.loads(r.read())


# ------------------------------------------------------------ sampling
class TestSampling:
    def test_injected_rng_makes_decisions_reproducible(
            self, fresh_numerics):
        numerics.set_rng_for_tests(random.Random(7))
        first = [numerics.sample_decision(0.5) for _ in range(32)]
        numerics.set_rng_for_tests(random.Random(7))
        assert [numerics.sample_decision(0.5)
                for _ in range(32)] == first
        assert any(first) and not all(first)

    def test_rate_edges_skip_the_rng(self, fresh_numerics):
        numerics.set_rng_for_tests(None)
        assert not numerics.sample_decision(0.0)
        assert numerics.sample_decision(1.0)

    def test_check_nan_inf_arms_every_step(self, fresh_numerics):
        set_flags({"FLAGS_check_nan_inf": True,
                   "FLAGS_numerics_sample_rate": 0.25})
        assert numerics.tripwire_rate() == 1.0
        set_flags({"FLAGS_check_nan_inf": False})
        assert numerics.tripwire_rate() == 0.25
        assert numerics.enabled()


# ----------------------------------------------------------- tripwires
class TestTripwires:
    def test_healthy_batch_fires_no_anomaly(self, fresh_numerics):
        numerics.note_serving_logits(
            "decode", np.ones((2, 16), np.float32))
        numerics.drain()
        doc = numerics.numericsz_payload()
        assert doc["anomalies"]["total"] == 0
        assert doc["serving"]["decode"]["finite_fraction"] == 1.0

    def test_nan_batch_fires_exactly_one_nonfinite(
            self, fresh_numerics):
        bad = np.ones((2, 16), np.float32)
        bad[0, 0] = np.nan
        numerics.note_serving_logits("decode", bad)
        numerics.drain()
        doc = numerics.numericsz_payload()
        assert doc["anomalies"]["total"] == 1
        last = doc["anomalies"]["last"]
        assert last["reason"] == "nonfinite" and last["trace_id"]
        assert doc["serving"]["decode"]["finite_fraction"] < 1.0

    def test_host_reads_are_deferred_one_note(self, fresh_numerics):
        """The newest entry stays pending (its device values may still
        be in flight); the previous note publishes on the next one.
        (``numericsz_payload`` drains, so peek at the raw state.)"""
        ones = np.ones((2, 8), np.float32)
        numerics.note_serving_logits("decode", ones)
        numerics.note_serving_logits("decode", ones)
        doc = numerics._state().payload()
        assert doc["pending"] == 1
        assert doc["serving"]["decode"]["checks"] == 1
        assert numerics.drain() == 1
        assert numerics._state().payload()["serving"]["decode"][
            "checks"] == 2


# --------------------------------------------- decoder shadow + int8
def _decoder(kv_dtype=None):
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForCausalLM, gpt_tiny
    from paddle_tpu.serving.generation.model_fns import CachedDecoder

    paddle.seed(0)
    cfg = gpt_tiny(use_flash_attention=False)
    m = GPTForCausalLM(cfg)
    m.eval()
    b, prompt, ps, pps = 2, 4, 4, 4
    dec = CachedDecoder(m, max_batch=b, page_size=ps,
                        pages_per_seq=pps, donate=False,
                        kv_dtype=kv_dtype)
    k, v = m.init_kv_pools(1 + b * pps, ps, dtype=kv_dtype)
    tables = (1 + np.arange(b * pps, dtype=np.int32)
              .reshape(b, pps))
    ids = np.random.RandomState(0).randint(
        0, cfg.vocab_size, (b, prompt)).astype("int64")
    _, last, k, v, _ = dec.prefill(
        ids, np.full(b, prompt, np.int32), tables, None, None, k, v)
    cur = np.asarray(last).argmax(-1)
    return dec, tables, k, v, cur, prompt


def _decode_steps(dec, tables, k, v, cur, prompt, n):
    b = tables.shape[0]
    for i in range(n):
        pos = prompt + i
        _, logits, k, v, _ = dec.decode(
            cur, np.full(b, pos, np.int32), np.ones(b, bool),
            np.full(b, pos + 1, np.int32), tables, None, None, k, v)
        cur = np.asarray(logits).argmax(-1)
    return k, v, cur


class TestShadowVerification:
    def test_sampled_decode_reexecutes_through_oracle(
            self, fresh_numerics):
        set_flags({"FLAGS_numerics_shadow_rate": 1.0})
        dec, tables, k, v, cur, prompt = _decoder()
        _decode_steps(dec, tables, k, v, cur, prompt, 3)
        numerics.drain()
        doc = numerics.numericsz_payload()
        sh = doc["shadow"]["decode/f32"]
        assert sh["count"] == 3
        # on CPU the oracle IS the live path — bit-identical
        assert sh["max"] == 0.0

    def test_zero_rate_never_shadows(self, fresh_numerics):
        set_flags({"FLAGS_numerics_shadow_rate": 0.0,
                   "FLAGS_numerics_sample_rate": 0.0})
        dec, tables, k, v, cur, prompt = _decoder()
        _decode_steps(dec, tables, k, v, cur, prompt, 3)
        numerics.drain()
        assert numerics.numericsz_payload()["shadow"] == {}

    def test_int8_scale_drift_tracks_baseline(self, fresh_numerics):
        set_flags({"FLAGS_numerics_sample_rate": 1.0})
        dec, tables, k, v, cur, prompt = _decoder(kv_dtype="int8")
        _decode_steps(dec, tables, k, v, cur, prompt, 3)
        numerics.drain()
        doc = numerics.numericsz_payload()
        ent = doc["int8"]["decode"]
        assert ent["baseline"] > 0.0 and ent["notes"] >= 2
        assert abs(ent["drift"]) < 0.5
        assert "decode/int8" not in doc["shadow"]  # shadow off here


# -------------------------------------------------------------- canary
class TestCanary:
    def test_device_checksum_matches_golden_twin(
            self, fresh_numerics):
        a = numerics.run_device_canary(record=False)
        b = numerics.run_device_canary(record=False)
        assert a["ok"] and b["ok"]
        assert a["got"] == b["got"] == numerics.canary_reference()

    def test_recorded_failure_promotes_one_anomaly_per_episode(
            self, fresh_numerics):
        fired = []
        flip = {"ok": True}
        runner = numerics.CanaryRunner(
            name="t", probe=lambda: dict(flip),
            on_corrupt=lambda: fired.append(1))
        runner.run_once()
        assert not runner.corrupt and fired == []
        flip["ok"] = False
        runner.run_once()
        runner.run_once()
        assert runner.corrupt and fired == [1]  # once per episode
        flip["ok"] = True
        runner.run_once()
        assert not runner.corrupt
        flip["ok"] = False
        runner.run_once()
        assert fired == [1, 1]  # new episode fires again
        numerics.drain()
        doc = numerics.numericsz_payload()
        assert doc["canary"]["failures"] >= 3
        assert doc["anomalies"]["by_reason"]["canary_failure"] == 2


# ------------------------------------------------------ fleet e2e
class TestFleetQuarantine:
    def test_corrupt_replica_quarantined_and_readmitted(
            self, fresh_numerics):
        from paddle_tpu.serving import fleet
        reps = []
        for _ in range(2):
            be = fleet.StubBackend(device_ms=1.0)
            app = fleet.ReplicaApp(be).start()
            be.warmup()
            fleet.arm_canary(be, app, period_s=0.05)
            reps.append((be, app))
        router = fleet.FleetRouter(
            {i: app.url for i, (_, app) in enumerate(reps)},
            name="t_numerics", health_interval_ms=50.0,
            breaker_open_ms=200.0)
        try:
            import time

            def _wait(pred, timeout=20.0):
                deadline = time.monotonic() + timeout
                while time.monotonic() < deadline:
                    if pred():
                        return True
                    time.sleep(0.05)
                return pred()

            assert _wait(lambda: len(router._routable()) == 2)

            # single-bit corruption: silent to sums, caught by the
            # bit-exact canary round-trip
            reps[0][0].chaos({"corrupt": "bitflip"})

            def _quarantined():
                s = {st["replica"]: st
                     for st in router.replica_states()}.get("0", {})
                return (not s.get("ready", True)
                        and s.get("breaker", {}).get("state")
                        == "open")
            assert _wait(_quarantined), "corrupt replica not fenced"

            # its own /numericsz shows the episode; healthy traffic
            # still routes on the survivor
            doc = _get_json(reps[0][1].url + "/numericsz")
            assert doc["canary"]["corrupt"]
            assert doc["canary"]["last"]["probe"]["ok"] is False
            out = router.submit([np.ones(4, np.float32)]).result(
                timeout=10)
            assert np.all(np.isfinite(np.asarray(out[0])))

            # the fleet-merged view names the corrupt replica
            merged = router.merged_numericsz()
            assert merged["fleet"]["corrupt_replicas"] == ["0"]
            assert merged["fleet"]["canary_failures_total"] >= 1

            reps[0][0].chaos({"restore": True})
            assert _wait(lambda: len(router._routable()) == 2), \
                "restored replica never readmitted"
        finally:
            router.shutdown()
            for _, app in reps:
                app.stop()


# ------------------------------------------------------------- records
class TestCommittedRecord:
    def test_numerics_record_loads_and_gates_hold(self):
        path = os.path.join(REPO_ROOT, "NUMERICS_r01.json")
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
        assert doc["metric"] == "numerics_overhead"
        assert doc["value"] <= 3.0
        assert doc["drill"]["nan_detected"]
        assert doc["drill"]["healthy_clean"]
        assert doc["drill"]["anomaly_capture"]
        assert doc["canary"]["golden_match"]

    def test_perfci_gates_cover_numerics(self):
        import sys
        sys.path.insert(0, REPO_ROOT)
        from tools import perfci
        report = perfci.run(REPO_ROOT)
        by_name = {g["gate"]: g for g in report["results"]}
        for name in ("numerics_overhead_pct", "numerics_drill_detects",
                     "numerics_drill_capture", "numerics_canary_golden",
                     "chaos_sdc_nan_detected",
                     "chaos_sdc_bitflip_detected",
                     "chaos_sdc_zero_lost"):
            assert by_name[name]["status"] == "pass", name


# ------------------------------------------------------------- pdlint
class TestAnalyzerScope:
    def test_numerics_module_is_clean(self):
        from paddle_tpu import analysis
        from paddle_tpu.analysis import (LockDisciplineAnalyzer,
                                         MetricDisciplineAnalyzer)
        obs = os.path.join(REPO_ROOT, "paddle_tpu", "observability")
        found = [f for f in analysis.run_analyzers(
            [obs], [LockDisciplineAnalyzer(dirs=()),
                    MetricDisciplineAnalyzer()], root=REPO_ROOT)
            if f.path.endswith("numerics.py")]
        assert found == [], "\n".join(f.format() for f in found)

    def test_injected_unguarded_pending_write_flips_lk001(
            self, tmp_path):
        """Self-test: the numerics ledger idiom (locked deque, drain
        swap) with its guard dropped must be flagged."""
        from paddle_tpu import analysis
        from paddle_tpu.analysis import LockDisciplineAnalyzer
        p = tmp_path / "bad_ledger.py"
        p.write_text(textwrap.dedent("""
            import threading

            class Ledger:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._pending = []

                def note(self, entry):
                    with self._lock:
                        self._pending = self._pending + [entry]

                def drain(self):
                    out = self._pending
                    self._pending = []      # LK001: unguarded swap
                    return out
        """))
        found = analysis.run_analyzers(
            [str(tmp_path)], [LockDisciplineAnalyzer(dirs=())],
            root=str(tmp_path))
        assert [(f.rule, f.symbol) for f in found] == \
            [("LK001", "Ledger._pending")]

    def test_injected_unsuffixed_counter_flips_md003(self, tmp_path):
        """Self-test: a numerics-shaped counter family missing its
        _total suffix must be flagged."""
        from paddle_tpu import analysis
        from paddle_tpu.analysis import MetricDisciplineAnalyzer
        p = tmp_path / "bad_metrics.py"
        p.write_text(textwrap.dedent("""
            def families(reg):
                return reg.counter(
                    "paddle_numerics_anomalies",
                    "anomaly ledger")    # MD003: counter sans _total
        """))
        found = analysis.run_analyzers(
            [str(tmp_path)], [MetricDisciplineAnalyzer()],
            root=str(tmp_path))
        assert [f.rule for f in found] == ["MD003"]
