"""tools/perfci.py — the committed-record perf regression gate.

Acceptance: exit zero on the committed records, non-zero on an
injected regressed bench record; a skipped or crashed wrapper must be
"no measurement", never "measured zero"; the PERF.md do-not-retry
sweeps are machine-readable.

The wrapper-style rounds (a driver's ``{"n", "cmd", "rc", "tail",
"parsed"}`` around one bench run) are built here in ``tmp_path``: the
repo commits none, and the gate they are judged by is this file's own.
"""
import json
import os
import shutil
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

from tools import perfci  # noqa: E402


def _committed(name):
    with open(os.path.join(REPO_ROOT, name), encoding="utf-8") as f:
        return json.load(f)


TRAIN_GATE = {"name": "train_tok_s", "metric": "train_tokens_per_sec",
              "files": "ROUND_r*.json", "path": ("value",),
              "op": "min", "baseline": 10000.0, "rel_tol": 0.05,
              "unit": "tokens/s", "why": "wrapper-style rounds"}


def _measured_round(n, value=10100.0):
    return {"n": n, "cmd": "python bench.py", "rc": 0, "tail": "",
            "parsed": {"metric": "train_tokens_per_sec", "value": value,
                       "unit": "tokens/s"}}


def _crashed_round(n):
    return {"n": n, "cmd": "python bench.py", "rc": 1,
            "tail": "Traceback (most recent call last): ..."}


def _skipped_round(n):
    return {"n": n, "cmd": "python bench.py", "rc": 0, "tail": "",
            "parsed": {"metric": "no_measurement", "skipped": True,
                       "value": 0.0, "unit": "diagnostic",
                       "error": "no run was made"}}


def _write_rounds(root, rounds):
    for doc in rounds:
        with open(os.path.join(str(root), f"ROUND_r{doc['n']:02d}.json"),
                  "w") as f:
            json.dump(doc, f)
    return str(root)


def _train_gate_result(root):
    report = perfci.run(root, gates=[TRAIN_GATE])
    return report["results"][0]


class TestCommittedRecords:
    def test_committed_records_pass(self):
        report = perfci.run(REPO_ROOT)
        fails = [r for r in report["results"] if r["status"] == "fail"]
        assert fails == [], fails

    def test_cli_exits_zero_on_committed(self, capsys):
        assert perfci.main(["--records", REPO_ROOT]) == 0

    def test_train_gate_uses_latest_measured_round(self, tmp_path):
        """r04 crashed and r05 was skipped — the gate must fall back
        to r03's measurement and report the newer rounds as stale, not
        fail on them."""
        root = _write_rounds(tmp_path, [
            _measured_round(3), _crashed_round(4), _skipped_round(5)])
        gate = _train_gate_result(root)
        assert gate["status"] == "pass"
        assert gate["file"] == "ROUND_r03.json"
        assert any("ROUND_r05.json" in s for s in gate["stale_rounds"])
        assert any("ROUND_r04.json" in s for s in gate["stale_rounds"])

    def test_coldstart_ratio_gate_present(self):
        report = perfci.run(REPO_ROOT)
        gate = next(r for r in report["results"]
                    if r["gate"] == "fleet_coldstart_ratio")
        assert gate["status"] == "pass"
        assert gate["value"] >= 2.5


class TestInjectedRegression:
    def _dir_with(self, tmp_path, fname, doc):
        for name in ("BENCH_DECODE_r01.json", "BENCH_FLEET_r01.json",
                     "TRACE_r01.json", "ELASTIC_r01.json"):
            shutil.copy(os.path.join(REPO_ROOT, name),
                        str(tmp_path / name))
        with open(str(tmp_path / fname), "w") as f:
            json.dump(doc, f)
        return str(tmp_path)

    def test_regressed_train_record_fails(self, tmp_path):
        """A newer measured round with a regressed tok/s must flip the
        gate to fail."""
        root = _write_rounds(tmp_path, [
            _measured_round(3),
            _measured_round(6, value=6000.0)])  # way under 10000*(1-5%)
        gate = _train_gate_result(root)
        assert gate["status"] == "fail"
        assert gate["file"] == "ROUND_r06.json"
        assert perfci.run(root, gates=[TRAIN_GATE])["counts"]["fail"] == 1

    def test_regressed_p99_fails(self, tmp_path):
        doc = _committed("BENCH_DECODE_r01.json")
        doc["engine_p99_inter_token_ms"] = 50.0
        root = self._dir_with(tmp_path, "BENCH_DECODE_r02.json", doc)
        assert perfci.main(["--records", root]) == 1

    def test_broken_invariant_fails(self, tmp_path):
        doc = _committed("TRACE_r01.json")
        doc["accounting"]["accounting_consistent"] = False
        root = self._dir_with(tmp_path, "TRACE_r02.json", doc)
        report = perfci.run(root)
        gate = next(r for r in report["results"]
                    if r["gate"] == "trace_accounting")
        assert gate["status"] == "fail"

    def test_newer_skip_does_not_mask_regression_nor_fail(self, tmp_path):
        """A skipped round NEWER than a regressed measurement must not
        rescue the gate (latest MEASURED wins)."""
        root = _write_rounds(tmp_path, [
            _measured_round(3), _measured_round(6, value=6000.0),
            _skipped_round(7)])
        gate = _train_gate_result(root)
        assert gate["status"] == "fail"
        assert gate["file"] == "ROUND_r06.json"
        assert any("ROUND_r07.json" in s for s in gate["stale_rounds"])


class TestClassification:
    def test_skipped_round_is_not_measured(self):
        rec = perfci.normalize_record("ROUND_r05.json", _skipped_round(5))
        assert rec["status"] == "skipped"

    def test_crashed_wrapper_is_not_measured(self):
        rec = perfci.normalize_record("ROUND_r04.json", _crashed_round(4))
        assert rec["status"] == "crashed"

    def test_measured_record(self):
        rec = perfci.normalize_record("ROUND_r03.json",
                                      _measured_round(3, value=10827.0))
        assert rec["status"] == "measured"
        assert rec["record"]["value"] == 10827.0

    def test_missing_record_is_skip_not_fail(self, tmp_path):
        report = perfci.run(str(tmp_path))     # empty dir
        assert report["counts"]["fail"] == 0
        assert report["counts"]["skip"] == len(perfci.GATES)
        assert perfci.main(["--records", str(tmp_path)]) == 0

    def test_corrupt_json_classified_crashed(self, tmp_path):
        (tmp_path / "BENCH_r09.json").write_text("{nope")
        recs = perfci.load_records(str(tmp_path), "BENCH_r*.json")
        assert recs[0]["status"] == "crashed"


class TestDoNotRetry:
    def test_annotations_are_machine_readable(self):
        for e in perfci.DO_NOT_RETRY:
            assert set(e) >= {"config", "sweep", "result", "verdict",
                              "source"}

    def test_lookup_by_config_and_sweep(self):
        hits = perfci.do_not_retry_for("gpt3_1p3b", "recompute")
        assert len(hits) >= 2            # dots/none and attn entries
        hits = perfci.do_not_retry_for("gpt3_1p3b", "batch=4")
        assert hits and "OOM" in hits[0]["result"]
        # wildcard entries apply to every config
        assert perfci.do_not_retry_for("anything", "logsumexp")

    def test_cli_dump(self, capsys):
        assert perfci.main(["--do-not-retry"]) == 0
        doc = json.loads(capsys.readouterr().out)
        assert isinstance(doc, list) and len(doc) >= 8

    def test_json_report_carries_annotations(self, capsys):
        assert perfci.main(["--records", REPO_ROOT, "--json"]) == 0
        doc = json.loads(capsys.readouterr().out)
        assert doc["do_not_retry"] == perfci.DO_NOT_RETRY
        assert doc["counts"]["fail"] == 0


def test_usage_error_exit_2(tmp_path):
    assert perfci.main(["--records", str(tmp_path / "missing")]) == 2
