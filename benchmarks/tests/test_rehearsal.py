"""The CPU rehearsal: both runners end to end at ``gpt_tiny`` through
the command line, on configuration and traffic files that live under
these tests; and a cell, a configuration, a mix and a metric added as
files only, found by name."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmarks import common

DATA = os.path.join(common.HERE, "tests", "data")
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def bench(manifest, cell, *, trace, seconds=3, seed=3_000_000_019):
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    proc = subprocess.run(
        [sys.executable, os.path.join(common.HERE, "run.py"), "--rehearse",
         "--manifest", manifest, "--workload", cell, "--seed", str(seed),
         "--seconds", str(seconds), "--trace", str(trace)],
        capture_output=True, text=True, timeout=600, env=env,
        cwd=common.ROOT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def device_trace_metrics(manifest):
    return {m["name"] for m in common.load_json(manifest)["per_layer"]
            if m["source"] == "device_trace"}


@pytest.mark.parametrize("cell,trace,expect", [
    ("tiny-train", 0, {"train_tokens_per_s", "setup_s"}),
    ("tiny-train", 1, {"train_step_ms"}),
    ("tiny-closed", 1, {"engine_loop_ms.backlog", "batch_occupancy.backlog",
                        "decode_call_ms.backlog"}),
    ("tiny-closed", 0, {"serve_tokens_per_s", "setup_s"}),
    ("tiny-open", 0, {"token_gap_p99_ms", "setup_s"}),
    ("tiny-open", 1, {"loadgen_late_p99_ms", "ttft_p50_ms", "ttft_p90_ms",
                      "decode_call_ms.chat", "prefill_call_ms.chat"}),
])
def test_runner_end_to_end(cell, trace, expect):
    manifest = os.path.join(DATA, "BENCHMARK.json")
    line = bench(manifest, cell, trace=trace)
    assert KEYS <= set(line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert "busy_s" not in line["device"] and "breakdown" not in line
    # a CPU run prints no device metric, and no utilization of a peak
    assert set(line["metrics"]) == expect
    assert not set(line["metrics"]) & device_trace_metrics(manifest)
    for m in line["metrics"].values():
        assert m["value"] > 0 and m["unit"]


def test_no_tpu_is_a_failure_without_rehearse():
    proc = subprocess.run(
        [sys.executable, os.path.join(common.HERE, "run.py"), "--manifest",
         os.path.join(DATA, "BENCHMARK.json"), "--workload", "tiny-train",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, cwd=common.ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0
    assert not proc.stdout.strip()


def test_a_cell_added_by_files_alone(tmp_path):
    """A new configuration, a new mix, a new per-layer metric and the
    cell that joins them: four files and manifest entries, no edit to
    any file of the harness."""
    root = tmp_path / "added"
    for sub in ("configs", "traffic", "metrics"):
        (root / sub).mkdir(parents=True)
    config = common.load_json(os.path.join(DATA, "configs", "gpt-tiny.json"))
    config["name"] = "gpt-tiny-3l"
    config["model"]["kwargs"]["num_layers"] = 3
    config["sizes"]["num_layers"] = 3
    (root / "configs" / "gpt-tiny-3l.json").write_text(json.dumps(config))
    shutil.copy(os.path.join(DATA, "traffic", "tiny-train.json"),
                root / "traffic" / "tiny-train-b4.json")
    traffic = common.load_json(str(root / "traffic" / "tiny-train-b4.json"))
    traffic["batch"] = 4
    (root / "traffic" / "tiny-train-b4.json").write_text(json.dumps(traffic))
    (root / "metrics" / "window_steps.py").write_text(
        'LAYER = "step (jit/train_step.py)"\nUNIT = "steps"\n'
        'BETTER = "higher"\nSOURCE = "program_counter"\n'
        'MOVES = "train_tokens_per_s"\n\n\n'
        'def read(run):\n    return len(run["step_s"])\n')
    manifest = common.load_json(os.path.join(DATA, "BENCHMARK.json"))
    manifest["paths"] = [str(root)]
    manifest["configs"].append({
        "name": "gpt-tiny-3l", "source": "test", "reduced": [],
        "file": str(root / "configs" / "gpt-tiny-3l.json"), "why": "test"})
    manifest["workloads"].append({
        "name": "tiny3-train-b4", "config": "gpt-tiny-3l",
        "traffic": "tiny-train-b4", "chips": 1, "why": "added by files"})
    for m in manifest["end_to_end"]:
        if m["name"] == "train_tokens_per_s":
            m["workloads"].append("tiny3-train-b4")
    manifest["per_layer"].append({
        "name": "window_steps", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "step (jit/train_step.py)",
        "moves": "train_tokens_per_s", "workloads": ["tiny3-train-b4"]})
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(manifest))
    line = bench(str(path), "tiny3-train-b4", trace=1, seconds=2)
    assert line["correct"] is True
    assert set(line["metrics"]) == {"window_steps"}
    assert line["metrics"]["window_steps"]["value"] == line["attempted"]
    e2e = bench(str(path), "tiny3-train-b4", trace=0, seconds=2)
    assert set(e2e["metrics"]) == {"train_tokens_per_s", "setup_s"}
