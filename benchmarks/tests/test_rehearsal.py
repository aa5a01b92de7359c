"""The CPU rehearsal: both runners end to end at ``gpt_tiny`` through
the command line, on configuration and traffic files that live under
these tests; a cell, a configuration, a mix and a metric added as
files only, found by name; and a configuration of another architecture
(its class, its reference) added the same way."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmarks import common

DATA = os.path.join(common.HERE, "tests", "data")
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def bench_process(manifest, cell, *, trace, seconds=3, seed=3_000_000_019,
                  more=()):
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    return subprocess.run(
        [sys.executable, os.path.join(common.HERE, "run.py"), "--rehearse",
         "--manifest", manifest, "--workload", cell, "--seed", str(seed),
         "--seconds", str(seconds), "--trace", str(trace), *more],
        capture_output=True, text=True, timeout=600, env=env,
        cwd=common.ROOT)


def line_of(proc) -> dict:
    """The result line of a run that ended well."""
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    # the numbers compared come last, in the line and on standard error
    assert list(line)[-1] == "compared"
    assert proc.stderr.strip().splitlines()[-1].startswith("compared ")
    return line


def bench(manifest, cell, **kw):
    return line_of(bench_process(manifest, cell, **kw))


def notes_of(proc) -> dict:
    """The runner's notes, from the one JSON line of standard error."""
    return next(json.loads(text) for text in proc.stderr.splitlines()
                if text.startswith('{"checks"'))["notes"]


def variant(tmp_path, name, change):
    """A manifest of its own with the configuration ``name``, which is
    ``gpt-tiny.json`` after ``change(config)``, and the cells
    ``<name>-train`` and ``<name>-closed`` beside the rehearsal's."""
    config = common.load_json(os.path.join(DATA, "configs", "gpt-tiny.json"))
    config["name"] = name
    change(config)
    (tmp_path / "configs").mkdir(exist_ok=True)
    file = tmp_path / "configs" / f"{name}.json"
    file.write_text(json.dumps(config))
    manifest = common.load_json(os.path.join(DATA, "BENCHMARK.json"))
    manifest["paths"] = manifest["paths"] + [str(tmp_path)]
    manifest["configs"].append({"name": name, "source": "test",
                                "file": str(file), "reduced": [],
                                "why": "test"})
    for kind in ("train", "closed"):
        manifest["workloads"].append({
            "name": f"{name}-{kind}", "config": name,
            "traffic": f"tiny-{kind}", "chips": 1, "why": "test"})
        for m in manifest["end_to_end"] + manifest["per_layer"]:
            if f"tiny-{kind}" in m.get("workloads", ()):
                m["workloads"].append(f"{name}-{kind}")
    path = tmp_path / f"{name}.BENCHMARK.json"
    path.write_text(json.dumps(manifest))
    return str(path)


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
    assert "control_gap_over_std" not in line["compared"]   # --control only
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


# ------------------------------- a configuration of another architecture
def negated(config, reference="gpt_negated"):
    config["model"]["class"] = "benchmarks.tests.data.negated_gpt:NegatedGPT"
    config["reference"] = reference


@pytest.mark.parametrize("kind", ["train", "closed"])
def test_another_architecture_is_files_and_names(tmp_path, kind):
    """A class of its own and a reference of its own, both under
    ``tests/data`` and named by the configuration file: no edit to the
    harness, and ``correct``."""
    manifest = variant(tmp_path, "negated", negated)
    line = bench(manifest, f"negated-{kind}", trace=0)
    assert line["correct"] is True and line["failed"] == 0


@pytest.mark.parametrize("kind,number", [
    ("train", "first_loss_rel_gap"), ("closed", "parity_gap_over_std")])
def test_both_names_are_live(tmp_path, kind, number):
    """The same class judged by the plain ``gpt`` reference is not
    ``correct``: the runner builds what ``model.class`` names and
    compares with what ``reference`` names."""
    manifest = variant(tmp_path, "crossed",
                       lambda c: negated(c, reference="gpt"))
    line = bench(manifest, f"crossed-{kind}", trace=0)
    assert line["correct"] is False
    pair = line["compared"][number]
    assert pair["value"] > pair["limit"]


def test_a_token_altered_where_it_is_produced_is_not_correct(tmp_path):
    """The served path broken underneath a sound harness: a quarter of
    the positions yield another token than the model's, every stream
    is whole, nothing leaks, and ``parity`` alone says not correct."""
    def faulty(config):
        config["model"]["class"] = \
            "benchmarks.tests.data.negated_gpt:SometimesNegatedGPT"
    line = bench(variant(tmp_path, "altered", faulty), "altered-closed",
                 trace=0)
    assert line["correct"] is False
    failed = {name for name, pair in line["compared"].items()
              if pair["value"] > pair["limit"]}
    assert failed == {"parity_gap_over_std"}


def test_bfloat16_weights_are_served_and_judged(tmp_path):
    """``serve.weights_dtype`` bfloat16: the seeded model is cast by
    the program's own ``Layer.to``, the pool follows it (half the
    bytes), and the reference, given the same bf16 arrays, agrees."""
    pools = {}
    for dtype in ("float32", "bfloat16"):
        manifest = variant(
            tmp_path, dtype,
            lambda c: c["serve"].update(weights_dtype=dtype))
        proc = bench_process(manifest, f"{dtype}-closed", trace=0)
        line = line_of(proc)
        assert line["correct"] is True, line["compared"]
        pools[dtype] = notes_of(proc)["pool_bytes"]
    assert pools["bfloat16"] * 2 == pools["float32"]


def test_control_reads_beside_the_program():
    """``--control``: the reference one precision step down, read at
    the same prompts and served tokens, printed last in ``compared``;
    ``correct`` stays the program's. At this size the precision
    control proves nothing about the limit (a 256-token vocabulary
    leaves no near-ties for bfloat16 to flip: it reads 0), and on the
    chip it reads among the program's own readings (PERF.md 6)."""
    line = bench(os.path.join(DATA, "BENCHMARK.json"), "tiny-closed",
                 trace=0, more=["--control"])
    assert line["correct"] is True
    assert list(line["compared"])[-2:] == ["control_gap_over_std",
                                          "altered_token_gap_over_std"]
    assert line["compared"]["control_gap_over_std"]["value"] >= 0.0
    # a token altered where it is produced is what the limit does fail:
    # the least gap that another token id reads lies above it
    pair = line["compared"]["altered_token_gap_over_std"]
    assert pair["value"] > pair["limit"]


def drop(section, key):
    def change(config):
        del (config[section] if section else config)[key]
    return change


@pytest.mark.parametrize("change,says", [
    (drop(None, "reference"), "does not state reference"),
    (drop("model", "class"), "does not state model.class"),
    (drop("train", "criterion"), "does not state train.criterion"),
    (drop("serve", "weights_dtype"), "does not state serve.weights_dtype"),
    (lambda c: c.update(reference="no-such"), "reference/no-such.py"),
    (lambda c: c["model"].update(**{"class": "paddle_tpu.models:NoSuchLM"}),
     "model.class = 'paddle_tpu.models:NoSuchLM'"),
    (lambda c: c["train"].update(criterion="paddle_tpu.no_such:Loss"),
     "train.criterion = 'paddle_tpu.no_such:Loss'"),
    (lambda c: c["sizes"].update(kv_lora_rank=512),
     "sizes.kv_lora_rank=512"),
    (lambda c: c["serve"].update(parity_tol=0.5), "parity_tol_why"),
], ids=["no-reference", "no-class", "no-criterion", "no-dtype",
        "unknown-reference", "unknown-class", "unknown-criterion",
        "unreported-size", "tolerance-without-why"])
def test_a_configuration_at_fault_fails_and_says_which(tmp_path, change,
                                                        says):
    manifest = variant(tmp_path, "faulty", change)
    cell = "faulty-closed" if "parity" in says else "faulty-train"
    proc = bench_process(manifest, cell, trace=0, seconds=1)
    assert proc.returncode != 0
    assert not proc.stdout.strip()
    assert says in proc.stderr, proc.stderr[-2000:]
