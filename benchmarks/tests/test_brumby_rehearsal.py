"""The CPU rehearsal of a configuration whose every layer is power
retention, a recurrent state a sequence and no pages: the Brumby block at
tiny widths (``data/configs/brumby-tiny.json``) through ``run.py
--rehearse`` under the closed loop, judged by ``reference/brumby.py``;
and the cost and the trace reader its ``.retention`` metrics read."""
import json
import os

from benchmarks import common, retention_cost, scoped_steps
from benchmarks.tests.test_rehearsal import DATA, bench

CELL = "brumby-tiny-closed"
REAL_CELL = "serve-brumby-backlog"


def manifest_with_the_cell(tmp_path) -> str:
    manifest = common.load_json(os.path.join(DATA, "BENCHMARK.json"))
    real = common.load_json(os.path.join(common.ROOT, "BENCHMARK.json"))
    manifest["configs"].append({
        "name": "brumby-tiny", "source": "test", "reduced": [],
        "file": os.path.join("benchmarks", "tests", "data", "configs",
                             "brumby-tiny.json"), "why": "test"})
    manifest["workloads"].append({
        "name": CELL, "config": "brumby-tiny",
        "traffic": "tiny-closed", "chips": 1, "why": "test"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "tiny-closed" in m.get("workloads", ()):
            m["workloads"].append(CELL)
    have = {m["name"] for m in manifest["per_layer"]}
    for m in real["per_layer"]:
        if REAL_CELL in m.get("workloads", ()) and m["name"] not in have:
            manifest["per_layer"].append(dict(m, workloads=[CELL]))
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(manifest))
    return str(path)


def test_the_block_is_served_and_judged(tmp_path):
    line = bench(manifest_with_the_cell(tmp_path), CELL, trace=0)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert line["compared"]["pages_held"]["value"] == 0


def test_the_device_metrics_are_silent_without_a_device_trace(tmp_path):
    """A CPU run prints no device metric, and its readers raise
    nothing."""
    line = bench(manifest_with_the_cell(tmp_path), CELL, trace=1)
    assert line["correct"] is True
    for name in ("retention_ms_per_step.retention",
                 "retention_state_roofline.retention",
                 "decode_step_mfu_pct.retention"):
        assert name not in line["metrics"]


def test_the_cost_counts_the_states_at_the_cells_sizes():
    """``retention_cost`` at the cell's own sizes: 3,207,594,280
    parameters, of which the embedding's 777,912,320 are read a row a
    lane; 32 lanes read and write 5 layers x 34,080,768 B of state a
    step, 10.9 GB, about 1.6 operations a byte."""
    config = common.load_json(os.path.join(
        common.ROOT, "benchmarks", "configs", "brumby-14b-base.json"))
    cfg = common.resolve(config["model"]["preset"], "model.preset")(
        **config["model"]["kwargs"])
    per_slot = 5 * (33_816_576 + 264_192)
    state = retention_cost.state_step_cost(cfg, state_slots_live=32,
                                           bytes_per_slot=per_slot)
    assert state["bytes"] == 2 * 32 * per_slot
    assert 10.8e9 < state["bytes"] < 11.0e9
    assert 1.5 < state["flops"] / state["bytes"] < 1.7
    flops = retention_cost.step_flops(cfg, lanes=32, state_slots_live=32)
    assert flops == 2 * 32 * (3_207_594_280 - 151_936 * 5120) \
        + state["flops"]


def _trace(ops, spans):
    """A reduced trace of one device plane: ``(name, start, dur,
    op_name)`` operations, ``(name, start, dur)`` decode programs, and
    ``engine::decode_call`` spans with their arguments."""
    return {"window": (0, 1_000), "spans": [
        ("engine::decode_call", s, d, "loop", args) for s, d, args in spans],
        "devices": {"/device:TPU:0": {
            "ops": ops, "modules": [("jit__decode(1)", 10, 100),
                                    ("jit__decode(2)", 210, 100),
                                    ("jit__prefill(3)", 400, 100)]}}}


def test_the_reader_takes_its_scopes_span_arguments_and_cost_as_data():
    """Two decode programs held by spans carrying the arguments, one
    prefill: the time under each scope asked for, over the decode steps
    alone, the arguments summed, and the cost of the mean step."""
    ops = [("fusion.1", 20, 30, "jit(_decode)/retention/state_update/mul"),
           ("fusion.2", 60, 10, "jit(_decode)/retention/phi/mul"),
           ("fusion.3", 220, 40, "jit(_decode)/retention/state_update/add"),
           ("fusion.4", 410, 50, "jit(_prefill)/retention/chunk_scan/x")]
    args = {"active": 30, "context_tokens": 900, "state_slots_live": 30}
    trace = _trace(ops, [(5, 150, args), (205, 150, dict(args, active=32))])
    seen = []
    s = scoped_steps.summarize(
        trace, ("retention", "retention/state_update"),
        ("active", "state_slots_live"),
        lambda step: seen.append(step) or {"bytes": 1.0})
    assert s["steps"] == 2 and s["device_s"] == 200e-9
    assert s["device_s_by_scope"] == {"retention": 80e-9,
                                      "retention/state_update": 70e-9}
    assert s["args"] == {"active": 62, "state_slots_live": 60}
    assert seen == [{"active": 31.0, "state_slots_live": 30.0}]
    assert abs(scoped_steps.ms_per_step(s, "retention/state_update")
               - 35e-6) < 1e-15
    # a program whose spans lack an argument asked for has no steps
    assert scoped_steps.summarize(trace, ("retention",),
                                  ("experts_touched",)) is None
