"""The CPU rehearsal of a configuration with expert layers and two kinds
of KV pool: the SmallThinker block at tiny widths
(``data/configs/smallthinker-tiny.json``) through ``run.py --rehearse``
under the closed loop, judged by ``reference/smallthinker.py`` and read
by the metric files PR 29 added."""
import json
import os

from benchmarks import common
from benchmarks.tests.test_rehearsal import DATA, bench

CELL = "smallthinker-tiny-closed"
COUNTERS = {"kv_pool_occupancy_pct.longctx", "moe_max_load_over_mean.longctx"}


def manifest_with_the_cell(tmp_path) -> str:
    manifest = common.load_json(os.path.join(DATA, "BENCHMARK.json"))
    real = common.load_json(os.path.join(common.ROOT, "BENCHMARK.json"))
    manifest["configs"].append({
        "name": "smallthinker-tiny", "source": "test", "reduced": [],
        "file": os.path.join("benchmarks", "tests", "data", "configs",
                             "smallthinker-tiny.json"), "why": "test"})
    manifest["workloads"].append({
        "name": CELL, "config": "smallthinker-tiny",
        "traffic": "tiny-closed", "chips": 1, "why": "test"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "tiny-closed" in m.get("workloads", ()):
            m["workloads"].append(CELL)
    # the new cell's own metrics, as the real manifest states them
    for m in real["per_layer"]:
        if m.get("workloads") == ["serve-smallthinker-longctx"]:
            manifest["per_layer"].append(dict(m, workloads=[CELL]))
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(manifest))
    return str(path)


def test_the_block_is_served_and_judged(tmp_path):
    line = bench(manifest_with_the_cell(tmp_path), CELL, trace=0)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert line["compared"]["pages_held"]["value"] == 0


def test_its_counters_reach_the_traced_line(tmp_path):
    """A CPU run prints no device metric; the two that read the
    program's counters are there."""
    line = bench(manifest_with_the_cell(tmp_path), CELL, trace=1)
    assert line["correct"] is True
    assert COUNTERS <= set(line["metrics"])
    assert 0 < line["metrics"]["kv_pool_occupancy_pct.longctx"]["value"] \
        <= 100
    assert line["metrics"]["moe_max_load_over_mean.longctx"]["value"] >= 1
