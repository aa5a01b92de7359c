"""The CPU rehearsal of a configuration whose expert layers hold a share
of their experts beside a shared one, after a dense first layer: the
K-EXAONE block at tiny widths (``data/configs/kexaone-tiny.json``)
through ``run.py --rehearse`` under the closed loop, judged by
``reference/kexaone.py`` and read by the metric files PR 33 added."""
import json
import os

from benchmarks import common
from benchmarks.tests.test_rehearsal import DATA, bench

CELL = "kexaone-tiny-closed"
REAL_CELL = "serve-kexaone-reasoning"
COUNTERS = {"kv_pool_occupancy_pct.longctx", "moe_max_load_over_mean.longctx",
            "moe_local_share_pct.reasoning"}


def manifest_with_the_cell(tmp_path) -> str:
    manifest = common.load_json(os.path.join(DATA, "BENCHMARK.json"))
    real = common.load_json(os.path.join(common.ROOT, "BENCHMARK.json"))
    manifest["configs"].append({
        "name": "kexaone-tiny", "source": "test", "reduced": [],
        "file": os.path.join("benchmarks", "tests", "data", "configs",
                             "kexaone-tiny.json"), "why": "test"})
    manifest["workloads"].append({
        "name": CELL, "config": "kexaone-tiny",
        "traffic": "tiny-closed", "chips": 1, "why": "test"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "tiny-closed" in m.get("workloads", ()):
            m["workloads"].append(CELL)
    # what the real cell reports beyond the rehearsal's own metrics, as
    # the real manifest states it
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


def test_its_counters_reach_the_traced_line(tmp_path):
    """A CPU run prints no device metric; those that read the program's
    counters are there, and this share of 4 experts among 16 got some
    of the work and not all."""
    line = bench(manifest_with_the_cell(tmp_path), CELL, trace=1)
    assert line["correct"] is True
    assert COUNTERS <= set(line["metrics"])
    assert 0 < line["metrics"]["moe_local_share_pct.reasoning"]["value"] \
        < 100
    for name in ("decode_step_mfu_pct.reasoning",
                 "moe_experts_roofline.reasoning",
                 "moe_shared_ms_per_step.reasoning"):
        assert name not in line["metrics"]      # no device trace here


def test_the_cost_counts_by_layer_kind():
    """``kexaone_cost`` at the cell's own sizes, against the issue's
    arithmetic: 3,712.0 M parameters of which the held experts are
    4 x 16 x 37.75 M, and a step that touches all 64 reads about 7.4 GB
    of weights."""
    from benchmarks import kexaone_cost
    config = common.load_json(os.path.join(
        common.ROOT, "benchmarks", "configs", "k-exaone-236b-a23b.json"))
    cfg = common.resolve(config["model"]["preset"], "model.preset")(
        **config["model"]["kwargs"])
    assert kexaone_cost.expert_layers(cfg) == 4
    assert kexaone_cost.expert_elems(cfg) == 3 * 6144 * 2048
    held = 4 * 16 * kexaone_cost.expert_elems(cfg)
    # everything but the held experts and the embedding is read whole
    assert kexaone_cost.dense_elems(cfg) == \
        cfg.num_params() - held - cfg.vocab_size * cfg.hidden_size
    step = kexaone_cost.decode_step_cost(
        cfg, experts_touched=64, local_assignments=128 * 4, lanes=128,
        elem_bytes=2.0, attention={"flops": 0.0, "bytes": 0.0})
    assert 7.1e9 < step["bytes"] < 7.3e9
    assert step["flops"] < 0.5e12
