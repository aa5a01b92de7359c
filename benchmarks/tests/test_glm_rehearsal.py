"""The CPU rehearsal of a configuration whose layers attend through a
latent cache and whose expert layers hold a share of their experts,
chosen with a selection bias, beside a shared one: the GLM-4.7-Flash
block at tiny widths (``data/configs/glm-tiny.json``) through ``run.py
--rehearse`` under the closed loop, judged by
``reference/glm4_moe_lite.py`` and read by the ``.latent`` metric files
and the counters the real cell reports."""
import json
import os

from benchmarks import common
from benchmarks.tests.test_rehearsal import DATA, bench

CELL = "glm-tiny-closed"
REAL_CELL = "serve-glm47flash-reasoning"
COUNTERS = {"kv_pool_occupancy_pct.longctx", "moe_max_load_over_mean.longctx",
            "moe_local_share_pct.reasoning", "moe_narrow_share_pct.reasoning"}


def manifest_with_the_cell(tmp_path) -> str:
    manifest = common.load_json(os.path.join(DATA, "BENCHMARK.json"))
    real = common.load_json(os.path.join(common.ROOT, "BENCHMARK.json"))
    manifest["configs"].append({
        "name": "glm-tiny", "source": "test", "reduced": [],
        "file": os.path.join("benchmarks", "tests", "data", "configs",
                             "glm-tiny.json"), "why": "test"})
    manifest["workloads"].append({
        "name": CELL, "config": "glm-tiny",
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


def test_its_counters_reach_the_traced_line(tmp_path):
    """A CPU run prints no device metric; those that read the program's
    counters are there."""
    line = bench(manifest_with_the_cell(tmp_path), CELL, trace=1)
    assert line["correct"] is True
    assert COUNTERS <= set(line["metrics"])
    assert 0 < line["metrics"]["moe_local_share_pct.reasoning"]["value"] \
        < 100
    for name in ("mla_ms_per_step.latent", "latent_attn_roofline.latent",
                 "moe_ms_per_step.latent", "decode_step_mfu_pct.latent"):
        assert name not in line["metrics"]      # no device trace here


def test_the_cost_counts_the_latent_in_place_of_k_and_v():
    """``latent_cost`` at the cell's own sizes, against the arithmetic
    of the configuration file: 1,339.1 M parameters of which the held
    experts are 11 x 8 x 9.44 M; at a mean context of 1,400 a lane, 128
    lanes read 2.48 GB of latent rows a step, 37.8 operations a byte."""
    from benchmarks import kexaone_cost, latent_cost
    config = common.load_json(os.path.join(
        common.ROOT, "benchmarks", "configs", "glm-4.7-flash.json"))
    cfg = common.resolve(config["model"]["preset"], "model.preset")(
        **config["model"]["kwargs"])
    assert cfg.num_params() == 1_339_098_816
    assert kexaone_cost.expert_layers(cfg) == 11
    held = 11 * 8 * kexaone_cost.expert_elems(cfg)
    assert latent_cost.dense_elems(cfg) == \
        cfg.num_params() - held - cfg.vocab_size * cfg.hidden_size
    attn = latent_cost.attention_step_cost(
        cfg, context_tokens=128 * 1400, lanes=128, elem_bytes=2.0)
    assert 2.47e9 < attn["bytes"] < 2.49e9
    assert abs(attn["flops"] / attn["bytes"] - 37.8) < 0.1
    step = latent_cost.decode_step_cost(
        cfg, experts_touched=88, local_assignments=128 * 4 * 11 / 8,
        lanes=128, context_tokens=128 * 1400, elem_bytes=2.0)
    assert 4.9e9 < step["bytes"] < 5.2e9
