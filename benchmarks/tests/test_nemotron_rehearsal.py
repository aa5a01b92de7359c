"""The CPU rehearsal of a configuration whose every layer is one mixer
and some keep a recurrent state: the Nemotron-H block at tiny widths
(``data/configs/nemotron-h-tiny.json``) through ``run.py --rehearse``
under the closed loop, judged by ``reference/nemotron_h.py`` and read
by the metric files PR 35 added."""
import json
import os

from benchmarks import common
from benchmarks.tests.test_rehearsal import DATA, bench

CELL = "nemotron-h-tiny-closed"
REAL_CELL = "serve-nemotron3-reasoning"
COUNTERS = {"kv_pool_occupancy_pct.longctx", "moe_max_load_over_mean.longctx",
            "moe_local_share_pct.reasoning", "state_mib_per_slot.hybrid"}
HYBRID = ("decode_step_mfu_pct.hybrid", "ssm_ms_per_step.hybrid",
          "ssm_state_roofline.hybrid", "moe_ms_per_step.hybrid",
          "moe_experts_roofline.hybrid")


def manifest_with_the_cell(tmp_path) -> str:
    manifest = common.load_json(os.path.join(DATA, "BENCHMARK.json"))
    real = common.load_json(os.path.join(common.ROOT, "BENCHMARK.json"))
    manifest["configs"].append({
        "name": "nemotron-h-tiny", "source": "test", "reduced": [],
        "file": os.path.join("benchmarks", "tests", "data", "configs",
                             "nemotron-h-tiny.json"), "why": "test"})
    manifest["workloads"].append({
        "name": CELL, "config": "nemotron-h-tiny",
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
    # neither a page nor a state slot is held after the drain
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
    # the state pools' bytes a slot, from the arrays: five layers of a
    # [3, 128] tail and an [8, 8, 16] state, all float32 here
    assert line["metrics"]["state_mib_per_slot.hybrid"]["value"] \
        == 5 * (3 * 128 + 8 * 8 * 16) * 4 / 2 ** 20
    for name in HYBRID:
        assert name not in line["metrics"]      # no device trace here


def test_the_real_manifest_names_the_cell_and_its_readers():
    real = common.load_json(os.path.join(common.ROOT, "BENCHMARK.json"))
    cell = next(w for w in real["workloads"] if w["name"] == REAL_CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("nemotron-3-super-120b-a12b", "backlog-reasoning", 1)
    mine = {m["name"] for m in real["per_layer"]
            if REAL_CELL in m.get("workloads", ())}
    assert set(HYBRID) | COUNTERS <= mine
    # the readers built on decode_scopes.summarize find nothing to read
    # for a model without window layers: the cell is left out of them
    assert not mine & {"moe_ms_per_step.longctx",
                       "moe_shared_ms_per_step.reasoning",
                       "paged_attn_roofline.longctx",
                       "decode_step_mfu_pct.reasoning",
                       "moe_experts_roofline.reasoning"}


def test_the_cost_counts_by_letter_of_the_pattern():
    """``nemotron_h_cost`` at the cell's own sizes, against the issue's
    arithmetic: 4,648.2 M parameters of which the held experts are
    5 x 128 x 5.505 M; a step of 128 lanes that touches all 640 needs
    about 14.7 GB (experts 7.05, states 5.4, the rest once) and 0.3
    TFLOP: bytes bind, 18 ms at the HBM peak."""
    from benchmarks import nemotron_h_cost as cost
    config = common.load_json(os.path.join(
        common.ROOT, "benchmarks", "configs",
        "nemotron-3-super-120b-a12b.json"))
    cfg = common.resolve(config["model"]["preset"], "model.preset")(
        **config["model"]["kwargs"])
    assert [cost.layers_of(cfg, c) for c in "M*E"] == [5, 1, 5]
    assert cost.expert_elems(cfg) == 2 * 1024 * 2688
    held = 5 * 128 * cost.expert_elems(cfg)
    assert cost.dense_elems(cfg) == \
        cfg.num_params() - held - cfg.vocab_size * cfg.hidden_size
    state = cost.state_step_cost(cfg, state_slots_live=128, elem_bytes=2.0)
    # 128 lanes x 5 layers x (4 MiB + 60 KiB) x 2, and the tokens' own
    assert 5.44e9 < state["bytes"] < 5.50e9
    experts = cost.experts_step_cost(
        cfg, experts_touched=640, local_assignments=128 * 22 * 5 / 4,
        elem_bytes=2.0)
    assert 7.0e9 < experts["bytes"] < 7.1e9
    step = cost.decode_step_cost(
        cfg, experts_touched=640, local_assignments=128 * 22 * 5 / 4,
        lanes=128, state_slots_live=128, context_tokens=128 * 1500,
        elem_bytes=2.0)
    assert 14.5e9 < step["bytes"] < 14.9e9
    assert 0.25e12 < step["flops"] < 0.35e12
    assert step["bytes"] / 819e9 > 10 * step["flops"] / 197e12


def test_the_scope_matcher_knows_the_state_update():
    from benchmarks import hybrid_scopes
    name = "jit(_decode)/jit(main)/ssm/state_update/while/body/mul"
    assert hybrid_scopes.scopes_of("fusion.1", name) == \
        ("ssm", "ssm/state_update")
    assert hybrid_scopes.scopes_of(
        "fusion.2", "jit(_decode)/moe/latent_down/dot_general") == ("moe",)
    assert hybrid_scopes.scopes_of("ragged-dot.3", "") == \
        ("moe", "moe/experts")
    assert hybrid_scopes.scopes_of("fusion.4", "jit(_decode)/ssm_mixer/x") \
        == ()
