"""``BENCHMARK.json`` against the files it names, and the yardstick's
arithmetic."""
import os
import re

import pytest

from benchmarks import common, flash_cost, run

MANIFEST = common.load_json(os.path.join(common.ROOT, "BENCHMARK.json"))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
ALL_METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]


def test_names_units_and_lengths():
    for entry in ALL_METRICS + MANIFEST["workloads"] + MANIFEST["configs"]:
        assert NAME.match(entry["name"]), entry["name"]
    for m in ALL_METRICS:
        assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"])
        assert m["better"] in ("lower", "higher")
    for w in MANIFEST["workloads"]:
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    names = [m["name"] for m in ALL_METRICS]
    assert len(names) == len(set(names))


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_every_cell_finds_its_files_and_reports_enough(cell):
    w = next(x for x in MANIFEST["workloads"] if x["name"] == cell)
    config = next(c for c in MANIFEST["configs"] if c["name"] == w["config"])
    assert os.path.exists(os.path.join(common.ROOT, config["file"]))
    traffic = common.load_json(run.find_file(
        MANIFEST, "traffic", w["traffic"], (".json",)))
    assert traffic["kind"] in run.RUNNERS
    e2e = [m["name"] for m in run.metrics_of(MANIFEST, cell, "end_to_end")]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert run.metrics_of(MANIFEST, cell, "per_layer")


@pytest.mark.parametrize("entry", MANIFEST["configs"],
                         ids=lambda c: c["name"])
def test_every_configuration_names_what_can_be_found(entry):
    """The keys the harness has no default for, and each name behind
    them: cheap, no model is built."""
    config = common.load_json(os.path.join(common.ROOT, entry["file"]))
    common.check_config_keys(config, entry["file"])
    assert callable(common.resolve(config["model"]["class"], "model.class"))
    assert callable(common.resolve(config["model"]["preset"],
                                   "model.preset"))
    if "train" in config:
        assert callable(common.resolve(config["train"]["criterion"],
                                       "train.criterion"))
    assert config["serve"]["weights_dtype"] in ("float32", "bfloat16")
    reference = common.load_module(
        run.find_file(MANIFEST, "reference", config["reference"], (".py",)),
        "reference_under_test")
    assert callable(reference.logits) and callable(reference.causal_lm_loss)
    assert entry["reduced"] == config["reduced"]


@pytest.mark.parametrize("entry", ALL_METRICS, ids=lambda m: m["name"])
def test_reader_says_what_the_manifest_says(entry):
    reader = run.load_reader(MANIFEST, entry["name"])
    assert reader.UNIT == entry["unit"]
    assert reader.SOURCE == entry["source"]
    assert reader.BETTER == entry["better"]
    assert reader.LAYER == entry.get("layer")
    assert reader.MOVES == entry.get("moves")
    assert callable(reader.read)


@pytest.mark.parametrize("entry", MANIFEST["per_layer"],
                         ids=lambda m: m["name"])
def test_a_layer_metric_moves_a_metric_its_cells_report(entry):
    cells = entry.get("workloads") or [w["name"]
                                       for w in MANIFEST["workloads"]]
    for cell in cells:
        e2e = [m["name"] for m in run.metrics_of(MANIFEST, cell,
                                                 "end_to_end")]
        assert entry["moves"] in e2e, (entry["name"], cell)


def test_bounds_and_run_length_fit_the_contract():
    for m in MANIFEST["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    runs = 2 + 14 * 24
    assert runs * (MANIFEST["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_flash_cost_of_the_train_cell():
    """b=2 s=2048 16 heads of 128, 24 layers, two forward calls and a
    backward: 9 * B*H*S^2*D a layer, 3.7 TFLOP a step, compute-bound."""
    cost = flash_cost.flash_step_cost(batch=2, heads=16, seq=2048,
                                      head_dim=128, layers=24,
                                      forward_calls=2)
    assert cost["flops"] == 24 * 9 * 2 * 16 * 2048 * 2048 * 128
    assert cost["flops"] == pytest.approx(3.71e12, rel=0.01)
    least = flash_cost.roofline(cost, common.chip_peaks("TPU v5 lite"))
    assert least["bound"] == "compute"
    assert least["min_seconds"] == pytest.approx(0.0188, rel=0.01)
    assert flash_cost.train_flops_per_token(
        n_params=1_315_000_000, layers=24, hidden=2048,
        seq=2048) == pytest.approx(9.10e9, rel=0.01)


def test_an_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        common.chip_peaks("TPU v9 imaginary")
