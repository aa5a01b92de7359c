"""``BENCHMARK.json``'s configurations name what can be found: the
tier-1 copy of ``benchmarks/tests/test_manifest.py::
test_every_configuration_names_what_can_be_found`` (PERF.md section 7:
the benchmark's own tests are not tier-1). Cheap: no model is built."""
import os

import pytest

from benchmarks import common, run

MANIFEST = common.load_json(os.path.join(common.ROOT, "BENCHMARK.json"))


@pytest.mark.parametrize("entry", MANIFEST["configs"],
                         ids=lambda c: c["name"])
def test_every_configuration_names_what_can_be_found(entry):
    """The keys the harness has no default for, and each name behind
    them."""
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
    assert callable(reference.control_logits)
    assert entry["reduced"] == config["reduced"]


@pytest.mark.parametrize("entry", MANIFEST["configs"],
                         ids=lambda c: c["name"])
def test_the_files_sizes_are_the_presets(entry):
    """Every size a file states is what its preset gives for the file's
    own keyword arguments (the runner checks the built model the same
    way; here the config object alone, which reports each of them)."""
    config = common.load_json(os.path.join(common.ROOT, entry["file"]))
    preset = common.resolve(config["model"]["preset"], "model.preset")
    cfg = preset(**config["model"].get("kwargs", {}))
    for key, want in config["sizes"].items():
        assert getattr(cfg, key) == want, key


def test_the_cut_configuration_states_its_cut():
    """``reduced`` names the depth alone, with the published depth and
    the deployment beside it; no width differs from the source's."""
    config = common.load_json(os.path.join(
        common.ROOT, "benchmarks", "configs", "smallthinker-21ba3b.json"))
    assert config["reduced"] == ["num_layers", "num_hidden_layers"]
    cut = config["reduced_from"]["num_layers"]
    assert (cut["published"], cut["run"]) == (52, 8) and cut["deployment"]
    assert len(config["assumed"]) == 4
    assert config["serve"]["weights_dtype"] == "bfloat16" \
        and config["model"]["kwargs"]["dtype"] == "bfloat16"
    sizes = config["sizes"]
    for ours, theirs in (("hidden_size", "hidden_size"),
                         ("num_heads", "num_attention_heads"),
                         ("num_kv_heads", "num_key_value_heads"),
                         ("head_dim", "head_dim"),
                         ("moe_num_experts", "moe_num_primary_experts"),
                         ("moe_top_k", "moe_num_active_primary_experts"),
                         ("moe_intermediate_size", "moe_ffn_hidden_size"),
                         ("sliding_window", "sliding_window_size"),
                         ("vocab_size", "vocab_size"),
                         ("max_seq_len", "max_position_embeddings")):
        assert sizes[ours] == config[theirs], ours
    assert config["num_hidden_layers"] == sizes["num_layers"] == 8
