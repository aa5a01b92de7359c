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


def test_the_shared_configuration_states_its_share():
    """``k-exaone-236b-a23b.json``: ``reduced`` is the depth, the
    experts held and the vocabulary (each under the source's name and
    the program's), with the published numbers and the 8-chip
    deployment beside them; no width differs from the catalog row's."""
    config = common.load_json(os.path.join(
        common.ROOT, "benchmarks", "configs", "k-exaone-236b-a23b.json"))
    assert config["reduced"] == ["num_layers", "num_hidden_layers",
                                 "moe_num_experts", "num_experts",
                                 "vocab_size"]
    cut = config["reduced_from"]
    assert cut["chips_sharing_a_layer"] == 8
    assert "expert-parallel" in cut["deployment"]
    for key, published, run_here in (("num_layers", 48, 5),
                                     ("moe_num_experts", 128, 16),
                                     ("vocab_size", 153600, 19200)):
        assert (cut[key]["published"], cut[key]["run"]) == \
            (published, run_here) and cut[key]["why"]
    assert "num_hidden_layers" in cut["num_layers"] \
        and "num_experts" in cut["moe_num_experts"]
    assert len(config["assumed"]) == 4
    assert "multi-token-prediction" in config["not_built"]
    assert config["serve"]["weights_dtype"] == "bfloat16" \
        and config["model"]["kwargs"] == {
            "num_layers": 5, "moe_num_experts": 16, "vocab_size": 19200,
            "dtype": "bfloat16"}
    sizes = config["sizes"]
    # every width and count the source publishes, under both names
    published = {
        "hidden_size": 6144, "num_attention_heads": 64,
        "num_key_value_heads": 8, "head_dim": 128,
        "intermediate_size": 18432, "moe_intermediate_size": 2048,
        "num_experts_per_tok": 8, "num_shared_experts": 1,
        "sliding_window": 128, "rms_norm_eps": 1e-5,
        "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
        "norm_topk_prob": True, "first_k_dense_replace": 1,
        "n_group": 1, "topk_group": 1, "hidden_act": "silu",
        "max_position_embeddings": 262144, "num_routed_experts": 128}
    for key, want in published.items():
        assert config[key] == want, key
    assert config["rope_parameters"]["rope_theta"] == 1000000
    assert len(config["layer_types"]) == len(config["mlp_layer_types"]) == 48
    for ours, theirs in (("hidden_size", "hidden_size"),
                         ("num_heads", "num_attention_heads"),
                         ("num_kv_heads", "num_key_value_heads"),
                         ("head_dim", "head_dim"),
                         ("intermediate_size", "intermediate_size"),
                         ("moe_num_experts", "num_experts"),
                         ("moe_router_experts", "num_routed_experts"),
                         ("moe_top_k", "num_experts_per_tok"),
                         ("moe_intermediate_size", "moe_intermediate_size"),
                         ("moe_routed_scale", "routed_scaling_factor"),
                         ("sliding_window", "sliding_window"),
                         ("layer_norm_eps", "rms_norm_eps"),
                         ("vocab_size", "vocab_size"),
                         ("max_seq_len", "max_position_embeddings")):
        assert sizes[ours] == config[theirs], ours
    assert sizes["moe_shared_intermediate_size"] == \
        config["num_shared_experts"] * config["moe_intermediate_size"]
    assert config["num_hidden_layers"] == sizes["num_layers"] == 5
    # the layers run are the published layers 0-4 under their kinds
    preset = common.resolve(config["model"]["preset"], "model.preset")
    cfg = preset(**config["model"]["kwargs"])
    assert [("sliding_attention", "full_attention")[not w]
            for w in cfg.sliding_window_layout] == config["layer_types"][:5]
    assert [("dense", "sparse")[m] for m in cfg.moe_layout] == \
        config["mlp_layer_types"][:5]
    serve = config["serve"]
    assert (serve["max_batch"], serve["page_size"]) == (128, 16)
    assert not serve["prefix_cache"]
    assert serve["num_pages"] == 1 + 128 * -(-serve["max_seq_len"] // 16)


def test_the_hybrid_configuration_states_its_share():
    """``nemotron-3-super-120b-a12b.json``: ``reduced`` is the depth,
    the experts held and the vocabulary (each under the source's name
    and the program's), with the published numbers and the 4-chip
    deployment beside them; no width differs from the catalog row's;
    the layers run are the first 11 letters of the whole pattern."""
    config = common.load_json(os.path.join(
        common.ROOT, "benchmarks", "configs",
        "nemotron-3-super-120b-a12b.json"))
    assert config["source"] == (
        "https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-"
        "BF16/blob/main/config.json")
    assert config["reduced"] == ["num_layers", "num_hidden_layers",
                                 "moe_num_experts", "n_routed_experts",
                                 "vocab_size"]
    cut = config["reduced_from"]
    assert cut["chips_sharing_a_layer"] == 4
    assert "expert-parallel" in cut["deployment"]
    for key, published, run_here in (("num_layers", 88, 11),
                                     ("moe_num_experts", 512, 128),
                                     ("vocab_size", 131072, 32768)):
        assert (cut[key]["published"], cut[key]["run"]) == \
            (published, run_here) and cut[key]["why"]
    assert "num_hidden_layers" in cut["num_layers"] \
        and "n_routed_experts" in cut["moe_num_experts"]
    assert sorted(config["assumed"]) == [
        "attention_has_no_positions", "gated_norm_per_group",
        "latent_placement", "state_precision", "ungated_relu2"]
    assert "multi-token-prediction" in config["not_built"]
    assert config["serve"]["weights_dtype"] == "bfloat16" \
        and config["model"]["kwargs"] == {
            "num_layers": 11, "moe_num_experts": 128, "vocab_size": 32768,
            "dtype": "bfloat16"}
    # every width and count the source publishes, under its own name
    published = {
        "hidden_size": 4096, "mamba_num_heads": 128, "mamba_head_dim": 64,
        "expand": 2, "ssm_state_size": 128, "n_groups": 8,
        "conv_kernel": 4, "chunk_size": 128, "num_attention_heads": 32,
        "num_key_value_heads": 2, "head_dim": 128,
        "num_experts_per_tok": 22, "routed_scaling_factor": 5,
        "norm_topk_prob": True, "n_group": 1, "topk_group": 1,
        "moe_latent_size": 1024, "moe_intermediate_size": 2688,
        "moe_shared_expert_intermediate_size": 5376, "n_shared_experts": 1,
        "mlp_hidden_act": "relu2", "layer_norm_epsilon": 1e-5,
        "use_conv_bias": True, "tie_word_embeddings": False,
        "max_position_embeddings": 262144, "router_experts": 512,
        "num_nextn_predict_layers": 1, "mtp_hybrid_override_pattern": "*E"}
    for key, want in published.items():
        assert config[key] == want, key
    pattern = config["hybrid_override_pattern"]
    assert len(pattern) == 88
    assert [pattern.count(c) for c in "M*E"] == [40, 8, 40]
    assert [pattern[:11].count(c) for c in "M*E"] == [5, 1, 5]
    sizes = config["sizes"]
    for ours, theirs in (("hidden_size", "hidden_size"),
                         ("pattern", "hybrid_override_pattern"),
                         ("num_heads", "num_attention_heads"),
                         ("num_kv_heads", "num_key_value_heads"),
                         ("head_dim", "head_dim"),
                         ("mamba_num_heads", "mamba_num_heads"),
                         ("mamba_head_dim", "mamba_head_dim"),
                         ("ssm_state_size", "ssm_state_size"),
                         ("mamba_n_groups", "n_groups"),
                         ("conv_kernel", "conv_kernel"),
                         ("chunk_size", "chunk_size"),
                         ("moe_num_experts", "n_routed_experts"),
                         ("moe_router_experts", "router_experts"),
                         ("moe_top_k", "num_experts_per_tok"),
                         ("moe_routed_scale", "routed_scaling_factor"),
                         ("moe_latent_size", "moe_latent_size"),
                         ("moe_intermediate_size", "moe_intermediate_size"),
                         ("moe_shared_intermediate_size",
                          "moe_shared_expert_intermediate_size"),
                         ("layer_norm_eps", "layer_norm_epsilon"),
                         ("vocab_size", "vocab_size"),
                         ("max_seq_len", "max_position_embeddings")):
        assert sizes[ours] == config[theirs], ours
    assert sizes["mamba_num_heads"] * sizes["mamba_head_dim"] == \
        config["expand"] * sizes["hidden_size"]
    assert config["num_hidden_layers"] == sizes["num_layers"] == 11
    assert sizes["ssm_state_dtype"] == "float32"
    # the layers run are the published layers 0-10 under their kinds
    preset = common.resolve(config["model"]["preset"], "model.preset")
    cfg = preset(**config["model"]["kwargs"])
    assert cfg.kinds == pattern[:11] == "MEMEMEM*EME"
    # the whole preset is the published model: its size is its name
    assert abs(preset().num_params() / 1e9 - 120.67) < 0.005
    serve = config["serve"]
    assert serve["max_batch"] in (128, 96, 64) and serve["page_size"] == 16
    assert not serve["prefix_cache"]
    assert serve["num_pages"] == \
        1 + serve["max_batch"] * -(-serve["max_seq_len"] // 16)
    assert serve["seq_buckets"] == [512, 1024, 2048]
    assert serve["parity_tol_why"] and serve["num_pages_why"]
    entry = next(c for c in MANIFEST["configs"]
                 if c["name"] == "nemotron-3-super-120b-a12b")
    assert entry["source"] == config["source"]
    assert entry["reduced"] == config["reduced"]
    cell = next(w for w in MANIFEST["workloads"]
                if w["name"] == "serve-nemotron3-reasoning")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("nemotron-3-super-120b-a12b", "backlog-reasoning", 1)


def test_no_key_of_the_catalog_row_differs_but_the_reduced():
    """Where the catalog beside the ``model-configs`` guide can be read:
    the file holds every number of the row's ``config`` under the same
    key, but for the three that ``reduced`` names."""
    import json
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    config = common.load_json(os.path.join(
        common.ROOT, "benchmarks", "configs",
        "nemotron-3-super-120b-a12b.json"))
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["source_url"] == config["source"])
    differs = {k for k, v in row["config"].items() if config.get(k) != v}
    assert differs == {"num_hidden_layers", "n_routed_experts",
                       "vocab_size"}
    assert differs <= set(config["reduced"])
