"""The SmallThinker block through the serving path, at tiny widths on the
CPU: 8 layers of pattern full-window-window-window, hidden 64, 4 query
heads over 2 K/V heads of 16, 8 ReGLU experts top-2 of width 32, window
8, pages of 4 slots, float32.

The judge is ``benchmarks/reference/smallthinker.py``, which imports
nothing of the program: the model's full forward, and prefill then
decode through ``GenerationServer``'s cache (logits, not tokens), for
prompts shorter and longer than the window and decodes that wrap a
window layer's ring three times, on the pure body and on the kernels in
interpret mode.
"""
import importlib.util
import os

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import models
from paddle_tpu.jit.functional import state_arrays
from paddle_tpu.ops.paged_attention import kv_pool_shape
from paddle_tpu.serving.generation import GenerationServer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REFERENCE = os.path.join(ROOT, "benchmarks", "reference", "smallthinker.py")
WINDOW, PAGE, RING = 8, 4, 3          # RING = ceil(8 / 4) + 1
PATTERN = (0, 1, 1, 1) * 2


def tiny_config(**kw):
    d = dict(vocab_size=128, hidden_size=64, num_layers=8, num_heads=4,
             num_kv_heads=2, head_dim=16, max_seq_len=256, norm="rmsnorm",
             layer_norm_eps=1e-6, bias=False, position="rope",
             rope_theta=1.5e6, rope_layout=PATTERN, sliding_window=WINDOW,
             sliding_window_layout=PATTERN, moe_num_experts=8, moe_top_k=2,
             moe_intermediate_size=32, moe_router_input="attention_input",
             tie_word_embeddings=False)
    d.update(kw)
    return models.GPTConfig(**d)


@pytest.fixture(scope="module")
def reference():
    spec = importlib.util.spec_from_file_location("ref_smallthinker",
                                                  REFERENCE)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def model():
    paddle.seed(11)
    m = models.GPTForCausalLM(tiny_config())
    m.eval()
    return m


def test_reference_imports_nothing_of_the_program():
    src = open(REFERENCE).read()
    assert "import paddle_tpu" not in src and "from paddle_tpu" not in src


def test_full_forward_matches_the_reference(model, reference):
    ids = np.random.default_rng(0).integers(0, 128, (2, 40))
    params = state_arrays(model)[0]
    got = np.asarray(model(paddle.to_tensor(ids))._data)
    want = np.asarray(reference.logits(params, ids, model.config))
    # float32 on both sides, summed in other orders
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    # the control computes something else: float8 activations move it
    low = np.asarray(reference.control_logits(params, ids, model.config))
    assert np.abs(low - want).max() > 100 * np.abs(got - want).max()


def serve(model, prompts, max_new, **server_kw):
    """``prompts`` (of distinct lengths) served together. Returns the
    tokens and, by prompt length, the logits each token was chosen from
    (a dispatch leaves them on the device beside the tokens its program
    chose: a decode step's row is the lane, a prefill's the sequence's
    place in the call), and the last snapshot."""
    seen = {}
    kw = dict(max_batch=4, page_size=PAGE, num_pages=64, max_seq_len=64,
              seq_buckets=[8, 16, 32], start=False)
    kw.update(server_kw)
    srv = GenerationServer(model, **kw)
    dispatch, enqueue = srv._dispatch, srv._runners[0].enqueue

    def note(seq, row, tokens, logits):
        seen.setdefault(len(seq.req.prompt), []).append(
            np.array(logits[row]))
        # the program's choice is the first best of that row
        assert tokens[row] == logits[row].argmax()
        # a lane never holds more of a window layer than its ring
        assert len(seq.window_pages) <= srv.kv.ring_pages

    def spy(kind, feeds, seqs, *args, **kwargs):
        """A prefill: a sequence's row is its place in the call."""
        ran = dispatch(kind, feeds, seqs, *args, **kwargs)
        logits = np.asarray(ran.logits)
        for i, seq in enumerate(seqs):
            note(seq, i, ran.tokens, logits)
        return ran

    def step_spy(kind, feeds):
        """A decode step, as the loop enqueues it (a step ahead of its
        harvest): a sequence's row is its lane."""
        step = enqueue(kind, feeds)
        if kind == "decode":
            tokens, logits = np.asarray(step.tokens), np.asarray(step.logits)
            for lane in np.flatnonzero(feeds[2]):
                note(srv._slots[lane], lane, tokens, logits)
        return step

    srv._dispatch, srv._runners[0].enqueue = spy, step_spy
    futures = [srv.submit_generate(p, max_new_tokens=max_new)
               for p in prompts]
    srv.start()
    tokens = [f.result(timeout=300) for f in futures]
    snap = srv.metrics_snapshot()
    srv.shutdown()
    srv.kv.assert_no_leaks()
    assert srv.kv.used_pages == 0
    return tokens, seen, snap


@pytest.mark.parametrize("use_pallas", [False, True],
                         ids=["pure-body", "kernels-interpreted"])
def test_prefill_then_decode_matches_the_reference_logits(
        model, reference, use_pallas):
    """Prompts of 5 (shorter than the window) and 23 tokens (longer: the
    prefill keeps its last 8 of a window layer), 37 new tokens each: a
    ring of 12 slots is gone round three times and more."""
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 128, n) for n in (5, 23)]
    tokens, seen, snap = serve(model, prompts, 37, use_pallas=use_pallas)
    params = state_arrays(model)[0]
    for prompt, toks in zip(prompts, tokens):
        assert len(toks) == 37
        ids = np.concatenate([prompt, toks[:-1]])[None]
        want = np.asarray(reference.logits(
            params, ids, model.config,
            positions=np.arange(len(prompt) - 1, ids.shape[1])))[0]
        got = np.stack(seen[len(prompt)])
        assert got.shape == want.shape
        np.testing.assert_allclose(got, want, atol=5e-5, rtol=0)
    kv = snap["engine"]["kv"]
    assert kv["capacity"] == {"full": 63, "window": 4 * RING}
    assert kv["pages_in_use"] == {"full": 0, "window": 0}
    # 60 positions are 15 pages of a ring of 3: 12 of them recycled one
    assert kv["window_pages_recycled"] >= 3 * RING
    moe = snap["engine"]["moe"]
    assert moe["assignments"] == 8 * 2 * (5 + 23 + 2 * 36)   # dropless
    assert 0 < moe["experts_touched"] <= moe["assignments"]
    # every expert is held: no capacity, so neither count
    assert "narrow_calls" not in moe and "wide_calls" not in moe


def test_window_pool_is_sized_from_the_window_not_the_context(model):
    srv = GenerationServer(model, max_batch=4, page_size=PAGE, num_pages=64,
                           max_seq_len=64, seq_buckets=[8], start=False)
    k, _ = srv.kv.k, srv.kv.v
    pages = [int(a.shape[0]) for a in k]
    assert pages == [64 if p == 0 else 1 + 4 * RING for p in PATTERN]
    assert srv.pages_per_seq == 64 // PAGE + RING
    assert srv.kv.table_width(64) == srv.pages_per_seq
    srv.shutdown()


def test_prefix_cache_with_window_layers_is_refused(model):
    with pytest.raises(ValueError, match="ring cannot be shared"):
        GenerationServer(model, max_batch=2, page_size=PAGE, num_pages=16,
                         max_seq_len=32, prefix_cache=True, start=False)
    # not asked for: off, whatever the flag's default
    srv = GenerationServer(model, max_batch=2, page_size=PAGE, num_pages=16,
                           max_seq_len=32, start=False)
    assert srv.prefix is None
    srv.shutdown()


def test_a_group_over_the_prefill_budget_is_split(model):
    """Four prompts of one bucket, a budget of two rows at the largest
    bucket: two dispatches of two rows."""
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 128, n) for n in (9, 10, 11, 12)]
    _, _, snap = serve(model, prompts, 2, seq_buckets=[8, 16],
                       prefill_token_budget=32)
    pre = snap["engine"]["prefill"]
    assert pre["split_groups"] == 1
    assert pre["by_shape"] == {"2x16": 2}


def test_a_gpt_servers_groups_are_not_split():
    """The default budget leaves what the benchmark's GPT servers can
    dispatch as it was: 32 rows of 768, 16 rows of 1024."""
    from paddle_tpu.serving.generation.engine import PREFILL_TOKEN_BUDGET
    assert PREFILL_TOKEN_BUDGET // 768 >= 32
    assert PREFILL_TOKEN_BUDGET // 1024 >= 16
    paddle.seed(0)
    gpt = models.GPTForCausalLM(models.gpt_tiny())
    gpt.eval()
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, 256, n) for n in (9, 10, 11, 12)]
    _, _, snap = serve(gpt, prompts, 2, seq_buckets=[8, 16],
                       prefix_cache=False)
    pre = snap["engine"]["prefill"]
    assert pre["split_groups"] == 0 and pre["by_shape"] == {"4x16": 1}
    assert "moe" not in snap["engine"]


@pytest.mark.parametrize("masked", [False, True], ids=["all", "some-dead"])
def test_expert_layer_is_dropless(masked):
    """Against every expert computed for every token: whatever the
    routing, and when every token goes to ONE expert (a capacity would
    drop most), nothing is lost; dead rows touch no expert."""
    from paddle_tpu.ops.moe import dropless_moe
    rng = np.random.default_rng(7)
    t, h, e, i, k = 24, 16, 8, 12, 2
    x, r = rng.standard_normal((2, t, h)).astype(np.float32)
    wr = rng.standard_normal((h, e)).astype(np.float32)
    wg, wu = rng.standard_normal((2, e, h, i)).astype(np.float32)
    wd = rng.standard_normal((e, i, h)).astype(np.float32)
    valid = np.arange(t) % 3 != 0 if masked else None

    def dense(wr, k):
        s = r @ wr
        top = np.argsort(-s, axis=1)[:, :k]
        out = np.zeros((t, h), np.float32)
        for tok in range(t):
            w = np.exp(s[tok, top[tok]] - s[tok, top[tok]].max())
            w /= w.sum()
            for weight, ex in zip(w, top[tok]):
                act = np.maximum(x[tok] @ wg[ex], 0) * (x[tok] @ wu[ex])
                out[tok] += weight * (act @ wd[ex])
        return out if valid is None else out * valid[:, None]

    got, stats = dropless_moe(x, r, wr, wg, wu, wd, top_k=k, valid=valid)
    np.testing.assert_allclose(np.asarray(got), dense(wr, k), atol=1e-4)
    live = t if valid is None else int(valid.sum())
    assert int(stats["assignments"]) == live * k
    # every token to expert 3
    one = np.zeros((h, e), np.float32)
    one[:, 3] = 1.0
    r_pos = np.abs(r)
    got, stats = dropless_moe(x, r_pos, one, wg, wu, wd, top_k=1,
                              valid=valid)
    want = (np.maximum(x @ wg[3], 0) * (x @ wu[3])) @ wd[3]
    if valid is not None:
        want = want * valid[:, None]
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-4)
    assert int(stats["experts_touched"]) == 1
    assert int(stats["max_expert_load"]) == live


def test_parameter_count_at_full_depth_without_building():
    cfg = models.smallthinker_21ba3b()
    assert cfg.num_layers == 52 and cfg.num_params() == 21_506_562_560
    cut = models.smallthinker_21ba3b(num_layers=8, dtype="bfloat16")
    assert cut.num_params() == 3_966_937_600
    assert cut.rope_layout == cut.sliding_window_layout == PATTERN


@pytest.mark.parametrize("config", [tiny_config, models.gpt_tiny],
                         ids=["smallthinker-tiny", "gpt-tiny"])
def test_config_counts_what_the_model_holds(config):
    cfg = config()
    paddle.seed(0)
    assert models.GPTForCausalLM(cfg).num_params() == cfg.num_params()


def test_parameters_are_born_in_the_configs_dtype():
    paddle.seed(0)
    m = models.GPTForCausalLM(tiny_config(dtype="bfloat16"))
    assert {str(a.dtype) for a in state_arrays(m)[0].values()} == \
        {"bfloat16"}
    k, _ = m.init_kv_pools(8, PAGE)
    assert k[0].dtype == jnp.bfloat16 \
        and k[0].shape == kv_pool_shape(8, PAGE, 2, 16)
    assert m.kv_cache_spec()["kinds"]["window"] == {
        "layers": [1, 2, 3, 5, 6, 7], "window": WINDOW}


@pytest.mark.parametrize("field,value", [
    ("num_kv_heads", 2), ("norm", "rmsnorm"), ("position", "rope"),
    ("sliding_window", 8), ("moe_num_experts", 4), ("dtype", "bfloat16")])
def test_the_stacked_scan_refuses_each_new_field_by_name(field, value):
    extra = {"moe_top_k": 1, "moe_intermediate_size": 8} \
        if field == "moe_num_experts" else {}
    with pytest.raises(ValueError, match=field):
        models.gpt_tiny(stacked=True, **{field: value}, **extra)


def test_chip_smokes_cached_logits_phase_and_its_control():
    """``chip_smoke.py --phase smallthinker`` rehearsed at tiny widths:
    the program inside the tolerance, the float8 control outside."""
    import sys
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    import chip_smoke
    out = chip_smoke.phase_cached_logits(
        tiny_config(), prompt_len=23, new_tokens=30, seq_bucket=32,
        page_size=PAGE, tol=1e-3)
    assert out["program"] < 1e-4 and out["control"] > 1e-2
    assert out["same_greedy_token"] == out["rows"] == 31
    with pytest.raises(AssertionError, match="full forward"):
        chip_smoke.phase_cached_logits(
            tiny_config(), prompt_len=9, new_tokens=3, seq_bucket=16,
            page_size=PAGE, tol=1e-9)
