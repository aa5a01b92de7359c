"""The K-EXAONE block through the serving path, at tiny widths on the
CPU: 5 layers under the published kinds (window, window, window, full,
window; layer 0 a dense SwiGLU MLP, the others experts), hidden 64, 8
query heads over 2 K/V heads of 16 with RMSNorm on q and k, a router of
16 outputs of which this share holds experts 4..7, 4 a token by sigmoid
scores normalised over the chosen and scaled 2.5, a shared expert,
window 8, pages of 4 slots, float32.

The judge is ``benchmarks/reference/kexaone.py``, which imports nothing
of the program: the model's full forward, prefill then decode through
``GenerationServer``'s cache (logits, not tokens) with rings that wrap,
and the sum over all the shares of a layer against the uncut layer.
"""
import importlib.util
import os

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import models
from paddle_tpu.jit.functional import state_arrays
from paddle_tpu.ops.moe import dropless_moe, route_sigmoid_norm
from paddle_tpu.serving.generation import GenerationServer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REFERENCE = os.path.join(ROOT, "benchmarks", "reference", "kexaone.py")
WINDOW, PAGE, RING = 8, 4, 3          # RING = ceil(8 / 4) + 1
WINDOWED = (1, 1, 1, 0, 1)
SPARSE = (0, 1, 1, 1, 1)


def tiny_config(**kw):
    d = dict(vocab_size=128, hidden_size=64, num_layers=5, num_heads=8,
             num_kv_heads=2, head_dim=16, max_seq_len=256,
             intermediate_size=96, norm="rmsnorm", layer_norm_eps=1e-5,
             bias=False, position="rope", rope_theta=1e6,
             rope_layout=WINDOWED, sliding_window=WINDOW,
             sliding_window_layout=WINDOWED, qk_norm=True,
             mlp_kind="swiglu", moe_layout=SPARSE, moe_num_experts=4,
             moe_router_experts=16, moe_expert_offset=4, moe_top_k=4,
             moe_intermediate_size=32, moe_shared_intermediate_size=32,
             moe_scoring="sigmoid_norm", moe_routed_scale=2.5,
             moe_activation="silu", tie_word_embeddings=False)
    d.update(kw)
    return models.GPTConfig(**d)


def seeded(cfg, seed=11):
    """The model with every norm's weight moved off 1 (a norm whose
    weight is left out would else go unseen) and routers wide enough
    that sigmoid scores differ."""
    paddle.seed(seed)
    m = models.GPTForCausalLM(cfg)
    rng = np.random.default_rng(seed)
    for name, p in m.named_parameters():
        if name.endswith(("norm_w", "ln_1.weight", "ln_2.weight",
                          "ln_f.weight")):
            p.set_value(1.0 + 0.2 * rng.standard_normal(p.shape))
        elif name.endswith("router_w"):
            p.set_value(0.5 * rng.standard_normal(p.shape))
    m.eval()
    return m


@pytest.fixture(scope="module")
def reference():
    spec = importlib.util.spec_from_file_location("ref_kexaone", REFERENCE)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def model():
    return seeded(tiny_config())


def test_reference_imports_nothing_of_the_program():
    src = open(REFERENCE).read()
    assert "import paddle_tpu" not in src and "from paddle_tpu" not in src


def test_the_layers_are_of_the_published_kinds(model):
    kinds = [type(layer.mlp).__name__ for layer in model.gpt.layers]
    assert kinds == ["GPTGatedMLP"] + ["GPTExpertMLP"] * 4
    assert [layer.attn.window for layer in model.gpt.layers] == \
        [WINDOW, WINDOW, WINDOW, None, WINDOW]
    assert [layer.attn.rope_theta is not None
            for layer in model.gpt.layers] == [True, True, True, False, True]
    expert = model.gpt.layers[1].mlp
    assert tuple(expert.router_w.shape) == (64, 16)
    assert tuple(expert.gate_w.shape) == (4, 64, 32)
    assert tuple(expert.shared_down_w.shape) == (32, 64)
    assert tuple(model.gpt.layers[0].attn.q_norm_w.shape) == (16,)


def test_full_forward_matches_the_reference(model, reference):
    ids = np.random.default_rng(0).integers(0, 128, (2, 40))
    params = state_arrays(model)[0]
    got = np.asarray(model(paddle.to_tensor(ids))._data)
    want = np.asarray(reference.logits(params, ids, model.config))
    # float32 on both sides, summed in other orders
    np.testing.assert_allclose(got, want, atol=5e-5, rtol=0)
    # the control computes something else: float8 moves it
    low = np.asarray(reference.control_logits(params, ids, model.config))
    assert np.abs(low - want).max() > 100 * np.abs(got - want).max()


@pytest.mark.parametrize("kept_float32", ["_fp8_weight", "_fp8"])
def test_the_control_rounds_weights_activations_and_the_stream(
        model, reference, kept_float32):
    """The control holds in float8 what the program holds in bfloat16:
    with the weights' rounding taken out, or the activations' and the
    residual stream's, it computes something else again, and with both
    out it is the reference."""
    ids = np.random.default_rng(1).integers(0, 128, (1, 32))
    params = state_arrays(model)[0]

    def control_without(*names):
        spec = importlib.util.spec_from_file_location("ref_part", REFERENCE)
        part = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(part)
        for name in names:
            setattr(part, name, part._f32)
        return np.asarray(part.control_logits(params, ids, model.config))

    whole, part = control_without(), control_without(kept_float32)
    plain = control_without("_fp8_weight", "_fp8")
    assert np.abs(part - plain).max() > 1e-3
    assert np.abs(whole - part).max() > 1e-3
    np.testing.assert_array_equal(
        plain, np.asarray(reference.logits(params, ids, model.config)))


def test_the_token_block_changes_no_number(reference):
    """An expert layer that computes 16 rows at a time (80 rows: five
    blocks) gives what the layer gives all at once, and counts the
    same."""
    ids = np.random.default_rng(1).integers(0, 128, (2, 40))
    whole, blocked = seeded(tiny_config()), \
        seeded(tiny_config(moe_token_block=16))
    a = np.asarray(whole(paddle.to_tensor(ids))._data)
    b = np.asarray(blocked(paddle.to_tensor(ids))._data)
    np.testing.assert_allclose(a, b, atol=2e-5, rtol=0)
    rng = np.random.default_rng(2)
    x = rng.standard_normal((40, 16)).astype(np.float32)
    wr = rng.standard_normal((16, 8)).astype(np.float32)
    wg, wu = rng.standard_normal((2, 4, 16, 12)).astype(np.float32)
    wd = rng.standard_normal((4, 12, 16)).astype(np.float32)
    valid = np.arange(40) % 5 != 0
    kw = dict(top_k=3, scoring="sigmoid_norm", scale=2.5,
              activation="silu", offset=2, valid=valid)
    one, s1 = dropless_moe(x, x, wr, wg, wu, wd, **kw)
    # 40 rows in blocks of 16: the last one padded with dead rows
    many, s2 = dropless_moe(x, x, wr, wg, wu, wd, token_block=16, **kw)
    np.testing.assert_allclose(np.asarray(one), np.asarray(many),
                               atol=1e-5, rtol=0)
    assert {k: int(v) for k, v in s1.items()} == \
        {k: int(v) for k, v in s2.items()}


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
    context of 60 is seven windows and a half, and a ring of 12 slots
    is gone round three times and more; the one full layer keeps it
    all."""
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
        # float32 on both sides; the cache, the ring and the sorted
        # dispatch only change the order of the sums (logits of std
        # about 0.5: 1e-4 is 2e-4 of it)
        np.testing.assert_allclose(got, want, atol=1e-4, rtol=0)
    kv = snap["engine"]["kv"]
    assert kv["capacity"] == {"full": 63, "window": 4 * RING}
    assert kv["pages_in_use"] == {"full": 0, "window": 0}
    assert kv["window_pages_recycled"] >= 3 * RING
    moe = snap["engine"]["moe"]
    # four expert layers, four experts a token, wherever they live
    assert moe["assignments"] == 4 * 4 * (5 + 23 + 2 * 36)
    # this share holds 4 of the router's 16
    assert 0 < moe["local_assignments"] < moe["assignments"]
    assert 0 < moe["experts_touched"] <= moe["local_assignments"]
    # one of the two counts a layer a program
    programs = sum(d["harvested"] for d in snap["engine"]["dispatch"].values())
    assert moe["narrow_calls"] + moe["wide_calls"] == 4 * programs


def test_a_prefill_hands_its_products_the_capacitys_rows(model, reference):
    """A 40-token prompt in a bucket of 64: 256 sorted rows a layer, a
    capacity of 128 (twice the 64 of even routing over 4 of 16 held),
    so the prefill's products are handed 128 rows; a decode step's 16
    rows are no more than one tile and are all handed in. The logits
    are the reference's either way."""
    prompt = np.random.default_rng(7).integers(0, 128, 40)
    tokens, seen, snap = serve(model, [prompt], 4, seq_buckets=[64],
                               max_seq_len=64)
    ids = np.concatenate([prompt, tokens[0][:-1]])[None]
    want = np.asarray(reference.logits(
        state_arrays(model)[0], ids, model.config,
        positions=np.arange(39, ids.shape[1])))[0]
    np.testing.assert_allclose(np.stack(seen[40]), want, atol=1e-4, rtol=0)
    moe, runs = snap["engine"]["moe"], snap["engine"]["dispatch"]
    assert runs["prefill"]["harvested"] == 1
    assert moe["narrow_calls"] == 4
    assert moe["wide_calls"] == 4 * runs["decode"]["harvested"]


def test_pools_are_one_full_layer_and_four_rings(model):
    srv = GenerationServer(model, max_batch=4, page_size=PAGE, num_pages=64,
                           max_seq_len=64, seq_buckets=[8], start=False)
    pages = [int(a.shape[0]) for a in srv.kv.k]
    assert pages == [1 + 4 * RING if w else 64 for w in WINDOWED]
    assert srv.pages_per_seq == 64 // PAGE + RING
    srv.shutdown()
    spec = model.kv_cache_spec()
    assert spec["kinds"]["window"] == {"layers": [0, 1, 2, 4],
                                       "window": WINDOW}
    assert spec["kinds"]["full"]["layers"] == [3]
    # the published window of 128 in 16-slot pages: a ring of 9
    from paddle_tpu.ops.paged_attention import ring_pages
    assert ring_pages(models.k_exaone_236b_a23b().sliding_window, 16) == 9


def test_the_shares_add_up_to_the_uncut_layer(reference):
    """Eight shares of two experts each: the routed parts all eight
    compute, and the shared expert counted once, are the whole layer as
    the reference computes it with all sixteen experts held."""
    rng = np.random.default_rng(5)
    t, h, e, i, k, shares = 48, 32, 16, 24, 4, 8
    x = rng.standard_normal((t, h)).astype(np.float32)
    wr = rng.standard_normal((h, e)).astype(np.float32)
    wg, wu = 0.3 * rng.standard_normal((2, e, h, i)).astype(np.float32)
    wd = 0.3 * rng.standard_normal((e, i, h)).astype(np.float32)
    sg, su = 0.3 * rng.standard_normal((2, h, i)).astype(np.float32)
    sd = 0.3 * rng.standard_normal((i, h)).astype(np.float32)
    kw = dict(top_k=k, scoring="sigmoid_norm", scale=2.5, activation="silu")
    held = e // shares
    total = np.zeros((t, h), np.float32)
    local = assignments = 0
    for r in range(shares):
        sl = slice(r * held, (r + 1) * held)
        part, stats = dropless_moe(
            x, x, wr, wg[sl], wu[sl], wd[sl], offset=r * held,
            shared=(sg, su, sd) if r == 0 else None, **kw)
        total += np.asarray(part)
        local += int(stats["local_assignments"])
        assignments = int(stats["assignments"])
        assert int(stats["experts_touched"]) <= held
    assert local == assignments == t * k      # each computed exactly once
    whole = {"wr": wr, "wg": wg, "wu": wu, "wd": wd, "sg": sg, "su": su,
             "sd": sd}
    import jax
    with jax.default_matmul_precision("highest"):
        want = np.asarray(reference.experts(
            jnp.asarray(x), whole, top_k=k, scale=2.5, offset=0))
    np.testing.assert_allclose(total, want, atol=2e-5, rtol=0)
    # and one share of the reference is that share of the program
    sl = slice(3 * held, 4 * held)
    with jax.default_matmul_precision("highest"):
        mine = np.asarray(reference.experts(
            jnp.asarray(x), dict(whole, wg=wg[sl], wu=wu[sl], wd=wd[sl]),
            top_k=k, scale=2.5, offset=3 * held, shared=False))
    part, _ = dropless_moe(x, x, wr, wg[sl], wu[sl], wd[sl],
                           offset=3 * held, **kw)
    np.testing.assert_allclose(np.asarray(part), mine, atol=2e-5, rtol=0)


def test_the_router_against_numpy():
    rng = np.random.default_rng(6)
    x = rng.standard_normal((20, 16)).astype(np.float32)
    wr = rng.standard_normal((16, 32)).astype(np.float32)
    experts, weights = route_sigmoid_norm(x, wr, 8, 2.5)
    s = 1.0 / (1.0 + np.exp(-(x.astype(np.float64) @ wr)))
    top = np.argsort(-s, axis=1)[:, :8]
    assert np.array_equal(np.sort(np.asarray(experts), 1), np.sort(top, 1))
    want = 2.5 * np.take_along_axis(s, np.asarray(experts), 1)
    want /= np.take_along_axis(s, top, 1).sum(1, keepdims=True)
    np.testing.assert_allclose(np.asarray(weights), want, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(weights).sum(1), 2.5, rtol=1e-5)


def test_dead_rows_and_rows_routed_elsewhere_touch_nothing():
    """A row that is not valid, and a row whose every choice lives on
    another chip, get zeros from the routed experts and are counted in
    no group; the shared expert still serves the second."""
    rng = np.random.default_rng(7)
    t, h, e, i = 12, 16, 8, 12
    x = rng.standard_normal((t, h)).astype(np.float32)
    # the router's scores follow the first 8 inputs: rows 0..5 prefer
    # experts 0..3, rows 6..11 experts 4..7
    x[:6, :4] += 8.0
    x[6:, 4:8] += 8.0
    wr = np.zeros((h, e), np.float32)
    wr[np.arange(e), np.arange(e)] = 1.0
    wg, wu = rng.standard_normal((2, 4, h, i)).astype(np.float32)
    wd = rng.standard_normal((4, i, h)).astype(np.float32)
    valid = np.ones(t, bool)
    valid[[0, 7]] = False
    kw = dict(top_k=4, scoring="sigmoid_norm", scale=2.5,
              activation="silu", valid=valid)
    out, stats = dropless_moe(x, x, wr, wg, wu, wd, offset=4, **kw)
    out = np.asarray(out)
    assert np.all(out[:6] == 0) and np.all(out[7] == 0)
    assert np.all(np.abs(out[[6, 8, 9, 10, 11]]).sum(1) > 0)
    assert int(stats["assignments"]) == 10 * 4
    assert int(stats["local_assignments"]) == 5 * 4
    assert int(stats["experts_touched"]) == 4
    assert int(stats["max_expert_load"]) == 5
    sh = [rng.standard_normal(s).astype(np.float32)
          for s in ((h, i), (h, i), (i, h))]
    both, _ = dropless_moe(x, x, wr, wg, wu, wd, offset=4, shared=sh, **kw)
    both = np.asarray(both)
    assert np.all(both[[0, 7]] == 0)
    assert np.all(np.abs(both[1:6]).sum(1) > 0)      # the shared expert's
    with pytest.raises(ValueError, match="not among the router's"):
        dropless_moe(x, x, wr, wg, wu, wd, top_k=4, offset=5)


def test_the_kernel_takes_a_short_prefill_whose_scores_would_not_fit():
    """Below ``FLAGS_flash_min_seqlen`` attention is dense unless its
    scores would take more than ``DENSE_SCORES_BYTES``: of this
    configuration's prefills that is 16 rows at 1,024 positions alone,
    and the largest dense prefills of the benchmark's other servers
    stay dense."""
    from unittest import mock

    import jax

    from paddle_tpu.framework import place
    from paddle_tpu.ops import flash_attention as fa

    def prefers(b, s, h, hk, d, dtype):
        q, k, v = (jax.ShapeDtypeStruct((b, s, n, d), dtype)
                   for n in (h, hk, hk))
        return fa.preferred(q, k, v, None, True)

    with mock.patch.object(place, "on_tpu", lambda: True):
        assert prefers(16, 1024, 64, 8, 128, jnp.bfloat16)
        assert not prefers(8, 1024, 64, 8, 128, jnp.bfloat16)
        assert not prefers(16, 512, 64, 8, 128, jnp.bfloat16)
        assert prefers(1, 2048, 64, 8, 128, jnp.bfloat16)
        assert not prefers(32, 768, 16, 16, 64, jnp.float32)   # gpt2-medium
        assert not prefers(16, 1024, 16, 16, 128, jnp.float32)  # gpt3-1p3b
        assert not prefers(4, 1024, 28, 4, 128, jnp.bfloat16)  # SmallThinker
    assert not prefers(16, 1024, 64, 8, 128, jnp.bfloat16)     # no TPU


def test_parameter_count_at_full_depth_without_building():
    cfg = models.k_exaone_236b_a23b()
    assert cfg.num_layers == 48 and cfg.moe_router_experts == 128
    assert cfg.num_params() == 236_571_150_336       # "236B"
    assert sum(cfg.layer_experts(i) for i in range(48)) == 47
    cut = models.k_exaone_236b_a23b(num_layers=5, moe_num_experts=16,
                                    vocab_size=19200, dtype="bfloat16")
    assert cut.num_params() == 3_712_027_904         # 6.91 GiB
    assert cut.sliding_window_layout == cut.rope_layout == WINDOWED
    assert cut.moe_layout == SPARSE and cut.moe_router_experts == 128


def test_config_counts_what_the_model_holds():
    cfg = tiny_config()
    paddle.seed(0)
    assert models.GPTForCausalLM(cfg).num_params() == cfg.num_params()


@pytest.mark.parametrize("field,value", [
    ("qk_norm", True), ("mlp_kind", "swiglu"), ("moe_layout", (0, 1)),
    ("moe_router_experts", 8), ("moe_expert_offset", 1),
    ("moe_scoring", "sigmoid_norm"), ("moe_routed_scale", 2.5),
    ("moe_activation", "silu"), ("moe_shared_intermediate_size", 8),
    ("moe_token_block", 64)])
def test_the_stacked_scan_refuses_each_new_field_by_name(field, value):
    with pytest.raises(ValueError, match=field):
        models.gpt_tiny(stacked=True, **{field: value})


def test_chip_smokes_cached_logits_phase_and_its_control():
    """``chip_smoke.py --phase kexaone`` rehearsed at tiny widths: the
    program inside the tolerance, the float8 control outside."""
    import sys
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    import chip_smoke
    out = chip_smoke.phase_cached_logits(
        tiny_config(), prompt_len=23, new_tokens=30, seq_bucket=32,
        page_size=PAGE, tol=1e-3, reference="kexaone")
    assert out["program"] < 1e-4 and out["control"] > 1e-2
    assert out["rows"] == 31
