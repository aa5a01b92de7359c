"""The GLM-4.7-Flash block through the serving path, at tiny widths on
the CPU: 3 layers (layer 0 a dense SwiGLU MLP, the others experts),
hidden 64, 4 heads of latent attention (queries through a rank-32
bottleneck, a 24-wide latent beside an 8-wide rotary key part that all
heads share; queries and keys 16 + 8, values 16), a router of 16
outputs of which this share holds experts 4..7, 4 a token chosen by
sigmoid scores plus a nonzero selection bias and weighted by the scores
normalised over the chosen and scaled 1.8, a shared expert, pages of 4
slots, float32.

The judge is ``benchmarks/reference/glm4_moe_lite.py``, which computes
attention in the expanded form and imports nothing of the program: the
model's full forward, prefill then decode through ``GenerationServer``'s
latent cache (logits, not tokens), one layer's absorbed decode against
its expanded form, and the sum over all the shares of a layer against
the uncut layer.
"""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import models
from paddle_tpu.jit.functional import state_arrays
from paddle_tpu.ops.moe import dropless_moe, route_sigmoid_norm
from paddle_tpu.serving.generation import GenerationServer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REFERENCE = os.path.join(ROOT, "benchmarks", "reference", "glm4_moe_lite.py")
PAGE = 4
SPARSE = (0, 1, 1)
WIDTH = 24 + 8                       # a token's cached row: latent | k_pe
LANES = 128                          # ... in whole lane tiles


def tiny_config(**kw):
    d = dict(vocab_size=128, hidden_size=64, num_layers=3, num_heads=4,
             max_seq_len=256, intermediate_size=96, norm="rmsnorm",
             layer_norm_eps=1e-5, bias=False, position="rope",
             rope_theta=1e6, q_lora_rank=32, kv_lora_rank=24,
             qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
             mlp_kind="swiglu", moe_layout=SPARSE, moe_num_experts=4,
             moe_router_experts=16, moe_expert_offset=4, moe_top_k=4,
             moe_intermediate_size=32, moe_shared_intermediate_size=32,
             moe_scoring="sigmoid_norm", moe_routed_scale=1.8,
             moe_selection_bias=True, moe_activation="silu",
             tie_word_embeddings=False)
    d.update(kw)
    return models.GPTConfig(**d)


def seeded(cfg, seed=11):
    """The model with every norm's weight moved off 1 (a norm whose
    weight is left out would else go unseen), routers wide enough that
    sigmoid scores differ, and a selection bias that changes which
    experts are chosen."""
    paddle.seed(seed)
    m = models.GPTForCausalLM(cfg)
    rng = np.random.default_rng(seed)
    for name, p in m.named_parameters():
        if name.endswith(("norm_w", "ln_1.weight", "ln_2.weight",
                          "ln_f.weight")):
            p.set_value(1.0 + 0.2 * rng.standard_normal(p.shape))
        elif name.endswith("router_w"):
            p.set_value(0.5 * rng.standard_normal(p.shape))
        elif name.endswith("router_bias"):
            p.set_value(0.3 * rng.standard_normal(p.shape))
    m.eval()
    return m


@pytest.fixture(scope="module")
def reference():
    spec = importlib.util.spec_from_file_location("ref_glm", REFERENCE)
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
    assert [type(layer.attn).__name__ for layer in model.gpt.layers] == \
        ["GPTLatentAttention"] * 3
    assert [type(layer.mlp).__name__ for layer in model.gpt.layers] == \
        ["GPTGatedMLP"] + ["GPTExpertMLP"] * 2
    attn = model.gpt.layers[0].attn
    assert tuple(attn.q_b_w.shape) == (32, 4 * (16 + 8))
    assert tuple(attn.kv_a_w.shape) == (64, WIDTH)
    assert tuple(attn.kv_b_w.shape) == (24, 4 * (16 + 16))
    assert tuple(attn.out_w.shape) == (4 * 16, 64)
    expert = model.gpt.layers[1].mlp
    assert tuple(expert.router_bias.shape) == (16,)
    assert tuple(expert.gate_w.shape) == (4, 64, 32)


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


def test_the_selection_bias_chooses_and_does_not_weigh():
    """Experts are the top 4 of ``s + b`` and are weighed by ``s``
    normalised over the chosen; a bias large enough to reorder the
    scores chooses other experts than ``s`` alone would."""
    rng = np.random.default_rng(6)
    x = rng.standard_normal((20, 16)).astype(np.float32)
    wr = rng.standard_normal((16, 32)).astype(np.float32)
    b = (0.5 * rng.standard_normal(32)).astype(np.float32)
    experts, weights = route_sigmoid_norm(x, wr, 4, 1.8, bias=b)
    s = 1.0 / (1.0 + np.exp(-(x.astype(np.float64) @ wr)))
    top = np.argsort(-(s + b), axis=1)[:, :4]
    assert np.array_equal(np.sort(np.asarray(experts), 1), np.sort(top, 1))
    assert not np.array_equal(np.sort(top, 1),
                              np.sort(np.argsort(-s, axis=1)[:, :4], 1))
    want = 1.8 * np.take_along_axis(s, np.asarray(experts), 1)
    want /= np.take_along_axis(s, top, 1).sum(1, keepdims=True)
    np.testing.assert_allclose(np.asarray(weights), want, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(weights).sum(1), 1.8, rtol=1e-5)
    # no bias is the router without one, and a zero bias chooses alike
    plain = route_sigmoid_norm(x, wr, 4, 1.8)
    zero = route_sigmoid_norm(x, wr, 4, 1.8, bias=np.zeros(32, np.float32))
    for a, z in zip(plain, zero):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(z))
    with pytest.raises(ValueError, match="sigmoid"):
        dropless_moe(x, x, wr, *rng.standard_normal((2, 4, 16, 8)),
                     rng.standard_normal((4, 8, 16)), top_k=4, bias=b)


def serve(model, prompts, max_new, **server_kw):
    """``prompts`` (of distinct lengths) served together. Returns the
    tokens, by prompt length the logits each token was chosen from (a
    prefill's row is the sequence's place in the call, a decode step's
    its lane), and the server after its shutdown."""
    seen = {}
    kw = dict(max_batch=4, page_size=PAGE, num_pages=64, max_seq_len=64,
              seq_buckets=[8, 16, 32], start=False)
    kw.update(server_kw)
    srv = GenerationServer(model, **kw)
    dispatch, enqueue = srv._dispatch, srv._runners[0].enqueue

    def note(seq, row, logits):
        seen.setdefault(len(seq.req.prompt), []).append(
            np.array(logits[row]))

    def spy(kind, feeds, seqs, *args, **kwargs):
        ran = dispatch(kind, feeds, seqs, *args, **kwargs)
        for i, seq in enumerate(seqs):
            note(seq, i, np.asarray(ran.logits))
        return ran

    def step_spy(kind, feeds):
        step = enqueue(kind, feeds)
        if kind == "decode":
            logits = np.asarray(step.logits)
            for lane in np.flatnonzero(feeds[2]):
                note(srv._slots[lane], lane, logits)
        return step

    srv._dispatch, srv._runners[0].enqueue = spy, step_spy
    futures = [srv.submit_generate(p, max_new_tokens=max_new)
               for p in prompts]
    srv.start()
    tokens = [f.result(timeout=300) for f in futures]
    srv.shutdown()
    return tokens, seen, srv


@pytest.mark.parametrize("use_pallas", [False, True],
                         ids=["pure-body", "kernels-interpreted"])
def test_prefill_then_decode_matches_the_reference_logits(
        model, reference, use_pallas):
    """Prompts of 5 and 23 tokens, 30 new tokens each, prefilled in the
    expanded form and decoded in the absorbed form through the latent
    pool: every token's logits against the reference's full forward.
    No page is held after the requests."""
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 128, n) for n in (5, 23)]
    tokens, seen, srv = serve(model, prompts, 30, use_pallas=use_pallas)
    params = state_arrays(model)[0]
    for prompt, toks in zip(prompts, tokens):
        assert len(toks) == 30
        ids = np.concatenate([prompt, toks[:-1]])[None]
        want = np.asarray(reference.logits(
            params, ids, model.config,
            positions=np.arange(len(prompt) - 1, ids.shape[1])))[0]
        got = np.stack(seen[len(prompt)])
        assert got.shape == want.shape
        # float32 on both sides; the cache, the absorbed form and the
        # sorted dispatch change only the order of the sums
        np.testing.assert_allclose(got, want, atol=1e-4, rtol=0)
    assert srv.kv.leak_check()["ok"] and srv.kv.used_pages == 0
    kv = srv.metrics_snapshot()["engine"]["kv"]
    assert kv["pages_in_use"] == {"full": 0}
    assert set(kv["pool_bytes"]) == {"latent"}


def test_absorbed_decode_matches_the_expanded_layer(model):
    """One layer: a 9-token prefill through the cache, then the 10th
    token decoded in the absorbed form, against the layer's expanded
    form over all ten tokens without a cache (the kernel and the pure
    body alike)."""
    from paddle_tpu.models.gpt import GPTKVCache
    attn = model.gpt.layers[1].attn
    rng = np.random.default_rng(4)
    h = rng.standard_normal((2, 10, 64)).astype(np.float32)
    want = np.asarray(attn(paddle.to_tensor(h))._data)
    tables = np.array([[1, 2, 3], [4, 5, 6]], np.int32)
    for use_pallas in (False, True):
        pool = model.init_kv_pools(8, PAGE)[0][1]

        def run(kind, x, at, pool):
            b, s = x.shape[:2]
            positions = np.broadcast_to(np.arange(at, at + s), (b, s))
            cache = GPTKVCache(
                kind, PAGE, paddle.to_tensor(pool), (),
                paddle.to_tensor(tables),
                paddle.to_tensor(np.full(b, at + s, np.int32)),
                paddle.to_tensor(np.ones((b, s), bool)),
                paddle.to_tensor(positions.astype(np.int32)),
                use_pallas=use_pallas)
            out, pool, v = attn(paddle.to_tensor(x), kv_cache=cache)
            assert v == ()
            return np.asarray(out._data), pool._data

        first, pool = run("prefill", h[:, :9], 0, pool)
        last, pool = run("decode", h[:, 9:], 9, pool)
        np.testing.assert_allclose(first, want[:, :9], atol=2e-5, rtol=0)
        np.testing.assert_allclose(last, want[:, 9:], atol=2e-5, rtol=0)
        # the pool holds [c | k_pe] a token, zeros past it, nothing else
        assert pool.shape == (8, PAGE, LANES)
        assert not np.asarray(pool)[..., WIDTH:].any()


def test_the_pool_is_a_576_wide_row_a_token_a_layer():
    """The published widths: 512 + 64 values a token a layer for all 20
    heads (K and V a head would take 20 x (256 + 256)), in a row of 640
    lanes, whole lane tiles, 2 bytes each in bfloat16 (the chip's tiled
    layout gives a 576-lane row 640 lanes too); the cache manager
    reports the pools as ``latent`` and refuses pools of another
    shape."""
    from paddle_tpu.serving.generation.kv_cache import PagedKVCache
    cfg = models.glm_4p7_flash(num_layers=2, moe_num_experts=8,
                               vocab_size=256)
    assert cfg.latent_width == 576
    with paddle.LazyGuard():
        m = models.GPTForCausalLM(cfg)
    spec = m.kv_cache_spec("bfloat16")
    assert spec["kinds"]["full"]["latent"] == 576
    assert spec["head_dim"] == 640 and spec["num_kv_heads"] == 1
    assert spec["kv_bytes_per_token"] == 2 * 640 * 2
    assert (20 * (256 + 256) * 2) / (576 * 2) == pytest.approx(17.78, 1e-3)
    tiny = seeded(tiny_config())
    kv = PagedKVCache(tiny, num_pages=9, page_size=PAGE, max_batch=2)
    assert [a.shape for a in kv.k] == [(9, PAGE, LANES)] * 3
    assert kv.v == [()] * 3
    assert kv.by_kind()["pool_bytes"] == {"latent": 3 * 9 * PAGE * LANES * 4}
    with pytest.raises(ValueError, match="latent pools hold"):
        PagedKVCache(tiny, num_pages=9, page_size=PAGE, max_batch=2,
                     dtype=jnp.bfloat16)
    with pytest.raises(ValueError, match="int8"):
        tiny.init_kv_pools(9, PAGE, "int8")


def test_windows_over_the_cache_are_refused_by_name(model):
    """A prefix hit's suffix prefill and a verify window attend several
    positions over the cache, which no latent pool serves: the server
    refuses the prefix cache and a draft, the decoder the two programs."""
    with pytest.raises(ValueError, match="latent"):
        GenerationServer(model, max_batch=2, page_size=PAGE, num_pages=16,
                         max_seq_len=32, seq_buckets=[8], prefix_cache=True,
                         start=False)
    with pytest.raises(ValueError, match="latent"):
        GenerationServer(model, max_batch=2, page_size=PAGE, num_pages=16,
                         max_seq_len=32, seq_buckets=[8], spec_k=2,
                         draft_model=seeded(tiny_config(), seed=12),
                         start=False)
    srv = GenerationServer(model, max_batch=2, page_size=PAGE, num_pages=16,
                           max_seq_len=32, seq_buckets=[8], start=False)
    assert srv.prefix is None
    z = np.zeros((2, 8), np.int32)
    with pytest.raises(NotImplementedError, match="latent"):
        srv.decoder.prefill_chunked(z, z[:, 0], z[:, 0], z, None, None,
                                    srv.kv.k, srv.kv.v)
    with pytest.raises(NotImplementedError, match="latent"):
        srv.decoder.verify(z, z[:, 0], z[:, 0], z, srv.kv.k, srv.kv.v)
    srv.shutdown()


def test_the_shares_add_up_to_the_uncut_layer(reference):
    """Eight shares of two experts each, chosen with a selection bias:
    the routed parts all eight compute, and the shared expert counted
    once, are the whole layer as the reference computes it with all
    sixteen experts held."""
    rng = np.random.default_rng(5)
    t, h, e, i, k, shares = 48, 32, 16, 24, 4, 8
    x = rng.standard_normal((t, h)).astype(np.float32)
    wr = rng.standard_normal((h, e)).astype(np.float32)
    br = (0.3 * rng.standard_normal(e)).astype(np.float32)
    wg, wu = 0.3 * rng.standard_normal((2, e, h, i)).astype(np.float32)
    wd = 0.3 * rng.standard_normal((e, i, h)).astype(np.float32)
    sg, su = 0.3 * rng.standard_normal((2, h, i)).astype(np.float32)
    sd = 0.3 * rng.standard_normal((i, h)).astype(np.float32)
    kw = dict(top_k=k, scoring="sigmoid_norm", scale=1.8, activation="silu",
              bias=br)
    held = e // shares
    total = np.zeros((t, h), np.float32)
    local = 0
    for r in range(shares):
        sl = slice(r * held, (r + 1) * held)
        part, stats = dropless_moe(
            x, x, wr, wg[sl], wu[sl], wd[sl], offset=r * held,
            shared=(sg, su, sd) if r == 0 else None, **kw)
        total += np.asarray(part)
        local += int(stats["local_assignments"])
    assert local == t * k                    # each computed exactly once
    whole = {"wr": wr, "br": br, "wg": wg, "wu": wu, "wd": wd, "sg": sg,
             "su": su, "sd": sd}
    with jax.default_matmul_precision("highest"):
        want = np.asarray(reference.experts(
            jnp.asarray(x), whole, top_k=k, scale=1.8, offset=0))
    np.testing.assert_allclose(total, want, atol=2e-5, rtol=0)


def test_parameter_count_at_full_depth_without_building():
    cfg = models.glm_4p7_flash()
    assert cfg.num_layers == 47 and cfg.moe_router_experts == 64
    assert cfg.head_dim == 256 and cfg.latent_width == 576
    assert cfg.num_params() == 29_943_393_920              # "30B"
    assert sum(cfg.layer_experts(i) for i in range(47)) == 46
    cut = models.glm_4p7_flash(num_layers=12, moe_num_experts=8,
                               vocab_size=19360, dtype="bfloat16")
    assert cut.num_params() == 1_339_098_816               # 2.49 GiB
    assert cut.moe_layout == (0,) + (1,) * 11


def test_config_counts_what_the_model_holds():
    cfg = tiny_config()
    paddle.seed(0)
    assert models.GPTForCausalLM(cfg).num_params() == cfg.num_params()


@pytest.mark.parametrize("field,value", [
    ("moe_selection_bias", True), ("q_lora_rank", 8), ("kv_lora_rank", 8),
    ("qk_nope_head_dim", 8), ("qk_rope_head_dim", 8), ("v_head_dim", 8)])
def test_the_stacked_scan_refuses_each_new_field_by_name(field, value):
    with pytest.raises(ValueError, match=field):
        models.gpt_tiny(stacked=True, **{field: value})


def test_a_latent_attention_needs_every_width():
    with pytest.raises(ValueError, match="latent attention needs"):
        tiny_config(v_head_dim=0)
    with pytest.raises(ValueError, match="latent attention needs"):
        tiny_config(sliding_window=8)
