"""The Nemotron-H block through the serving path, at tiny widths on the
CPU: the published layers 0-10 (``MEMEMEM*EME``: five state-space
mixers, five expert layers, one attention layer), hidden 64, 8 query
heads over 2 K/V heads of 16 with no positions, 8 state-space heads of
8 over 2 groups with a state of 16, kernel 4, chunks of 8, a router of
16 outputs of which this share holds experts 4..7, 5 a token by sigmoid
scores normalised over the chosen and scaled 5, ungated relu^2 experts
in a latent of 32 beside a shared expert, pages of 4 slots, float32.

The judge is ``benchmarks/reference/nemotron_h.py``, which imports
nothing of the program and computes the recurrence position by
position: the model's full forward, prefill then decode through
``GenerationServer``'s KV pool *and* state pool (logits, not tokens),
the chunked form against the sequential one, a slot that another
sequence held, and the sum over the shares of a layer against the uncut
layer.
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
from paddle_tpu.ops.moe import dropless_moe
from paddle_tpu.ops.ssm import (ssm_decode_pools, ssm_decode_step,
                                ssm_prefill)
from paddle_tpu.serving.generation import GenerationServer, PagedKVCache
from paddle_tpu.serving.generation.model_fns import CachedDecoder

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REFERENCE = os.path.join(ROOT, "benchmarks", "reference", "nemotron_h.py")
PAGE, CHUNK = 4, 8
M_LAYERS, E_LAYERS, A_LAYERS = [0, 2, 4, 6, 9], [1, 3, 5, 8, 10], [7]


def tiny_config(**kw):
    d = dict(vocab_size=128, hidden_size=64, num_layers=11, max_seq_len=256,
             num_heads=8, num_kv_heads=2, head_dim=16, mamba_num_heads=8,
             mamba_head_dim=8, ssm_state_size=16, mamba_n_groups=2,
             chunk_size=CHUNK, moe_num_experts=4, moe_router_experts=16,
             moe_expert_offset=4, moe_top_k=5, moe_latent_size=32,
             moe_intermediate_size=24, moe_shared_intermediate_size=48)
    d.update(kw)
    return models.NemotronHConfig(**d)


def seeded(cfg, seed=11):
    """The model with every norm's weight and ``D`` moved off 1 (one
    left out would else go unseen) and routers wide enough that sigmoid
    scores differ."""
    paddle.seed(seed)
    m = models.NemotronHForCausalLM(cfg)
    rng = np.random.default_rng(seed)
    for name, p in m.named_parameters():
        if name.endswith(("norm.weight", "norm_f.weight", "norm_w",
                          "d_skip")):
            p.set_value(1.0 + 0.2 * rng.standard_normal(p.shape))
        elif name.endswith("router_w"):
            p.set_value(0.5 * rng.standard_normal(p.shape))
    m.eval()
    return m


@pytest.fixture(scope="module")
def reference():
    spec = importlib.util.spec_from_file_location("ref_nemotron_h",
                                                  REFERENCE)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def model():
    # a served prefill of more than 32 tokens goes a row (or, at 16
    # positions, two rows) at a time
    m = seeded(tiny_config())
    m.prefill_block_tokens = 32
    return m


def test_reference_imports_nothing_of_the_program():
    src = open(REFERENCE).read()
    assert "import paddle_tpu" not in src and "from paddle_tpu" not in src
    # and its recurrence is a scan over positions, not the chunked form
    assert "jax.lax.scan(\n        step" in src and "cumsum" not in src


def test_the_layers_are_of_the_published_kinds(model):
    cfg = model.config
    assert cfg.kinds == "MEMEMEM*EME"
    assert [type(b.mixer).__name__ for b in model.backbone.layers] == [
        {"M": "NemotronHMamba", "E": "NemotronHExperts",
         "*": "GPTGroupedAttention"}[c] for c in cfg.kinds]
    assert (cfg.layers_of("M"), cfg.layers_of("E"), cfg.layers_of("*")) \
        == (M_LAYERS, E_LAYERS, A_LAYERS)
    mamba, experts = (model.backbone.layers[i].mixer for i in (0, 1))
    # [z | xBC | dt]: 64 + (64 + 2 * 2 * 16) + 8
    assert tuple(mamba.in_w.shape) == (64, 64 + 128 + 8)
    assert tuple(mamba.conv_w.shape) == (128, 4)
    assert tuple(mamba.norm_w.shape) == (64,)
    assert tuple(experts.router_w.shape) == (64, 16)
    assert tuple(experts.latent_down_w.shape) == (64, 32)
    assert tuple(experts.expert_up_w.shape) == (4, 32, 24)
    assert tuple(experts.expert_down_w.shape) == (4, 24, 32)
    assert tuple(experts.shared_up_w.shape) == (64, 48)
    attn = model.backbone.layers[7].mixer
    assert attn.rope_theta is None and attn.window is None


def test_the_seeded_steps_and_decays_are_neither_0_nor_1():
    """``dt_bias`` is the inverse softplus of a step in [0.001, 0.1],
    ``A_log`` the log of [1, 16], ``D`` 1: a decay that a wrong state
    shows in."""
    paddle.seed(5)
    mamba = models.NemotronHForCausalLM(
        tiny_config(mamba_num_heads=64, mamba_n_groups=2,
                    num_layers=1)).backbone.layers[0].mixer
    step = np.log1p(np.exp(np.asarray(mamba.dt_bias._data, np.float64)))
    assert 0.001 * 0.999 <= step.min() and step.max() <= 0.1 * 1.001
    a = np.exp(np.asarray(mamba.a_log._data, np.float64))
    assert 1.0 <= a.min() and a.max() <= 16.0 and a.std() > 1.0
    assert np.all(np.asarray(mamba.d_skip._data) == 1.0)


def test_full_forward_matches_the_reference(model, reference):
    ids = np.random.default_rng(0).integers(0, 128, (2, 40))
    params = state_arrays(model)[0]
    got = np.asarray(model(paddle.to_tensor(ids))._data)
    want = np.asarray(reference.logits(params, ids, model.config))
    # float32 on both sides, the chunked form and the sorted dispatch
    # summed in other orders than the scan and the loop over experts
    np.testing.assert_allclose(got, want, atol=5e-5, rtol=0)
    # the control computes something else: float8 moves it
    low = np.asarray(reference.control_logits(params, ids, model.config))
    assert np.abs(low - want).max() > 100 * np.abs(got - want).max()


def serve(model, prompts, max_new, **server_kw):
    """``prompts`` (of distinct lengths) served together. Returns the
    tokens, by prompt length the logits each token was chosen from, the
    last snapshot and the state slot each sequence held."""
    seen, slots = {}, {}
    kw = dict(max_batch=4, page_size=PAGE, num_pages=64, max_seq_len=64,
              seq_buckets=[8, 16, 32], start=False)
    kw.update(server_kw)
    srv = GenerationServer(model, **kw)
    dispatch, enqueue = srv._dispatch, srv._runners[0].enqueue

    def note(seq, row, tokens, logits):
        seen.setdefault(len(seq.req.prompt), []).append(
            np.array(logits[row]))
        assert tokens[row] == logits[row].argmax()
        # the slot is the cache manager's, in the row's last column
        assert 0 < seq.state_slot <= srv.max_batch
        assert srv._tables[seq.slot, -1] == seq.state_slot
        slots[len(seq.req.prompt)] = seq.state_slot

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
    return tokens, seen, snap, slots


def against_reference(reference, model, prompt, tokens, got, atol=1e-4):
    ids = np.concatenate([prompt, tokens[:-1]])[None]
    want = np.asarray(reference.logits(
        state_arrays(model)[0], ids, model.config,
        positions=np.arange(len(prompt) - 1, ids.shape[1])))[0]
    got = np.stack(got)
    assert got.shape == want.shape
    # float32 on both sides; the caches, the chunked form and the sorted
    # dispatch only change the order of the sums (logits of std about
    # 0.16: 1e-4 is 6e-4 of it)
    np.testing.assert_allclose(got, want, atol=atol, rtol=0)


@pytest.mark.parametrize("use_pallas", [False, True],
                         ids=["pure-body", "kernels-interpreted"])
def test_prefill_then_decode_matches_the_reference_logits(
        model, reference, use_pallas):
    """Prompts of 1, 2, 3 (shorter than the convolution's kernel: the
    tail has zeros before position 0) and 21 tokens (two chunks and
    five positions) in ONE padded prefill of [4, 32], so the state each
    row leaves is the one at its last real position; then 30 decode
    steps, almost four chunks past the longest prompt."""
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 128, n) for n in (1, 2, 3, 21)]
    tokens, seen, snap, slots = serve(model, prompts, 30,
                                      use_pallas=use_pallas,
                                      seq_buckets=[32])
    assert snap["engine"]["prefill"]["by_shape"] == {"4x32": 1}
    for prompt, toks in zip(prompts, tokens):
        assert len(toks) == 30
        against_reference(reference, model, prompt, toks,
                          seen[len(prompt)])
    assert sorted(slots.values()) == [1, 2, 3, 4]
    # the [4, 32] prefill ran a row at a time (``prefill_block_tokens``
    # 32): the same numbers as the window computed at once
    whole = seeded(tiny_config())
    assert whole.prefill_block_tokens == 1024
    tokens_whole, seen_whole, snap_whole, _ = serve(
        whole, prompts, 2, use_pallas=use_pallas, seq_buckets=[32])
    for n in (1, 2, 3, 21):
        np.testing.assert_allclose(seen[n][0], seen_whole[n][0],
                                   atol=2e-6, rtol=0)
    assert snap_whole["engine"]["moe"]["assignments"] == 5 * 5 * (27 + 4)
    # the decode step computes every held expert (4 lanes x 5 reach the
    # router's 16) and counts neither; the [4, 32] prefill, 640 sorted
    # rows of a share of 4 in 16, is one call a layer with a capacity
    # of 256
    moe_whole = snap_whole["engine"]["moe"]
    assert moe_whole["narrow_calls"] + moe_whole["wide_calls"] == 5
    kv = snap["engine"]["kv"]
    assert kv["capacity"] == {"full": 63, "state": 4}
    assert kv["pages_in_use"] == {"full": 0, "state": 0}
    # 5 state layers x 5 slots x (3 x 128 tail + 8 x 8 x 16 state) x 4 B
    assert kv["pool_bytes"] == {
        "full": 2 * 64 * PAGE * 2 * 16 * 4,
        "state": 5 * 5 * (3 * 128 + 8 * 8 * 16) * 4}
    moe = snap["engine"]["moe"]
    # five expert layers, five experts a token, wherever they live
    assert moe["assignments"] == 5 * 5 * (27 + 4 * 29)
    assert 0 < moe["local_assignments"] < moe["assignments"]
    # a row at a time: four calls a layer of 160 sorted rows, a capacity
    # of 128 (even routing gives 40)
    assert moe["narrow_calls"] + moe["wide_calls"] == 5 * 4
    assert moe["narrow_calls"] >= 5


def test_a_slot_is_clean(model, reference):
    """One lane, so one state slot: a sequence admitted into the slot a
    longer sequence just left gives the logits it gives on a fresh
    server (prefill writes the slot from zeros; it never reads it)."""
    rng = np.random.default_rng(4)
    long, short = rng.integers(0, 128, 27), rng.integers(0, 128, 5)
    tokens, seen, _, slots = serve(model, [long, short], 12, max_batch=1)
    assert slots == {27: 1, 5: 1}
    fresh_tokens, fresh, _, _ = serve(model, [short], 12, max_batch=1)
    assert tokens[1] == fresh_tokens[0]
    np.testing.assert_array_equal(np.stack(seen[5]), np.stack(fresh[5]))
    against_reference(reference, model, short, tokens[1], seen[5])


def _state_pools(kv):
    return [np.asarray(a) for i in M_LAYERS for a in (kv.k[i], kv.v[i])]


@pytest.mark.parametrize("stale_row", [False, True],
                         ids=["zero-row", "stale-row"])
def test_a_dead_lanes_step_changes_no_live_slot(model, stale_row):
    """A decode step over two lanes of which one is dead: the slot a
    third, parked sequence holds keeps every number, whether the dead
    lane's table row is zeros (as the engine leaves a freed lane) or
    still names that slot."""
    kv = PagedKVCache(model, num_pages=16, page_size=PAGE, max_batch=2)
    width = kv.table_width(32)
    dec = CachedDecoder(model, max_batch=2, page_size=PAGE,
                        pages_per_seq=width, max_positions=32, donate=False)
    tables = np.zeros((2, width), np.int32)
    kv.fill_row(tables[0], kv.alloc(8), [], kv.alloc_state())
    parked = kv.alloc_state()
    assert (tables[0, -1], parked) == (1, 2)
    rng = np.random.default_rng(5)
    ids = np.zeros((2, 8), np.int64)
    ids[:, :6] = rng.integers(0, 128, (2, 6))
    both = tables.copy()
    both[1, -1] = parked
    _, _, kv.k, kv.v, _ = dec.prefill(
        ids, np.array([6, 6], np.int32), both, None, None, kv.k, kv.v)
    before = _state_pools(kv)
    assert all(np.abs(a[parked]).max() > 0 for a in before)
    if stale_row:
        tables[1, -1] = parked
    _, _, kv.k, kv.v, _ = dec.decode(
        np.array([7, 9], np.int64), np.array([6, 6], np.int32),
        np.array([True, False]), np.array([7, 7], np.int32), tables,
        None, None, kv.k, kv.v)
    for was, now in zip(before, _state_pools(kv)):
        np.testing.assert_array_equal(was[parked], now[parked])
        assert np.abs(was[1] - now[1]).max() > 0       # the live lane's


def test_the_state_kind_is_counted_and_leaks_are_caught(model):
    kv = PagedKVCache(model, num_pages=8, page_size=PAGE, max_batch=3)
    assert kv.table_width(32) == 8 + 1 and kv.state_capacity == 3
    assert [tuple(a.shape) for a in (kv.k[0], kv.v[0])] == \
        [(4, 3, 128), (4, 8, 8, 16)]
    assert kv.k[1] == () and kv.v[1] == ()
    assert tuple(kv.k[7].shape) == (8, PAGE, 2 * 16)
    assert str(kv.v[0].dtype) == "float32"
    slots = [kv.alloc_state() for _ in range(3)]
    assert sorted(slots) == [1, 2, 3] and kv.alloc_state() is None
    assert kv.used_pages == 3
    assert kv.by_kind()["pages_in_use"] == {"full": 0, "state": 3}
    row = np.full(9, -1, np.int32)
    kv.fill_row(row, [5, 6], [], slots[1])
    assert row.tolist() == [5, 6, 0, 0, 0, 0, 0, 0, slots[1]]
    kv.release_state(slots[1])
    assert kv.alloc_state() == slots[1]
    with pytest.raises(RuntimeError, match="state slot"):
        kv.release_state(slots[0]), kv.release_state(slots[0])
    kv._state_held.discard(slots[2])        # lost: neither free nor held
    assert kv.leak_check()["leaked"] == 1
    with pytest.raises(AssertionError, match="leak"):
        kv.assert_no_leaks()
    with pytest.raises(ValueError, match="needs max_batch"):
        PagedKVCache(model, num_pages=8, page_size=PAGE)
    spec = model.kv_cache_spec()
    assert spec["kinds"] == {
        "full": {"layers": A_LAYERS, "window": None},
        "state": {"layers": M_LAYERS,
                  "bytes_per_slot": 5 * (3 * 128 + 8 * 8 * 16) * 4}}


# --------------------------------------------------- the op alone
def _op_inputs(seed, rows, length, heads=8, p=4, groups=2, n=16, k=4):
    rng = np.random.default_rng(seed)
    ch = heads * p + 2 * groups * n
    f = np.float32
    return dict(
        xbc=rng.standard_normal((rows, length, ch)).astype(f),
        dt=rng.standard_normal((rows, length, heads)).astype(f),
        weights=(0.5 * rng.standard_normal((ch, k)).astype(f),
                 0.1 * rng.standard_normal(ch).astype(f),
                 rng.standard_normal(heads).astype(f),
                 np.log(rng.uniform(1, 16, heads)).astype(f),
                 rng.standard_normal(heads).astype(f)),
        sizes=dict(heads=heads, head_dim=p, groups=groups, state=n))


def _sequential(xbc, dt, conv_w, conv_b, dt_bias, a_log, d_skip, *, heads,
                head_dim, groups, state):
    """The recurrence of the module's docstring over one row, in numpy
    float64, a position at a time."""
    t, ch = xbc.shape
    k = conv_w.shape[1]
    padded = np.concatenate([np.zeros((k - 1, ch)), xbc.astype(np.float64)])
    s = np.zeros((heads, head_dim, state))
    inner, gn, per = heads * head_dim, groups * state, heads // groups
    ys = []
    for i in range(t):
        c = conv_b + np.einsum("kc,ck->c", padded[i:i + k], conv_w)
        c = c / (1 + np.exp(-c))
        xs = c[:inner].reshape(heads, head_dim)
        b = np.repeat(c[inner:inner + gn].reshape(groups, state), per, 0)
        cm = np.repeat(c[inner + gn:].reshape(groups, state), per, 0)
        d = np.log1p(np.exp(dt[i].astype(np.float64) + dt_bias))
        a = np.exp(-np.exp(a_log.astype(np.float64)) * d)
        s = a[:, None, None] * s + d[:, None, None] * xs[:, :, None] \
            * b[:, None, :]
        ys.append(np.einsum("hpn,hn->hp", s, cm) + d_skip[:, None] * xs)
    return (np.stack(ys).reshape(t, inner), padded[t:t + k - 1], s)


@pytest.mark.parametrize("length,lens", [
    (21, (1, 2, 3, 19)),        # chunk boundaries at 8 and 16 inside
    (16, (16, 9, 8, 0))],       # whole chunks, one row dead
    ids=["ragged", "whole-chunks"])
def test_the_chunked_form_is_the_sequential_scan(length, lens):
    """Random ``dt`` and ``A`` over rows of different real lengths in
    one padded window: outputs at the real positions, and the tail and
    the state *at the last real position*, against the scan; and
    ``ssm_decode_step`` iterated from zeros against both."""
    given = _op_inputs(0, len(lens), length)
    lens = np.asarray(lens, np.int32)
    valid = np.arange(length)[None, :] < lens[:, None]
    y, tail, s = ssm_prefill(given["xbc"], given["dt"], valid, lens,
                             *given["weights"], chunk=CHUNK,
                             **given["sizes"])
    for r, n in enumerate(lens):
        if not n:           # a dead row leaves zeros in the trash slot
            assert not np.asarray(tail[r]).any()
            assert not np.asarray(s[r]).any()
            continue
        want_y, want_tail, want_s = _sequential(
            given["xbc"][r, :n], given["dt"][r, :n], *given["weights"],
            **given["sizes"])
        np.testing.assert_allclose(np.asarray(y[r, :n]), want_y,
                                   atol=2e-5, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(tail[r]), want_tail,
                                   atol=0, rtol=0)
        np.testing.assert_allclose(np.asarray(s[r]), want_s, atol=2e-5,
                                   rtol=1e-5)
        t_step = jnp.zeros((1,) + tail.shape[1:])
        s_step = jnp.zeros((1,) + s.shape[1:])
        for i in range(n):
            y_i, t_step, s_step = ssm_decode_step(
                given["xbc"][r:r + 1, i], given["dt"][r:r + 1, i], t_step,
                s_step, *given["weights"], **given["sizes"])
            np.testing.assert_allclose(np.asarray(y_i[0]), want_y[i],
                                       atol=2e-5, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(s_step[0]), want_s,
                                   atol=2e-5, rtol=1e-5)
        np.testing.assert_array_equal(np.asarray(t_step[0]),
                                      np.asarray(tail[r]))


def test_the_pools_step_is_the_step_in_place():
    """``ssm_decode_pools`` over slots in any order is
    ``ssm_decode_step`` on the gathered lanes; a dead lane writes the
    trash slot alone, and its output means nothing."""
    given = _op_inputs(1, 4, 1)
    rng = np.random.default_rng(2)
    tail_pool = rng.standard_normal((6, 3, 96)).astype(np.float32)
    s_pool = rng.standard_normal((6, 8, 4, 16)).astype(np.float32)
    slots = np.array([4, 2, 3, 5], np.int32)
    live = np.array([True, True, False, True])
    xbc, dt = given["xbc"][:, 0], given["dt"][:, 0]
    y, tails, states = ssm_decode_pools(
        xbc, dt, slots, live, jnp.asarray(tail_pool), jnp.asarray(s_pool),
        *given["weights"], **given["sizes"])
    want_y, want_tail, want_s = ssm_decode_step(
        xbc, dt, tail_pool[slots], s_pool[slots], *given["weights"],
        **given["sizes"])
    for lane in (0, 1, 3):
        np.testing.assert_allclose(np.asarray(y[lane]),
                                   np.asarray(want_y[lane]), atol=1e-6)
        np.testing.assert_array_equal(np.asarray(tails[slots[lane]]),
                                      np.asarray(want_tail[lane]))
        np.testing.assert_allclose(np.asarray(states[slots[lane]]),
                                   np.asarray(want_s[lane]), atol=1e-6)
    for untouched in (1, 3):        # a free slot, and the dead lane's
        np.testing.assert_array_equal(np.asarray(tails[untouched]),
                                      tail_pool[untouched])
        np.testing.assert_array_equal(np.asarray(states[untouched]),
                                      s_pool[untouched])


# ------------------------------------------------------ the share
def test_the_shares_add_up_to_the_uncut_layer(reference):
    """Four shares of four experts each: the routed parts all four
    compute, each through ``W_up``, and the shared expert counted once,
    are the whole layer as the reference computes it with all sixteen
    experts held."""
    rng = np.random.default_rng(5)
    t, h, lat, e, i, si, k, shares = 48, 32, 16, 16, 24, 40, 5, 4
    f = np.float32
    u = rng.standard_normal((t, h)).astype(f)
    whole = {"wr": rng.standard_normal((h, e)).astype(f),
             "wdn": 0.3 * rng.standard_normal((h, lat)).astype(f),
             "w1": 0.3 * rng.standard_normal((e, lat, i)).astype(f),
             "w2": 0.3 * rng.standard_normal((e, i, lat)).astype(f),
             "wup": 0.3 * rng.standard_normal((lat, h)).astype(f),
             "s1": 0.3 * rng.standard_normal((h, si)).astype(f),
             "s2": 0.3 * rng.standard_normal((si, h)).astype(f)}
    kw = dict(top_k=k, scoring="sigmoid_norm", scale=5.0,
              activation="relu2")
    held = e // shares
    latent = u @ whole["wdn"]
    total = np.square(np.maximum(u @ whole["s1"], 0)) @ whole["s2"]
    local = 0
    for r in range(shares):
        sl = slice(r * held, (r + 1) * held)
        part, stats = dropless_moe(
            latent, u, whole["wr"], None, whole["w1"][sl], whole["w2"][sl],
            offset=r * held, **kw)
        total = total + np.asarray(part) @ whole["wup"]
        local += int(stats["local_assignments"])
        assert int(stats["assignments"]) == t * k
        assert int(stats["experts_touched"]) <= held
    assert local == t * k                     # each computed exactly once
    with jax.default_matmul_precision("highest"):
        want = np.asarray(reference.experts(
            jnp.asarray(u), whole, top_k=k, scale=5.0, offset=0))
        # and one share of the reference is that share of the program
        sl = slice(2 * held, 3 * held)
        mine = np.asarray(reference.experts(
            jnp.asarray(u), dict(whole, w1=whole["w1"][sl],
                                 w2=whole["w2"][sl]),
            top_k=k, scale=5.0, offset=2 * held, shared=False))
    np.testing.assert_allclose(total, want, atol=3e-5, rtol=1e-5)
    part, _ = dropless_moe(latent, u, whole["wr"], None, whole["w1"][sl],
                           whole["w2"][sl], offset=2 * held, **kw)
    np.testing.assert_allclose(np.asarray(part) @ whole["wup"], mine,
                               atol=3e-5, rtol=1e-5)


def test_a_wide_decode_step_computes_every_held_expert(model):
    """Lanes that hand each of the router's experts a row or more in
    the mean (``lanes * top_k >= router experts``) take the two batched
    products over every held expert: ``dropless_moe``'s numbers and its
    counts, dead lanes and absent experts among them; fewer lanes, and
    every prefill, sort their rows by expert."""
    from paddle_tpu.models.nemotron_h import _every_held_expert
    rng = np.random.default_rng(5)
    t, wide, lat, inter, k, offset, scale = 24, 16, 8, 12, 3, 2, 5.0
    f = np.float32
    r = rng.standard_normal((t, wide)).astype(f)
    x = rng.standard_normal((t, lat)).astype(f)
    wr = rng.standard_normal((wide, 8)).astype(f)
    w1 = rng.standard_normal((3, lat, inter)).astype(f)
    w2 = rng.standard_normal((3, inter, lat)).astype(f)
    for valid in (None, np.arange(t) % 4 != 0):
        want, counts = dropless_moe(
            x, r, wr, None, w1, w2, top_k=k, scoring="sigmoid_norm",
            scale=scale, activation="relu2", offset=offset, valid=valid)
        got, stats = _every_held_expert(
            x, r, valid, wr, w1, w2, top_k=k, scale=scale, offset=offset)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=5e-4, rtol=1e-5)
        if valid is not None:
            assert not np.asarray(got)[~valid].any()
        # the same four counts; it hands no grouped product any rows,
        # so it counts neither a narrow nor a wide call
        assert {n: int(v) for n, v in stats.items()} == \
            {n: int(counts[n]) for n in stats}
        assert set(counts) - set(stats) == {"narrow_calls", "wide_calls"}
    # which of the two a program holds: 16 router outputs, 5 a token
    layer = model.backbone.layers[1]
    u = paddle.to_tensor(rng.standard_normal((4, 1, 64)).astype(f))

    def products(decode, rows):
        text = str(jax.make_jaxpr(
            lambda a: layer.mixer(paddle.to_tensor(a), decode=decode)[0]
            ._data)(u._data[:rows]))
        return "ragged_dot" in text

    assert not products(True, 4)        # 20 assignments: every expert
    assert products(True, 3)            # 15: sorted by expert
    assert products(False, 4)           # a prefill: sorted


# ------------------------------------------------------- refusals
def test_the_engine_refuses_what_a_state_slot_cannot_do_by_name(model):
    kw = dict(max_batch=2, page_size=PAGE, num_pages=16, max_seq_len=32,
              seq_buckets=[8], start=False)
    with pytest.raises(ValueError, match="prefix_cache=True.*'state' kind"):
        GenerationServer(model, prefix_cache=True, **kw)
    with pytest.raises(ValueError, match="a draft model.*'state' kind"):
        GenerationServer(model, draft_model=seeded(tiny_config()),
                         spec_k=2, **kw)
    srv = GenerationServer(model, **kw)      # nobody asked: no prefix cache
    assert srv.prefix is None
    feeds = (np.zeros((1, 8), np.int64), np.zeros(1, np.int32),
             np.full(1, 4, np.int32), np.zeros((1, srv.pages_per_seq),
                                               np.int32))
    with pytest.raises(NotImplementedError,
                       match="prefill_chunked with NemotronHForCausalLM.*"
                       "'state' kind"):
        srv.decoder.prefill_chunked(*feeds, None, None, srv.kv.k, srv.kv.v)
    with pytest.raises(NotImplementedError, match="verify with Nemotron"):
        srv.decoder.verify(*feeds, srv.kv.k, srv.kv.v)
    srv.shutdown()


def test_a_bfloat16_state_is_another_pool(model):
    """``ssm_state_dtype`` is what the chip smoke's second control
    turns: the slot then keeps S in bfloat16 and the logits move."""
    low = seeded(tiny_config(ssm_state_dtype="bfloat16"))
    _, v = low.init_kv_pools(4, PAGE, state_slots=2)
    assert str(v[0].dtype) == "bfloat16"
    prompts = [np.random.default_rng(6).integers(0, 128, 9)]
    _, seen, _, _ = serve(model, prompts, 8, max_batch=1)
    _, seen_low, _, _ = serve(low, prompts, 8, max_batch=1)
    # the prefill's logits read no stored state; the decode steps' do
    np.testing.assert_array_equal(seen[9][0], seen_low[9][0])
    assert np.abs(np.stack(seen[9][1:])
                  - np.stack(seen_low[9][1:])).max() > 5e-6
    # and it is another size, which the cache manager checks
    assert model.kv_cache_spec()["kinds"]["state"]["bytes_per_slot"] \
        == 5 * (3 * 128 + 8 * 8 * 16) * 4
    assert low.kv_cache_spec()["kinds"]["state"]["bytes_per_slot"] \
        == 5 * (3 * 128 * 4 + 8 * 8 * 16 * 2)


def test_the_manager_refuses_state_pools_of_another_precision(model):
    """The state's precision is held from the arrays: pools that are
    not the bytes a slot ``kv_cache_spec()`` states (a state kept in
    bfloat16 under a spec that says float32) build no cache manager, so
    no server and no run."""
    pools = model.init_kv_pools

    def halved(*args, **kw):
        k, v = pools(*args, **kw)
        return k, [a.astype(jnp.bfloat16) if i in M_LAYERS else a
                   for i, a in enumerate(v)]

    model.init_kv_pools = halved
    try:
        with pytest.raises(ValueError, match="bytes a slot and "
                           "kv_cache_spec\\(\\) states 28160"):
            PagedKVCache(model, num_pages=8, page_size=PAGE, max_batch=2)
    finally:
        del model.init_kv_pools
    PagedKVCache(model, num_pages=8, page_size=PAGE, max_batch=2)


# ------------------------------------------------------- the preset
def test_parameter_count_at_full_depth_without_building():
    cfg = models.nemotron_3_super_120b_a12b()
    assert (cfg.num_layers, len(cfg.pattern)) == (88, 88)
    assert [cfg.kinds.count(c) for c in "M*E"] == [40, 8, 40]
    assert cfg.num_params() == 120_668_687_360       # "120B"
    assert abs(cfg.num_params() / 1e9 - 120.67) < 0.005
    cut = models.nemotron_3_super_120b_a12b(
        num_layers=11, moe_num_experts=128, vocab_size=32768,
        dtype="bfloat16")
    assert cut.kinds == "MEMEMEM*EME"
    assert cut.num_params() == 4_648_161_152          # 8.66 GiB
    assert cut.moe_router_experts == 512 and cut.moe_top_k == 22
    # a sequence's state: 5 layers x (3 x 10240 x 2 B + 4 MiB)
    assert 5 * cut.state_bytes_per_slot(2) == 5 * (61440 + 4 * 2 ** 20)
    with pytest.raises(ValueError, match="not among the router's"):
        models.nemotron_3_super_120b_a12b(moe_num_experts=128,
                                          moe_expert_offset=400)


def test_config_counts_what_the_model_holds():
    cfg = tiny_config()
    paddle.seed(0)
    assert models.NemotronHForCausalLM(cfg).num_params() == cfg.num_params()


def test_chip_smokes_cached_logits_phase_and_both_controls():
    """``chip_smoke.py --phase nemotron`` rehearsed at tiny widths: the
    program inside the tolerance, the float8 control outside; the
    program with its SSM state in bfloat16 is read beside them (here,
    where all else is float32, it shows; on the chip, among bfloat16
    activations, it does not: PERF.md section 6, PR 35)."""
    import sys
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    import chip_smoke
    preset, cut, told = chip_smoke.CACHED_LOGITS_PHASES["nemotron"]
    assert cut == {"num_layers": 11, "moe_num_experts": 128,
                   "vocab_size": 32768, "dtype": "bfloat16"}
    assert getattr(models, preset)(**cut).kinds == "MEMEMEM*EME"
    out = chip_smoke.phase_cached_logits(
        tiny_config(), page_size=PAGE, **dict(
            told, prompt_len=23, new_tokens=30, seq_bucket=32, tol=2e-5))
    assert out["program"] < 1e-5 and out["control"] > 1e-2
    assert 2e-5 < out["state_in_bfloat16"] < out["control"]
    assert out["rows"] == 31
