"""The Brumby block (power retention of degree 2 in attention's place)
through the serving path, at tiny widths on the CPU: hidden 64, 10 query
heads over 2 K/V heads of 8 (so ``D`` = 36), q/k RMSNorm, RoPE, a sigmoid
gate a K/V head, a SwiGLU MLP of 96, an untied head over 256 rows, chunks
of 8, float32.

The judge is ``benchmarks/reference/brumby.py``, which imports nothing of
the program and computes the quadratic form over every pair of
positions, with no feature map, no chunks and no cache: the op's chunked
prefill and its one-token recurrence against it and against each other,
the model's full forward, and prefill then decode through
``GenerationServer``'s state pools (logits, not tokens).
"""
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import models
from paddle_tpu.jit.functional import state_arrays
from paddle_tpu.ops.retention import (phi, retention_decode_pools,
                                      retention_prefill, state_dim)
from paddle_tpu.serving.generation import GenerationServer, PagedKVCache
from paddle_tpu.serving.generation.model_fns import CachedDecoder

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REFERENCE = os.path.join(ROOT, "benchmarks", "reference", "brumby.py")
CHUNK, VOCAB, LAYERS, KV, D = 8, 256, 2, 2, 36
SLOT = KV * (D * 8 + 5 * 8) * 4     # S [2, 36, 8] and z [2, 5, 8] float32


def tiny_config(**kw):
    d = dict(num_layers=LAYERS, hidden_size=64, num_heads=10,
             num_kv_heads=KV, head_dim=8, intermediate_size=96,
             vocab_size=VOCAB, max_seq_len=256)
    d.update(kw)
    return models.brumby_14b_base(**d)


def seeded(cfg, seed=7):
    """The model with every norm's weight moved off 1 (one left out
    would else go unseen) and chunks of ``CHUNK`` positions."""
    paddle.seed(seed)
    m = models.GPTForCausalLM(cfg)
    rng = np.random.default_rng(seed)
    for name, p in m.named_parameters():
        if name.endswith(("ln_1.weight", "ln_2.weight", "ln_f.weight",
                          "norm_w")):
            p.set_value(1.0 + 0.2 * rng.standard_normal(p.shape))
    for layer in m.gpt.layers:
        layer.attn.CHUNK = CHUNK
    m.eval()
    return m


@pytest.fixture(scope="module")
def reference():
    spec = importlib.util.spec_from_file_location("ref_brumby", REFERENCE)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def model():
    return seeded(tiny_config())


def test_reference_imports_nothing_of_the_program():
    src = open(REFERENCE).read()
    assert "import paddle_tpu" not in src and "from paddle_tpu" not in src
    # the quadratic form over every pair: no feature map, no state
    # carried from chunk to chunk
    assert "phi(" not in src and "lax.scan" not in src
    assert "cumsum(log_gate" in src


@pytest.mark.parametrize("d", [8, 128])
def test_phi_squares_the_dot_product(d):
    """``phi(q) . phi(k) = (q . k)^2``, ``D = d(d+1)/2`` (36; 8,256 at the
    published head of 128)."""
    rng = np.random.default_rng(d)
    q, k = rng.standard_normal((2, 5, d))
    got = np.asarray(jnp.sum(phi(q) * phi(k), axis=-1))
    assert phi(q).shape == (5, state_dim(d)) == (5, d * (d + 1) // 2)
    # float32 sums of D products: what is lost is a share of the
    # largest the products could add to, not of the result
    scale = np.sum(np.abs(q * k), -1) ** 2
    np.testing.assert_allclose(got, np.sum(q * k, -1) ** 2, rtol=0,
                               atol=1e-6 * scale.max())
    assert state_dim(128) == 8256


def _op_inputs(rows, length, seed=0):
    rng = np.random.default_rng(seed)
    g, d = 3, 8
    q = rng.standard_normal((rows, length, KV, g, d)).astype(np.float32)
    k, v = rng.standard_normal((2, rows, length, KV, d)).astype(np.float32)
    log_gate = np.log(rng.uniform(0.7, 0.999, (rows, length, KV))
                      ).astype(np.float32)
    return q, k, v, log_gate


def test_chunked_prefill_recurrence_and_quadratic_form_agree(reference):
    """Rows of 37 (four chunks and five positions), 20 and 1 real
    positions in one padded window of 37: the chunked prefill's outputs
    against the reference's quadratic form, and position by position
    against the one-token recurrence over the pools, whose states at
    each row's last real position are the prefill's."""
    q, k, v, log_gate = _op_inputs(3, 37)
    lens = np.array([37, 20, 1])
    valid = np.arange(37)[None] < lens[:, None]
    y, s, z = retention_prefill(q, k, v, log_gate, valid, chunk=CHUNK)
    for r, n in enumerate(lens):
        want = reference.retention(q[r, :n], k[r, :n], v[r, :n],
                                   log_gate[r, :n], eps=1e-6)
        # float32 on both sides, sums in other orders (outputs of
        # magnitude 1)
        np.testing.assert_allclose(y[r, :n], want, atol=2e-5, rtol=0)
    s_pool = jnp.zeros((4, KV, D, 8), jnp.float32)
    z_pool = jnp.zeros((4, KV, 5, 8), jnp.float32)
    for t in range(37):
        live = t < lens
        y_t, s_pool, z_pool = retention_decode_pools(
            q[:, t], k[:, t], v[:, t], log_gate[:, t], np.arange(1, 4),
            live, s_pool, z_pool)
        for r in np.flatnonzero(live):
            np.testing.assert_allclose(y_t[r], y[r, t], atol=2e-5, rtol=0)
    np.testing.assert_allclose(s_pool[1:], s, atol=2e-5, rtol=1e-6)
    np.testing.assert_allclose(z_pool[1:], z, atol=2e-5, rtol=1e-6)


@pytest.mark.parametrize("d", [8, 128])
def test_the_kernel_is_the_recurrence(d):
    """``retention_decode_pools`` through the Pallas kernel (interpreted)
    against the scan over lanes, lanes of slots 2 and 4 live and one
    dead: the same new states to float32 rounding, and outputs within
    the kernel's bfloat16 products (float32 accumulation: a few parts in
    a thousand of the outputs' scale, where the scan computes in float32
    here); the dead lane moves the trash slot alone."""
    rng = np.random.default_rng(d)
    b, g, big = 3, 5, state_dim(d)
    q = rng.standard_normal((b, KV, g, d)).astype(np.float32)
    k, v = rng.standard_normal((2, b, KV, d)).astype(np.float32)
    log_gate = np.log(rng.uniform(0.7, 0.999, (b, KV))).astype(np.float32)
    rows = d // 2 + 1
    s_pool = rng.standard_normal((5, KV, big, d)).astype(np.float32)
    z_pool = np.abs(rng.standard_normal((5, KV, rows, d))).astype(np.float32)
    z_pool[:, :, -1, d // 2:] = 0.0           # as a prefill leaves it
    slots, live = np.array([2, 4, 1]), np.array([True, True, False])
    got = retention_decode_pools(q, k, v, log_gate, slots, live,
                                 jnp.asarray(s_pool), jnp.asarray(z_pool),
                                 use_pallas=True)
    want = retention_decode_pools(q, k, v, log_gate, slots, live,
                                  jnp.asarray(s_pool), jnp.asarray(z_pool))
    scale = np.abs(np.asarray(want[0])[:2]).max()
    np.testing.assert_allclose(np.asarray(got[0])[:2],
                               np.asarray(want[0])[:2], rtol=0,
                               atol=8e-3 * scale)
    for pool_got, pool_want, was in zip(got[1:], want[1:],
                                        (s_pool, z_pool)):
        np.testing.assert_allclose(pool_got, pool_want, atol=1e-5,
                                   rtol=1e-6)
        for slot in (1, 3):
            np.testing.assert_array_equal(np.asarray(pool_got)[slot],
                                          was[slot])


def test_prefill_never_holds_a_per_chunk_state():
    """``retention_prefill`` at the published widths over ``[2, 512]``
    (four chunks a row), lowered: no array of the program holds more
    ``D x d`` states than the rows' own, so nothing keeps a state a
    chunk."""
    rows, length, kv, g, d = 2, 512, 8, 5, 128
    big = state_dim(d)

    def sds(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype)

    text = jax.jit(retention_prefill).lower(
        sds(rows, length, kv, g, d), sds(rows, length, kv, d),
        sds(rows, length, kv, d), sds(rows, length, kv, dtype=jnp.float32),
        sds(rows, length, dtype=jnp.bool_)).as_text()
    shapes = [tuple(int(n) for n in m.split("x"))
              for m in re.findall(r"tensor<((?:\d+x)+)f32>", text)
              for m in [m.rstrip("x")]]
    states = [s for s in shapes if s[-2:] == (big, d)]
    assert states, "no state in the program"
    assert max(int(np.prod(s)) for s in states) == rows * kv * big * d


def test_full_forward_matches_the_reference(model, reference):
    ids = np.random.default_rng(0).integers(0, VOCAB, (2, 29))
    params = state_arrays(model)[0]
    got = np.asarray(model(paddle.to_tensor(ids))._data)
    want = np.asarray(reference.logits(params, ids, model.config))
    # float32 on both sides, the chunked form summed in another order
    # (logits of std about 0.5)
    np.testing.assert_allclose(got, want, atol=5e-5, rtol=0)
    # the control computes something else: float8 moves it
    low = np.asarray(reference.control_logits(params, ids, model.config))
    assert np.abs(low - want).max() > 100 * np.abs(got - want).max()


def serve(model, prompts, max_new, **server_kw):
    """``prompts`` (of distinct lengths) served together. Returns the
    tokens, by prompt length the logits each token was chosen from, the
    last snapshot and the state slot each sequence held."""
    seen, slots = {}, {}
    kw = dict(max_batch=4, page_size=16, num_pages=2, max_seq_len=64,
              seq_buckets=[8, 16, 32], start=False)
    kw.update(server_kw)
    srv = GenerationServer(model, **kw)
    dispatch, enqueue = srv._dispatch, srv._runners[0].enqueue

    def note(seq, row, tokens, logits):
        seen.setdefault(len(seq.req.prompt), []).append(
            np.array(logits[row]))
        assert tokens[row] == logits[row].argmax()
        # no pages: the table row is the slot alone
        assert seq.pages == [] and srv._tables.shape[1] == 1
        assert srv._tables[seq.slot, 0] == seq.state_slot > 0
        slots[len(seq.req.prompt)] = seq.state_slot

    def spy(kind, feeds, seqs, *args, **kwargs):
        ran = dispatch(kind, feeds, seqs, *args, **kwargs)
        logits = np.asarray(ran.logits)
        for i, seq in enumerate(seqs):
            note(seq, i, ran.tokens, logits)
        return ran

    def step_spy(kind, feeds):
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


@pytest.mark.parametrize("use_pallas,atol", [(False, 1e-4), (True, 2e-3)],
                         ids=["pure-body", "kernel-interpreted"])
def test_prefill_then_decode_matches_the_reference_logits(
        model, reference, use_pallas, atol):
    """Prompts of 1, 7, 8 and 21 tokens (inside a chunk, one short of a
    chunk, a whole chunk, two chunks and five positions) in ONE padded
    prefill of [4, 32], so each row's state is the one at its last real
    position; then 20 decode steps, past three chunk boundaries, by the
    scan over lanes or by the kernel. Every served token's logits
    against the reference's full forward over the prompt and the tokens
    before it."""
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, VOCAB, n) for n in (1, 7, 8, 21)]
    tokens, seen, snap, slots = serve(model, prompts, 20, seq_buckets=[32],
                                      use_pallas=use_pallas)
    assert snap["engine"]["prefill"]["by_shape"] == {"4x32": 1}
    params = state_arrays(model)[0]
    for prompt, toks in zip(prompts, tokens):
        assert len(toks) == 20
        ids = np.concatenate([prompt, toks[:-1]])[None]
        want = np.asarray(reference.logits(
            params, ids, model.config,
            positions=np.arange(len(prompt) - 1, ids.shape[1])))[0]
        # float32 on both sides; the chunked form and the recurrence
        # only change the order of the sums (logits of std about 0.5:
        # 1e-4 is 2e-4 of it); the kernel's state products run in
        # bfloat16 (2e-3 is 4e-3 of it)
        np.testing.assert_allclose(np.stack(seen[len(prompt)]), want,
                                   atol=atol, rtol=0)
    assert sorted(slots.values()) == [1, 2, 3, 4]
    kv = snap["engine"]["kv"]
    assert kv["capacity"] == {"full": 1, "state": 4}
    assert kv["pages_in_use"] == {"full": 0, "state": 0}
    # 2 layers x 5 slots x (2 x 36 x 8 S + 2 x 5 x 8 z) x 4 B
    assert kv["pool_bytes"] == {"full": 0, "state": LAYERS * 5 * SLOT}


def _pools(kv):
    return [np.asarray(a) for pair in zip(kv.k, kv.v) for a in pair]


def _decoder(model, lanes=2, use_pallas=False):
    kv = PagedKVCache(model, num_pages=2, page_size=16, max_batch=lanes)
    dec = CachedDecoder(model, max_batch=lanes, page_size=16,
                        pages_per_seq=kv.table_width(64), max_positions=64,
                        donate=False, use_pallas=use_pallas)
    return kv, dec


def test_a_slots_state_after_prefill_is_the_recurrences(model):
    """A prompt of 19 tokens prefilled into one slot, and the same
    prompt fed into another one token at a time (a prefill of its first
    token, then 18 decode steps): both slots hold the same ``S`` and
    ``z`` of every layer."""
    kv, dec = _decoder(model)
    assert kv.table_width(64) == 1 and kv.alloc(kv.pages_for(64)) == []
    prompt = np.random.default_rng(8).integers(0, VOCAB, 19)
    tables = np.array([[kv.alloc_state()], [kv.alloc_state()]], np.int32)
    ids = np.zeros((2, 32), np.int64)
    ids[0, :19] = prompt
    ids[1, 0] = prompt[0]
    _, _, kv.k, kv.v, _ = dec.prefill(
        ids, np.array([19, 1], np.int32), tables, None, None, kv.k, kv.v)
    for t in range(1, 19):
        _, _, kv.k, kv.v, _ = dec.decode(
            np.array([0, prompt[t]], np.int64), np.array([0, t], np.int32),
            np.array([False, True]), np.array([0, t + 1], np.int32),
            tables, None, None, kv.k, kv.v)
    for pool in _pools(kv):
        assert np.abs(pool[1]).max() > 0
        np.testing.assert_allclose(pool[2], pool[1], atol=2e-5, rtol=1e-5)


@pytest.mark.parametrize("use_pallas", [False, True],
                         ids=["pure-body", "kernel-interpreted"])
@pytest.mark.parametrize("stale_row", [False, True],
                         ids=["zero-row", "stale-row"])
def test_a_dead_lane_writes_only_the_trash_slot(model, stale_row,
                                                use_pallas):
    """A decode step over two lanes of which one is dead: the slot a
    third, parked sequence holds keeps every number, whether the dead
    lane's table row is zeros (as the engine leaves a freed lane) or
    still names that slot; the live lane's slot moves."""
    kv, dec = _decoder(model, lanes=3, use_pallas=use_pallas)
    live_slot, parked = kv.alloc_state(), kv.alloc_state()
    tables = np.array([[live_slot], [parked]], np.int32)
    ids = np.zeros((2, 8), np.int64)
    ids[:, :6] = np.random.default_rng(5).integers(0, VOCAB, (2, 6))
    _, _, kv.k, kv.v, _ = dec.prefill(
        ids, np.array([6, 6], np.int32), tables, None, None, kv.k, kv.v)
    before = _pools(kv)
    lanes = np.array([[live_slot], [parked if stale_row else 0],
                      [0]], np.int32)
    _, _, kv.k, kv.v, _ = dec.decode(
        np.array([7, 9, 0], np.int64), np.array([6, 6, 0], np.int32),
        np.array([True, False, False]), np.array([7, 7, 0], np.int32),
        lanes, None, None, kv.k, kv.v)
    for was, now in zip(before, _pools(kv)):
        np.testing.assert_array_equal(was[parked], now[parked])
        assert np.abs(was[live_slot] - now[live_slot]).max() > 0
        moved = np.flatnonzero([np.abs(was[i] - now[i]).max() > 0
                                for i in range(len(was))])
        assert set(moved) <= {0, live_slot}


def test_the_server_refuses_what_a_state_cannot_do(model):
    """A slot holds the state after a sequence's last token alone: no
    prefix cache, no draft, and no window continued from a slot."""
    kw = dict(max_batch=2, page_size=16, num_pages=2, max_seq_len=64,
              seq_buckets=[16], start=False)
    with pytest.raises(ValueError, match="recurrent state"):
        GenerationServer(model, prefix_cache=True, **kw)
    srv = GenerationServer(model, **kw)
    assert srv.prefix is None
    feeds = (np.zeros((1, 8), np.int64), np.zeros(1, np.int32),
             np.full(1, 4, np.int32), np.zeros((1, srv.pages_per_seq),
                                               np.int32))
    with pytest.raises(NotImplementedError, match="'state' kind"):
        srv.decoder.prefill_chunked(*feeds, None, None, srv.kv.k, srv.kv.v)
    srv.shutdown()


def test_the_manager_refuses_state_pools_of_another_precision(model):
    """``retention_state_dtype`` is held from the arrays: a bfloat16
    pool under a spec that states float32 builds no cache manager."""
    spec = model.kv_cache_spec()["kinds"]["state"]
    assert spec == {"layers": [0, 1], "bytes_per_slot": LAYERS * SLOT}
    pools = model.init_kv_pools

    def halved(*args, **kw):
        k, v = pools(*args, **kw)
        return [a.astype(jnp.bfloat16) for a in k], v

    model.init_kv_pools = halved
    try:
        with pytest.raises(ValueError, match="bytes a slot and "
                           "kv_cache_spec\\(\\) states 5248"):
            PagedKVCache(model, num_pages=2, page_size=16, max_batch=2)
    finally:
        del model.init_kv_pools
    low = seeded(tiny_config(retention_state_dtype="bfloat16"))
    assert low.kv_cache_spec()["kinds"]["state"]["bytes_per_slot"] == \
        LAYERS * SLOT // 2
    assert str(PagedKVCache(low, num_pages=2, page_size=16,
                            max_batch=2).k[0].dtype) == "bfloat16"


def test_the_preset_counts_the_published_model():
    """40 layers of 330,352,904 parameters, embedding and head 1.556 B:
    "14B"; the five layers served, 3.21 B; a sequence's state at the
    published widths, 8 K/V heads x (8,256 x 128 + 65 x 128) float32 a
    layer (``z``'s 8,256 in rows of 128 lanes)."""
    cfg = models.brumby_14b_base()
    assert cfg.num_params() == 14_769_945_920
    assert cfg.retention_state_dim == 8256 and cfg.num_heads == 40
    cut = models.brumby_14b_base(num_layers=5)
    assert cut.num_params() == 3_207_594_280
    with paddle.LazyGuard():
        big = models.GPTForCausalLM(cut)
    assert big.kv_cache_spec()["kinds"]["state"]["bytes_per_slot"] == \
        5 * (33_816_576 + 266_240)
    paddle.seed(0)
    assert models.GPTForCausalLM(tiny_config()).num_params() == \
        tiny_config().num_params()


def test_the_seeded_gates_carry_a_state_far():
    """The gate's bias is drawn in [3, 8]: at initialisation a gate lies
    between about 0.95 and 0.9997, so a wrong state shows."""
    paddle.seed(5)
    attn = models.GPTForCausalLM(tiny_config(num_kv_heads=10)) \
        .gpt.layers[0].attn
    bias = np.asarray(attn.gate_b._data)
    assert 3.0 <= bias.min() and bias.max() <= 8.0 and bias.std() > 0.5


@pytest.mark.parametrize("field,value", [("retention_degree", 2),
                                         ("retention_eps", 1e-5)])
def test_the_stacked_scan_refuses_retention_by_name(field, value):
    with pytest.raises(ValueError, match=field):
        models.gpt_tiny(stacked=True, **{field: value})


def test_retention_takes_degree_two_and_no_window():
    with pytest.raises(ValueError, match="retention_degree must be"):
        tiny_config(retention_degree=3)
    with pytest.raises(ValueError, match="power retention takes"):
        tiny_config(sliding_window=8)
    with pytest.raises(ValueError, match="retention_gate gates"):
        models.gpt_tiny(retention_gate=True)
