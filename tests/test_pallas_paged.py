"""Fused Pallas paged serving kernels — interpret-mode parity vs the
pure-JAX gather reference (ops/paged_attention.py), quantized-pool
behavior through the serving stack, and the autotune interpret guard.

The kernels' contract (ops/pallas_paged_attention.py) is masking parity
for LIVE rows/positions: fully-dead lanes emit zeros where the
reference emits a uniform average of garbage — both are discarded by
the engine, so tests compare live outputs only and merely assert dead
outputs stay finite.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import autotune
from paddle_tpu.ops.paged_attention import (
    _chunked_attention, _decode_attention, dequantize_kv, gather_pool,
    kv_pool_bytes, kv_pool_shape, paged_attention_update,
    quantize_kv_rows, resolve_kv_dtype)
from paddle_tpu.ops.pallas_paged_attention import (
    decode_copies_pages, paged_attention, supported)

H, D, PS = 4, 16, 8       # heads, head_dim, page_size


def _pools(num_pages, seed, scale=1.0):
    rng = np.random.RandomState(seed)
    shape = kv_pool_shape(num_pages, PS, H, D)
    return (jnp.asarray(rng.randn(*shape) * scale, jnp.float32),
            jnp.asarray(rng.randn(*shape) * scale, jnp.float32))


def _quantize_pool(pool, heads=H):
    """A float pool's values as the quantized pair, a head at a time."""
    p, ps, lanes = pool.shape
    vals, scales = quantize_kv_rows(
        pool.reshape(p * ps, heads, lanes // heads))
    return (vals.reshape(pool.shape),
            scales.reshape(kv_pool_shape(p, ps, heads)))


def _dequantize_pool(pool):
    """The quantized pair's values as a float32 pool."""
    vals, scales = pool
    return dequantize_kv(vals.reshape(*scales.shape, -1),
                         scales).reshape(vals.shape)


def _decode_case(seed=0, trash=0.0):
    """3 rows over 4 pages each (+ trash page 0); row 2 is dead."""
    B, P = 3, 4
    kp, vp = _pools(1 + B * P, seed)
    if trash:
        # garbage on the trash page must never reach a live output
        kp = kp.at[0].set(trash)
        vp = vp.at[0].set(trash)
    tables = np.zeros((B, P), np.int32)
    tables[0] = 1 + np.arange(P)
    tables[1] = 1 + P + np.arange(P)
    tables[1, 2:] = 0          # unallocated tail -> trash page
    ctx = np.array([PS * P, PS + 3, 0], np.int32)
    rng = np.random.RandomState(seed + 100)
    q = jnp.asarray(rng.randn(B, 1, H, D), jnp.float32)
    return q, kp, vp, jnp.asarray(tables), jnp.asarray(ctx)


def _decode_ref(q, kp, vp, tables, ctx, scale):
    ks = gather_pool(kp, tables, q.shape[2], out_dtype=q.dtype)
    vs = gather_pool(vp, tables, q.shape[2], out_dtype=q.dtype)
    return _decode_attention(q, ks, vs, ctx, scale)


SCALE = 1.0 / np.sqrt(D)


@pytest.mark.parametrize("trash", [0.0, 1e4])
def test_decode_parity_and_trash_isolation(trash):
    q, kp, vp, tables, ctx = _decode_case(trash=trash)
    val = jnp.ones((q.shape[0], 1), jnp.int32)
    pos = jnp.maximum(ctx - 1, 0)[:, None]
    out = paged_attention(q, kp, vp, tables, ctx, val, pos,
                          page_size=PS, kind="decode", scale=SCALE)
    ref = _decode_ref(q, kp, vp, tables, ctx, SCALE)
    live = np.asarray(ctx) > 0
    np.testing.assert_allclose(np.asarray(out)[live],
                               np.asarray(ref)[live],
                               rtol=2e-5, atol=2e-5)
    # the dead lane (ctx 0) emits zeros, never NaN/Inf
    assert np.all(np.isfinite(np.asarray(out)))
    assert np.allclose(np.asarray(out)[~live], 0.0)


def test_decode_tiled_variants_identical():
    q, kp, vp, tables, ctx = _decode_case()
    val = jnp.ones((q.shape[0], 1), jnp.int32)
    pos = jnp.maximum(ctx - 1, 0)[:, None]
    base = paged_attention(q, kp, vp, tables, ctx, val, pos,
                           page_size=PS, kind="decode", scale=SCALE)
    for bh, ppt in [(2, 1), (1, 2), (4, 4), (2, 2)]:
        out = paged_attention(q, kp, vp, tables, ctx, val, pos,
                              page_size=PS, kind="decode", scale=SCALE,
                              block_h=bh, pages_per_tile=ppt)
        np.testing.assert_allclose(np.asarray(out), np.asarray(base),
                                   rtol=1e-6, atol=1e-6)


def test_chunked_parity_cow_shared_tables():
    """Two rows share their prefix pages (prefix-cache COW layout);
    suffix positions start mid-sequence; padded tail is invalid."""
    B, P, S = 2, 4, 8
    kp, vp = _pools(1 + 2 + 2 * 2, 0)   # 2 shared + 2 private per row
    tables = np.zeros((B, P), np.int32)
    tables[0] = [1, 2, 3, 4]            # pages 1,2 shared
    tables[1] = [1, 2, 5, 6]
    start = np.array([2 * PS, 2 * PS + 3], np.int32)
    seg = np.array([S, S - 3], np.int32)
    offs = np.arange(S, dtype=np.int32)[None, :]
    pos = jnp.asarray(start[:, None] + offs)
    val = jnp.asarray((offs < seg[:, None]).astype(np.int32))
    ctx = jnp.asarray(start + seg)
    rng = np.random.RandomState(7)
    q = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    tables = jnp.asarray(tables)
    out = paged_attention(q, kp, vp, tables, ctx, val, pos,
                          page_size=PS, kind="chunked", scale=SCALE)
    ks = gather_pool(kp, tables, q.shape[2], out_dtype=q.dtype)
    vs = gather_pool(vp, tables, q.shape[2], out_dtype=q.dtype)
    ref = _chunked_attention(q, ks, vs, pos, np.asarray(val) > 0, SCALE)
    liv = np.asarray(val) > 0
    np.testing.assert_allclose(np.asarray(out)[liv], np.asarray(ref)[liv],
                               rtol=2e-5, atol=2e-5)
    assert np.all(np.isfinite(np.asarray(out)))


def test_chunked_block_q_tiling_identical():
    B, P, S = 2, 2, 8
    kp, vp = _pools(1 + B * P, 3)
    tables = jnp.asarray(
        np.arange(1, 1 + B * P, dtype=np.int32).reshape(B, P))
    start = np.array([0, 5], np.int32)
    seg = np.array([S, S], np.int32)
    offs = np.arange(S, dtype=np.int32)[None, :]
    pos = jnp.asarray(start[:, None] + offs)
    val = jnp.asarray((pos < PS * P).astype(np.int32) * 1)
    ctx = jnp.minimum(jnp.asarray(start + seg), PS * P)
    rng = np.random.RandomState(11)
    q = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    base = paged_attention(q, kp, vp, tables, ctx, val, pos,
                           page_size=PS, kind="chunked", scale=SCALE)
    for bq in (2, 4, 8):
        out = paged_attention(q, kp, vp, tables, ctx, val, pos,
                              page_size=PS, kind="chunked", scale=SCALE,
                              block_q=bq)
        np.testing.assert_allclose(np.asarray(out), np.asarray(base),
                                   rtol=1e-6, atol=1e-6)


def test_quantized_decode_matches_dequantized_reference():
    q, kp, vp, tables, ctx = _decode_case(seed=5)
    val = jnp.ones((q.shape[0], 1), jnp.int32)
    pos = jnp.maximum(ctx - 1, 0)[:, None]
    kq, vq = _quantize_pool(kp), _quantize_pool(vp)
    out = paged_attention(q, kq, vq, tables, ctx, val, pos,
                          page_size=PS, kind="decode", scale=SCALE)
    # oracle: the SAME int8 data dequantized, through the pure path
    kd, vd = _dequantize_pool(kq), _dequantize_pool(vq)
    ref = _decode_ref(q, kd, vd, tables, ctx, SCALE)
    live = np.asarray(ctx) > 0
    np.testing.assert_allclose(np.asarray(out)[live],
                               np.asarray(ref)[live],
                               rtol=2e-5, atol=2e-5)


def test_update_dispatch_parity_all_kinds():
    """paged_attention_update(use_pallas=True) against the pure
    reference for every kind, through the real write-then-attend flow."""
    B, P = 2, 2
    rng = np.random.RandomState(2)

    def pools():
        shape = kv_pool_shape(1 + B * P, PS, H, D)
        return jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32)

    tables = jnp.asarray(
        np.arange(1, 1 + B * P, dtype=np.int32).reshape(B, P))
    for kind, s, start in [("prefill", PS, [0, 0]),
                           ("chunked", 4, [3, 6]),
                           ("decode", 1, [9, 11])]:
        q = jnp.asarray(rng.randn(B, s, H, D), jnp.float32)
        k = jnp.asarray(rng.randn(B, s, H, D), jnp.float32)
        v = jnp.asarray(rng.randn(B, s, H, D), jnp.float32)
        offs = np.arange(s, dtype=np.int32)[None, :]
        pos = jnp.asarray(np.asarray(start)[:, None] + offs)
        val = jnp.ones((B, s), jnp.int32)
        ctx = jnp.asarray(np.asarray(start) + s, jnp.int32)
        outs = {}
        for up in (False, True):
            kp, vp = pools()
            out, kp2, vp2 = paged_attention_update(
                q, k, v, kp, vp, tables, ctx, val, pos,
                page_size=PS, kind=kind, use_pallas=up)
            outs[up] = (np.asarray(out), np.asarray(kp2),
                        np.asarray(vp2))
        np.testing.assert_allclose(outs[True][0], outs[False][0],
                                   rtol=2e-5, atol=2e-5, err_msg=kind)
        # pool writes are shared code — bit-identical
        np.testing.assert_array_equal(outs[True][1], outs[False][1])
        np.testing.assert_array_equal(outs[True][2], outs[False][2])


@pytest.mark.parametrize("use_pallas", [None, False, True])
def test_prefill_is_the_same_program_whoever_attends_decode(use_pallas):
    """Prefill reads no pool: it lowers to the same program (the
    dense/flash ``attention_bshd`` and the pool write) under every
    ``use_pallas``, so the decode kernel's arrival moved no prefill."""
    B, P, S = 2, 2, PS
    q = jnp.zeros((B, S, H, D), jnp.float32)
    pool = jnp.zeros(kv_pool_shape(1 + B * P, PS, H, D), jnp.float32)
    tables = jnp.asarray(
        np.arange(1, 1 + B * P, dtype=np.int32).reshape(B, P))
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    args = (q, q, q, pool, pool, tables, jnp.full((B,), S, jnp.int32),
            jnp.ones((B, S), bool), pos)

    def lowered(up):
        return jax.jit(lambda *a: paged_attention_update(
            *a, page_size=PS, kind="prefill", use_pallas=up)
        ).lower(*args).as_text()

    assert lowered(use_pallas) == lowered(False)


def test_supported_gates():
    kp, _ = _pools(3, 0)
    t = jnp.zeros((2, 2), jnp.int32)
    q = jnp.zeros((2, 1, H, D))
    assert supported(q, kp, t, PS, "decode")
    assert not supported(q, kp, t, 1, "decode")
    assert supported(q, (jnp.zeros(kv_pool_shape(3, PS, H, D), jnp.int8),
                         jnp.zeros(kv_pool_shape(3, PS, H))), t, PS,
                     "chunked")
    assert not supported(q, kp, t, PS, "prefill")
    assert not supported(q[0], kp, t, PS, "decode")


# ---------------------------------------------------------- quantization

def test_quantize_roundtrip_properties():
    rng = np.random.RandomState(9)
    kv = jnp.asarray(rng.randn(32, H, D) * 3, jnp.float32)
    vals, scales = quantize_kv_rows(kv)
    assert vals.dtype == jnp.int8 and scales.dtype == jnp.float32
    back = dequantize_kv(vals, scales)
    absmax = np.abs(np.asarray(kv)).max(axis=-1)
    # absmax/127 quantization step: half-step roundtrip bound per slot
    err = np.abs(np.asarray(back) - np.asarray(kv)).max(axis=-1)
    assert np.all(err <= absmax / 127 * 0.5 + 1e-7)
    # all-zero rows stay exactly zero (scale 0, no div-by-zero)
    zvals, zscales = quantize_kv_rows(jnp.zeros((4, H, D)))
    assert np.all(np.asarray(zscales) == 0)
    assert np.all(np.asarray(dequantize_kv(zvals, zscales)) == 0)


def test_kv_pool_bytes_ratio():
    f32 = kv_pool_bytes(64, PS, H, 64, None)
    i8 = kv_pool_bytes(64, PS, H, 64, "int8")
    bf16 = kv_pool_bytes(64, PS, H, 64, "bfloat16")
    assert f32 / i8 == pytest.approx(4 / (1 + 4 / 64))   # 3.76x @ D=64
    assert f32 / bf16 == 2.0
    with pytest.raises(ValueError):
        resolve_kv_dtype("int4")


# ------------------------------------------------------------- autotune

def test_autotune_interpret_guard():
    """Interpret mode (CPU tier-1) must never reach the timer: the
    enabled() gate is platform-based, pick() then returns the first
    candidate without ever building a kernel, and pretune is a no-op."""
    assert jax.devices()[0].platform == "cpu"
    assert not autotune.enabled()

    def boom(cand):
        raise AssertionError("autotune timed a kernel in interpret mode")

    got = autotune.pick("paged_test_guard", ("k", 1),
                        [(1, 1, 1), (1, 2, 1)], boom, ())
    assert got == (1, 1, 1)
    # the paged kernels' blocks are constants: nothing of theirs can
    # reach the timer on any backend
    import paddle_tpu.ops.pallas_paged_attention as ppa
    assert not hasattr(ppa, "pretune_paged")


def test_paged_block_candidates_legal():
    """Every candidate is a tile the TPU lowering takes: a block dim is
    the whole array's or a multiple of the native tile — 8 for the
    heads and window rows, 128 where heads is the minor dim (the
    quantized pools' scale blocks). gpt3_1p3b's 16 heads included."""
    for heads in (H, 16, 32):
        for quantized in (False, True):
            for kind, seq in [("decode", 1), ("chunked", 5),
                              ("chunked", 24), ("chunked", 128)]:
                cands = autotune.paged_block_candidates(
                    kind, seq, heads, D, PS, 4, quantized=quantized)
                assert cands
                for bq, bh, ppt in cands:
                    assert seq % bq == 0 and heads % bh == 0 \
                        and 4 % ppt == 0
                    assert bh == heads or \
                        bh % (128 if quantized else 8) == 0
                    assert bq == seq or bq % 8 == 0
    assert (1, 8, 1) in autotune.paged_block_candidates(
        "decode", 1, 16, D, PS, 4)


def test_paged_blocks_defaults_and_override_validation():
    # decode: the largest tile up to the constant that divides the table
    assert autotune.paged_blocks("decode", 1, H, D, PS, 4) == (1, H, 4)
    assert autotune.paged_blocks("decode", 1, H, D, PS, 128) == (
        1, H, autotune.PAGED_DECODE_PAGES_PER_TILE)
    assert autotune.paged_blocks("decode", 1, H, D, PS, 3) == (1, H, 1)
    assert autotune.paged_blocks("chunked", 24, H, D, PS, 4) == (8, H, 1)
    # a window no 8-multiple divides is taken whole
    assert autotune.paged_blocks("chunked", 5, H, D, PS, 4) == (5, H, 1)
    with pytest.raises(ValueError):
        autotune.paged_blocks("chunked", 24, H, D, PS, 4,
                              overrides=(5, None, None))
    with pytest.raises(ValueError):
        autotune.paged_blocks("decode", 1, H, D, PS, 4,
                              overrides=(None, 3, None))


# ------------------------------------------------- serving-stack parity

def _tiny_model(seed=1234):
    # deterministic init: greedy-parity assertions must not ride on a
    # lucky draw (near-tie argmaxes can legitimately flip under the
    # quantization error; a fixed model keeps the margin stable)
    from paddle_tpu.framework.random import seed as set_seed
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny
    set_seed(seed)
    return GPTForCausalLM(gpt_tiny())


def test_int8_logits_parity_through_cached_decoder():
    """f32 vs int8 pools through CachedDecoder prefill + decode: logits
    agree within the committed quantization bound (the engine-level
    greedy-parity bound rides on this)."""
    from paddle_tpu.serving.generation.model_fns import CachedDecoder
    m = _tiny_model()
    B, P, page = 2, 4, 16
    outs = {}
    for kd in ("", "int8"):
        dec = CachedDecoder(m, max_batch=B, page_size=page,
                            pages_per_seq=P, donate=False,
                            use_pallas=True, kv_dtype=kd)
        k, v = m.init_kv_pools(1 + B * P, page, kd or None)
        tables = np.arange(1, 1 + B * P,
                           dtype=np.int32).reshape(B, P)
        ids = np.array([[3, 5, 7, 11, 0, 0, 0, 0],
                        [2, 4, 6, 8, 10, 12, 0, 0]], np.int64)
        lens = np.array([4, 6], np.int32)
        _, last, k, v, _ = dec.prefill(ids, lens, tables, None, None, k, v)
        logits_seq = [np.asarray(last)]
        ctx = lens.copy()
        for step in range(3):
            tok = np.asarray(last).argmax(-1).astype(np.int64)
            _, logits, k, v, _ = dec.decode(tok, ctx, np.ones(B, bool),
                                         ctx + 1, tables, None, None, k, v)
            # a new array: the call above may still be reading the old
            # one (dispatch is asynchronous and the CPU backend aliases
            # numpy buffers), and `ctx += 1` in place raced with it
            ctx = ctx + 1
            last = logits
            logits_seq.append(np.asarray(logits))
        outs[kd] = logits_seq
    for a, b in zip(outs[""], outs["int8"]):
        assert np.abs(a - b).max() < 0.05
        # greedy argmax stream identical at every step
        np.testing.assert_array_equal(a.argmax(-1), b.argmax(-1))


def test_dtype_or_kernel_flip_changes_fingerprint():
    """A kv-dtype or kernel-routing flip must never hit a stale
    executable: both join the geometry fingerprint that keys the
    persistent compile cache and warmup manifests."""
    from paddle_tpu.serving.generation.model_fns import CachedDecoder
    m = _tiny_model()
    kw = dict(max_batch=2, page_size=16, pages_per_seq=4, donate=False)
    fps = {(up, kd): CachedDecoder(m, use_pallas=up, kv_dtype=kd,
                                   **kw).fingerprint()
           for up in (False, True) for kd in ("", "int8")}
    assert len(set(fps.values())) == 4
    # and the jit layer retraces on the pool-leaf structure change
    # regardless (tuple pools have different shapes/dtypes)
    sig_f32 = CachedDecoder._sig_of(
        (None, None, m.init_kv_pools(9, 16, None)))
    sig_i8 = CachedDecoder._sig_of(
        (None, None, m.init_kv_pools(9, 16, "int8")))
    assert sig_f32 != sig_i8


def test_engine_greedy_parity_capacity_and_leaks():
    """End-to-end: quantized engine produces the identical greedy
    stream, gets 2x pool pages for the same budget, reports smaller
    pool bytes, and leaks no pages."""
    from paddle_tpu.framework import flags as F
    from paddle_tpu.serving.generation.engine import GenerationServer
    m = _tiny_model()
    results = {}
    try:
        for kd, up in [("", False), ("int8", True)]:
            F.set_flags({"FLAGS_decode_kv_dtype": kd})
            srv = GenerationServer(m, max_batch=2, max_seq_len=64,
                                   use_pallas=up,
                                   name=f"ppq-{kd or 'f32'}")
            assert srv.use_pallas is up and srv.decoder.use_pallas is up
            try:
                toks = list(srv.generate([3, 5, 7, 11],
                                         max_new_tokens=8))
                chk = srv.kv.leak_check()
                assert chk["ok"] and chk["leaked"] == 0, chk
                results[kd] = dict(toks=toks,
                                   factor=srv.kv_capacity_factor,
                                   pages=srv.kv.capacity,
                                   bytes=srv.kv.pool_bytes())
            finally:
                srv.shutdown()
    finally:
        F.set_flags({"FLAGS_decode_kv_dtype": ""})
    f32, i8 = results[""], results["int8"]
    assert i8["toks"] == f32["toks"]
    assert i8["factor"] == 2 and f32["factor"] == 1
    assert i8["pages"] == 2 * f32["pages"]
    # 2x the pages at ~3.2x (D=16) byte shrink still nets out smaller
    assert i8["bytes"] < f32["bytes"]


def test_engine_spec_decode_parity_quantized():
    """Speculative decoding (draft + verify windows, the [B, k+1]
    chunked kernel) with int8 pools: identical accepted stream."""
    from paddle_tpu.framework import flags as F
    from paddle_tpu.serving.generation.engine import GenerationServer
    m, d = _tiny_model(), _tiny_model()
    toks = {}
    try:
        for kd, up in [("", False), ("int8", True)]:
            F.set_flags({"FLAGS_decode_kv_dtype": kd})
            srv = GenerationServer(m, max_batch=2, max_seq_len=64,
                                   draft_model=d, spec_k=3,
                                   use_pallas=up,
                                   name=f"ppsq-{kd or 'f32'}")
            try:
                toks[kd] = list(srv.generate([3, 5, 7, 11],
                                             max_new_tokens=8))
                srv.kv.assert_no_leaks()
            finally:
                srv.shutdown()
    finally:
        F.set_flags({"FLAGS_decode_kv_dtype": ""})
    assert toks["int8"] == toks[""]


# ------------------------------------- the decode path a TPU server takes

def test_default_path_is_what_the_backend_says():
    """No flag decides who attends: off the chip a call that names no
    path takes the pure body (tier-1 never interprets a kernel it did
    not ask for), a decoder built with defaults pins that, and the
    answer joins its fingerprint."""
    from paddle_tpu.framework import place
    from paddle_tpu.ops.paged_attention import kernel_by_default
    from paddle_tpu.serving.generation.model_fns import CachedDecoder
    assert not place.on_tpu() and not kernel_by_default(16)
    q, kp, vp, tables, ctx = _decode_case()
    val = jnp.ones((q.shape[0], 1), bool)
    pos = jnp.maximum(ctx - 1, 0)[:, None]
    txt = jax.jit(lambda *a: paged_attention_update(
        *a, page_size=PS, kind="decode")).lower(
        q, q, q, kp, vp, tables, ctx, val, pos).as_text()
    assert "stablehlo.gather" in txt   # gather_pool: the pure body
    kw = dict(max_batch=2, page_size=16, pages_per_seq=4, donate=False)
    m = _tiny_model()
    dec = CachedDecoder(m, **kw)
    assert dec.use_pallas is False
    assert dec.fingerprint() == CachedDecoder(
        m, use_pallas=False, **kw).fingerprint()


def test_default_path_on_a_tpu_is_the_kernel(monkeypatch):
    """What ``kernel_by_default`` answers on a TPU, steered from here:
    the kernels wherever they can serve the call, the pure body for the
    one-slot pages they cannot tile — by shape, and without raising."""
    from paddle_tpu.framework import place
    from paddle_tpu.ops.paged_attention import kernel_by_default
    monkeypatch.setattr(place, "on_tpu", lambda: True)
    assert kernel_by_default(16) and not kernel_by_default(1)
    q = jnp.zeros((1, 1, 2, 8))
    pool = jnp.zeros(kv_pool_shape(3, 1, 2, 8))  # one-slot pages
    txt = jax.jit(lambda *a: paged_attention_update(
        *a, page_size=1, kind="decode")).lower(
        q, q, q, pool, pool, jnp.zeros((1, 2), jnp.int32),
        jnp.ones((1,), jnp.int32), jnp.ones((1, 1), bool),
        jnp.zeros((1, 1), jnp.int32)).as_text()
    assert "stablehlo.gather" in txt   # gather_pool: the pure body


def test_decode_kernel_selection_is_by_shape():
    """By the row a page is copied in (``kv_heads * head_dim`` lanes),
    not by the head: 64-wide heads take the page-copying kernel too."""
    assert decode_copies_pages(16 * 128, False)   # gpt3-1p3b
    assert decode_copies_pages(16 * 64, False)    # gpt2-medium
    assert decode_copies_pages(4 * 128, False)    # SmallThinker's K/V heads
    assert not decode_copies_pages(4 * 16, False)  # half a lane tile
    assert not decode_copies_pages(16 * 128, True)  # quantized pools too


def _ragged_case(heads, head_dim, seed=0):
    """Eight lanes over 5-page tables (+ trash page 0): context 1, one
    short of a page, exactly a page, a page and one, the full table, a
    dead lane, and two lanes whose table keeps stale entries (another
    lane's pages, the trash page) past their context."""
    P, ps = 5, PS
    ctx = np.array([1, ps - 1, ps, ps + 1, P * ps, 0, 2 * ps + 3, 3],
                   np.int32)
    B = len(ctx)
    rng = np.random.RandomState(seed)
    shape = kv_pool_shape(1 + B * P, ps, heads, head_dim)
    kp = jnp.asarray(rng.randn(*shape), jnp.float32)
    vp = jnp.asarray(rng.randn(*shape), jnp.float32)
    # poison the trash page: it must never reach a live output
    kp, vp = kp.at[0].set(1e4), vp.at[0].set(-1e4)
    tables = 1 + np.arange(B * P, dtype=np.int32).reshape(B, P)
    tables[6, 3:] = tables[0, :2]       # stale: another lane's pages
    tables[7, 1:] = 0                   # stale: the trash page
    tables[5, :] = 0                    # the dead lane owns nothing
    return kp, vp, jnp.asarray(tables), jnp.asarray(ctx), rng


@pytest.mark.parametrize("chunk", [None, 1, 2, 3])
@pytest.mark.parametrize("heads,head_dim", [(4, 128), (2, 256), (4, 64),
                                            (8, 64), (16, 64)])
@pytest.mark.parametrize("kernel", ["pages", "grid"])
def test_decode_parity_over_head_widths_and_ragged_lanes(kernel, heads,
                                                         head_dim, chunk):
    """Both decode kernels against the pure body, at heads of one, two
    and half a lane tile: the page-copying kernel (at several chunk
    sizes, the default among them), and the grid kernel's vector-unit
    branch, which a call that names grid blocks takes (at several pages
    a tile)."""
    kp, vp, tables, ctx, rng = _ragged_case(heads, head_dim)
    B = ctx.shape[0]
    q = jnp.asarray(rng.randn(B, 1, heads, head_dim), jnp.float32)
    val = jnp.ones((B, 1), jnp.int32)
    pos = jnp.maximum(ctx - 1, 0)[:, None]
    scale = 1.0 / np.sqrt(head_dim)
    assert decode_copies_pages(heads * head_dim, False)
    kw = {"pages_per_chunk": chunk} if kernel == "pages" else \
        {"block_h": heads,
         "pages_per_tile": {None: None, 1: 1, 2: 5, 3: 1}[chunk]}
    out = paged_attention(q, kp, vp, tables, ctx, val, pos, page_size=PS,
                          kind="decode", scale=scale, **kw)
    ref = _decode_ref(q, kp, vp, tables, ctx, scale)
    live = np.asarray(ctx) > 0
    np.testing.assert_allclose(np.asarray(out)[live],
                               np.asarray(ref)[live], rtol=2e-5, atol=2e-5)
    assert np.allclose(np.asarray(out)[~live], 0.0)


@pytest.mark.parametrize("chunk", [None, 3])
@pytest.mark.parametrize("pool_dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("heads", [2, 16])
def test_page_copying_kernel_takes_64_wide_heads(heads, pool_dtype, chunk):
    """What the kernel could not take while a head was an axis of the
    pool: 64-wide heads, ungrouped, two to a lane tile (one tile a row,
    and gpt2-medium's eight), over lanes whose last page is part
    filled, a dead lane, stale table entries and a poisoned trash
    page."""
    kp, vp, tables, ctx, rng = _ragged_case(heads, 64, seed=4)
    kp, vp = kp.astype(pool_dtype), vp.astype(pool_dtype)
    B = ctx.shape[0]
    q = jnp.asarray(rng.randn(B, 1, heads, 64), jnp.float32)
    val = jnp.ones((B, 1), jnp.int32)
    pos = jnp.maximum(ctx - 1, 0)[:, None]
    out = paged_attention(q, kp, vp, tables, ctx, val, pos, page_size=PS,
                          kind="decode", scale=0.125,
                          pages_per_chunk=chunk)
    ref = _decode_ref(q, kp.astype(jnp.float32), vp.astype(jnp.float32),
                      tables, ctx, 0.125)
    live = np.asarray(ctx) > 0
    np.testing.assert_allclose(np.asarray(out)[live],
                               np.asarray(ref)[live], rtol=2e-5, atol=2e-5)
    assert np.allclose(np.asarray(out)[~live], 0.0)


@pytest.mark.parametrize("pool_dtype", [jnp.bfloat16, "int8"])
@pytest.mark.parametrize("head_dim", [64, 128])
def test_decode_parity_sub_f32_pools(head_dim, pool_dtype):
    """bf16 pools through whichever float kernel the width selects,
    int8 pools through the grid kernel's MXU branch, against the same
    stored values through the pure body."""
    kp, vp, tables, ctx, rng = _ragged_case(4, head_dim, seed=3)
    kp, vp = kp.at[0].set(0.0), vp.at[0].set(0.0)   # int8 absmax range
    B = ctx.shape[0]
    q = jnp.asarray(rng.randn(B, 1, 4, head_dim), jnp.float32)
    val = jnp.ones((B, 1), jnp.int32)
    pos = jnp.maximum(ctx - 1, 0)[:, None]
    scale = 1.0 / np.sqrt(head_dim)
    if pool_dtype == "int8":
        kq, vq = _quantize_pool(kp), _quantize_pool(vp)
        kd, vd = _dequantize_pool(kq), _dequantize_pool(vq)
    else:
        kq, vq = kp.astype(pool_dtype), vp.astype(pool_dtype)
        kd, vd = kq.astype(jnp.float32), vq.astype(jnp.float32)
    out = paged_attention(q, kq, vq, tables, ctx, val, pos, page_size=PS,
                          kind="decode", scale=scale)
    ref = _decode_ref(q, kd, vd, tables, ctx, scale)
    live = np.asarray(ctx) > 0
    np.testing.assert_allclose(np.asarray(out)[live],
                               np.asarray(ref)[live], rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("head_dim", [64, 128])
def test_chunked_parity_over_head_widths_and_ragged_lanes(head_dim):
    """The chunked window over the same ragged lanes: each lane's last
    (up to) four positions are the window, the rest its cached prefix;
    dead positions and the dead lane are invalid."""
    heads, S = 4, 4
    kp, vp, tables, ctx, rng = _ragged_case(heads, head_dim, seed=1)
    ctx_np = np.asarray(ctx)
    B = len(ctx_np)
    seg = np.minimum(ctx_np, S)
    start = ctx_np - seg
    offs = np.arange(S, dtype=np.int32)[None, :]
    pos = jnp.asarray(start[:, None] + offs)
    val_np = offs < seg[:, None]
    q = jnp.asarray(rng.randn(B, S, heads, head_dim), jnp.float32)
    scale = 1.0 / np.sqrt(head_dim)
    out = paged_attention(q, kp, vp, tables, ctx,
                          jnp.asarray(val_np.astype(np.int32)), pos,
                          page_size=PS, kind="chunked", scale=scale)
    ks = gather_pool(kp, tables, q.shape[2], out_dtype=q.dtype)
    vs = gather_pool(vp, tables, q.shape[2], out_dtype=q.dtype)
    ref = _chunked_attention(q, ks, vs, pos, jnp.asarray(val_np), scale)
    np.testing.assert_allclose(np.asarray(out)[val_np],
                               np.asarray(ref)[val_np],
                               rtol=2e-5, atol=2e-5)
    assert np.all(np.isfinite(np.asarray(out)))


@pytest.mark.parametrize("head_dim", [64, 128])
def test_update_decode_parity_through_the_write(head_dim):
    """``paged_attention_update`` write-then-attend, kernel against the
    pure body at both head widths: same output, same pools."""
    heads = 4
    kp, vp, tables, ctx, rng = _ragged_case(heads, head_dim, seed=2)
    B = ctx.shape[0]
    q, k, v = (jnp.asarray(rng.randn(B, 1, heads, head_dim), jnp.float32)
               for _ in range(3))
    valid = jnp.asarray(np.asarray(ctx) > 0)[:, None]
    pos = jnp.maximum(ctx - 1, 0)[:, None]
    got = {up: paged_attention_update(
        q, k, v, kp, vp, tables, ctx, valid, pos, page_size=PS,
        kind="decode", use_pallas=up) for up in (False, True)}
    live = np.asarray(ctx) > 0
    np.testing.assert_allclose(np.asarray(got[True][0])[live],
                               np.asarray(got[False][0])[live],
                               rtol=2e-5, atol=2e-5)
    for i in (1, 2):
        np.testing.assert_array_equal(np.asarray(got[True][i]),
                                      np.asarray(got[False][i]))


# lanes of 4 pages of 8 slots, each a pattern of contexts
LATENT_LANES = {
    "ragged": [19, 0, 32, 7],
    "first-dead": [0, 19, 32, 7],
    "two-dead": [19, 0, 0, 7],
    "last-dead": [19, 32, 7, 0],
    "all-dead": [0, 0, 0, 0],
    "full-table": [32, 32, 25, 32],
    "one-page": [8, 1, 3, 5],
}
# (pool dtype, pages a chunk, lanes, (copies a group, pages a product
# step) or None for the constants)
LATENT_CASES = [(dtype, chunk, "ragged", None)
                for dtype in (jnp.float32, jnp.bfloat16)
                for chunk in (None, 1, 3)] + [
    (jnp.float32, 6, "ragged", None),           # longer than the table
    (jnp.bfloat16, 6, "ragged", None),
] + [(jnp.bfloat16, chunk, lanes, None)
     for lanes in list(LATENT_LANES)[1:] for chunk in (None, 1)] + [
    (jnp.float32, 4, "ragged", (2, 2)),
    (jnp.bfloat16, 4, "first-dead", (2, 2)),
    (jnp.bfloat16, 4, "two-dead", (3, 1)),
]


def _latent_case_id(case):
    dtype, chunk, lanes, steps = case
    parts = [jnp.dtype(dtype).name, str(chunk)]
    if lanes != "ragged":
        parts.append(lanes)
    if steps is not None:
        parts.append("group%d-step%d" % steps)
    return "-".join(parts)


@pytest.mark.parametrize("pool_dtype,chunk,lanes,steps", LATENT_CASES,
                         ids=[_latent_case_id(c) for c in LATENT_CASES])
def test_latent_decode_kernel_against_the_pure_body(pool_dtype, chunk, lanes,
                                                    steps, monkeypatch):
    """The latent pool's page-copying kernel against the pure body that
    gathers the table: 5 heads of absorbed queries 40 wide (a 32-wide
    latent and an 8-wide rotary key part, in a row of 128 lanes whose
    last 88 are zeros) over lanes of a pattern of contexts (dead lanes
    first, last, in a row or all; tables filled exactly; single pages),
    whose copies cross from each live lane to the next; through the
    write, the same pool either way. ``steps`` sets the copies a group
    and the pages a product step, so that a chunk's copies take both
    loops and its product each size."""
    if steps is not None:
        monkeypatch.setattr(autotune, "PAGED_LATENT_COPY_GROUP", steps[0])
        monkeypatch.setattr(autotune, "PAGED_LATENT_PRODUCT_STEP", steps[1])
    from paddle_tpu.ops.paged_attention import (
        latent_pool_shape, paged_latent_attention_update)
    from paddle_tpu.ops.pallas_paged_attention import paged_latent_decode
    heads, width, latent, pages = 5, 40, 32, 4
    rng = np.random.RandomState(3)
    ctx = jnp.asarray(LATENT_LANES[lanes], jnp.int32)
    B = ctx.shape[0]
    tables = jnp.asarray(1 + rng.permutation(B * pages).reshape(B, pages),
                         jnp.int32)
    shape = latent_pool_shape(1 + B * pages, PS, width)
    assert shape[2] == 128
    rows = rng.randn(*shape[:2], width)
    pool = jnp.asarray(np.pad(rows, ((0, 0), (0, 0), (0, 128 - width))),
                       pool_dtype)
    q = jnp.asarray(rng.randn(B, 1, heads, width), pool_dtype)
    new = jnp.asarray(rng.randn(B, 1, width), pool_dtype)
    valid = jnp.asarray(np.asarray(ctx) > 0)[:, None]
    pos = jnp.maximum(ctx - 1, 0)[:, None]
    got = {up: paged_latent_attention_update(
        q, new, pool, tables, ctx, valid, pos, page_size=PS, kind="decode",
        scale=0.25, value_dim=latent, use_pallas=up) for up in (False, True)}
    live = np.asarray(ctx) > 0
    tol = 2e-5 if pool_dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(got[True][0], np.float32)[live],
        np.asarray(got[False][0], np.float32)[live], rtol=tol, atol=tol)
    np.testing.assert_array_equal(np.asarray(got[True][1], np.float32),
                                  np.asarray(got[False][1], np.float32))
    written = np.asarray(got[True][1], np.float32)
    assert not written[..., width:].any()
    # the kernel alone, at other chunk lengths: the pure body's numbers
    out = paged_latent_decode(q[:, 0], got[True][1], tables, ctx,
                              page_size=PS, scale=0.25, value_dim=latent,
                              pages_per_chunk=chunk)
    assert out.shape == (B, heads, latent) and out.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(out)[live], np.asarray(got[False][0], np.float32)[
            live, 0], rtol=tol, atol=tol)
    assert np.all(np.asarray(out)[~live] == 0)


@pytest.mark.parametrize("shape,chunk", [
    ((16, 2048, 4, 128, False), 4),   # GPT, 16 heads of 128, vector unit
    ((16, 1024, 4, 64, False), 4),    # GPT, 16 heads of 64, vector unit
    ((16, 512, 2, 576, True), 16),    # 28 over 4 heads of 128, full context
    ((16, 512, 2, 257, True), 16),    # the same over a ring of 257
], ids=["gpt-128", "gpt-64", "grouped-full", "grouped-ring"])
def test_decode_chunk_of_the_other_kernels_is_their_own(shape, chunk):
    """``paged_decode_chunk`` serves ``_decode_kernel`` alone, with its
    own constants: at the shapes its ladder was timed at it chooses what
    it chose before the latent kernel had constants of its own."""
    page_size, lanes, itemsize, pages, on_mxu = shape
    assert autotune.paged_decode_chunk(page_size, lanes, itemsize, pages,
                                       on_mxu=on_mxu) == chunk


def test_latent_chunk_is_its_own_constant_cut_to_table_and_vmem():
    """The latent kernel's (pages a chunk, pages a product step): its
    own constants at the latent cell's shape, the chunk cut to the
    table and to the VMEM two slots may take, the step whole chunks
    where it does not divide one, a named chunk taken as named."""
    chunk = autotune.PAGED_LATENT_PAGES_PER_CHUNK
    step = autotune.PAGED_LATENT_PRODUCT_STEP
    assert chunk % step == 0
    assert autotune.paged_latent_chunk(16, 640, 2, 257) == (chunk, step)
    assert autotune.paged_latent_chunk(16, 640, 2, 4) == (4, 4)
    fit = autotune.PAGED_DECODE_VMEM_BYTES // (2 * 16 * 8 * 640 * 2)
    assert fit < chunk and fit % step
    assert autotune.paged_latent_chunk(16, 8 * 640, 2, 257) == (fit, fit)
    assert autotune.paged_latent_chunk(16, 640, 2, 257, override=3) == (3, 3)
    assert autotune.paged_latent_chunk(16, 640, 2, 4, override=2 * step) \
        == (2 * step, step)
    with pytest.raises(ValueError):
        autotune.paged_latent_chunk(16, 640, 2, 257, override=0)
