"""Decode serving: paged KV cache, cached decode correctness, and the
continuous-batching GenerationServer (paddle_tpu/serving/generation)."""
import os
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import GPTForCausalLM, GPTKVCache, gpt_tiny
from paddle_tpu.serving import DeadlineExceededError, QueueFullError
from paddle_tpu.serving.generation import (GenerationServer, PagedKVCache,
                                           sample_next_tokens)
from paddle_tpu.serving.generation.model_fns import (CachedDecoder,
                                                     supports_cached_decode)


def make_model(**kw):
    paddle.seed(0)
    cfg = gpt_tiny(use_flash_attention=False, **kw)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m, cfg


def make_tables(batch, pages_per_seq):
    """Contiguous per-row page ranges skipping trash page 0."""
    return (1 + np.arange(batch * pages_per_seq, dtype=np.int32)
            .reshape(batch, pages_per_seq))


# ---------------------------------------------------------------- ops
class TestPagedOps:
    def test_write_gather_roundtrip(self):
        import jax.numpy as jnp

        from paddle_tpu.ops import paged_attention as pa
        pool = jnp.zeros(pa.kv_pool_shape(5, 4, 2, 3))
        tables = np.array([[2, 4], [1, 3]], np.int32)
        kv = np.arange(2 * 8 * 2 * 3, dtype=np.float32).reshape(2, 8, 2, 3)
        positions = np.broadcast_to(np.arange(8, dtype=np.int32), (2, 8))
        valid = np.ones((2, 8), bool)
        slots = pa.flat_slots(jnp.asarray(tables), jnp.asarray(positions),
                              jnp.asarray(valid), 4)
        pool = pa.write_pool(pool, np.asarray(slots).reshape(-1),
                             kv.reshape(-1, 2, 3))
        out = np.asarray(pa.gather_pool(pool, jnp.asarray(tables), 2))
        np.testing.assert_array_equal(out, kv)
        # a page's row is its heads side by side: head h in lanes
        # h * head_dim .. (h + 1) * head_dim - 1
        np.testing.assert_array_equal(np.asarray(pool[2, 1]),
                                      kv[0, 1].reshape(-1))

    def test_invalid_positions_hit_trash_page_only(self):
        import jax.numpy as jnp

        from paddle_tpu.ops import paged_attention as pa
        pool = jnp.full(pa.kv_pool_shape(3, 4, 1, 2), -7.0)
        tables = np.array([[1, 2]], np.int32)
        positions = np.broadcast_to(np.arange(8, dtype=np.int32), (1, 8))
        valid = np.zeros((1, 8), bool)     # everything masked
        slots = pa.flat_slots(jnp.asarray(tables), jnp.asarray(positions),
                              jnp.asarray(valid), 4)
        assert int(np.asarray(slots).max()) < 4   # all in page 0
        pool2 = pa.write_pool(pool, np.asarray(slots).reshape(-1),
                              np.ones((8, 1, 2), np.float32))
        np.testing.assert_array_equal(np.asarray(pool2[1:]),
                                      np.asarray(pool[1:]))


# ------------------------------------------------- allocator/kv cache
class TestPagedKVCache:
    def test_alloc_free_reuse(self):
        m, _ = make_model()
        kv = PagedKVCache(m, num_pages=5, page_size=4)
        assert kv.capacity == 4 and kv.free_pages == 4
        a = kv.alloc(3)
        assert len(a) == 3 and 0 not in a
        assert kv.alloc(2) is None          # all-or-nothing
        assert kv.free_pages == 1           # failed alloc took nothing
        kv.free(a)
        assert kv.free_pages == 4
        assert kv.evicted_pages_total == 3
        b = kv.alloc(4)
        assert sorted(b) == [1, 2, 3, 4]    # freed pages reused
        assert kv.pages_for(1) == 1 and kv.pages_for(9) == 3

    def test_trash_page_never_allocated_and_double_free_caught(self):
        m, _ = make_model()
        kv = PagedKVCache(m, num_pages=3, page_size=2)
        pages = kv.alloc(2)
        assert 0 not in pages
        with pytest.raises(ValueError):
            kv.free([0])
        kv.free(pages)
        with pytest.raises(RuntimeError):
            kv.free(pages)


    def test_a_model_without_state_layers_has_no_slot(self):
        """The state kind (PR 35) costs a model without such layers
        nothing: no column of the table row, slot 0 from
        ``alloc_state``, nothing counted."""
        m, _ = make_model()
        kv = PagedKVCache(m, num_pages=5, page_size=4, max_batch=2)
        assert kv.state_columns == 0 and kv.state_capacity == 0
        assert kv.table_width(16) == 4
        assert kv.alloc_state() == 0
        kv.release_state(0)
        row = np.full(4, -1, np.int32)
        kv.fill_row(row, [3, 1], [], kv.alloc_state())
        assert row.tolist() == [3, 1, 0, 0]
        assert kv.used_pages == 0
        by_kind = kv.by_kind()
        assert set(by_kind["capacity"]) == {"full"}
        assert by_kind["pool_bytes"] == {"full": kv.pool_bytes()}
        kv.assert_no_leaks()


# ------------------------------------------------------ cache numerics
class TestCacheEquivalence:
    @pytest.mark.parametrize("stacked", [False, True])
    def test_eager_prefill_is_bit_identical(self, stacked):
        """The cache-threaded forward runs the SAME attention math as
        the uncached path for prefill, so eagerly (no jit refusion) the
        logits are bit-identical."""
        m, cfg = make_model(stacked=stacked)
        b, prompt, ps, pps = 2, 5, 4, 8
        ids = np.random.RandomState(0).randint(
            0, cfg.vocab_size, (b, prompt)).astype("int64")
        full = m(paddle.to_tensor(ids)).numpy()
        k, v = m.init_kv_pools(1 + b * pps, ps)
        t = paddle.to_tensor
        if not stacked:
            k = [t(x) for x in k]
            v = [t(x) for x in v]
        else:
            k, v = t(k), t(v)
        pos = np.broadcast_to(np.arange(prompt, dtype=np.int32),
                              (b, prompt)).copy()
        cache = GPTKVCache(
            "prefill", ps, k, v, t(make_tables(b, pps)),
            t(np.full(b, prompt, np.int32)),
            t(np.ones((b, prompt), bool)), t(pos))
        logits, _ = m(t(ids), cache=cache)
        np.testing.assert_array_equal(logits.numpy(), full)

    @pytest.mark.parametrize("stacked", [False, True])
    def test_prefill_exact_and_decode_tight(self, stacked):
        """Jitted prefill matches the uncached forward within fp noise
        (XLA refusion); decode matches within tight fp tolerance."""
        m, cfg = make_model(stacked=stacked)
        b, prompt, ps, pps = 2, 5, 4, 8
        ids = np.random.RandomState(0).randint(
            0, cfg.vocab_size, (b, prompt)).astype("int64")
        full = m(paddle.to_tensor(ids)).numpy()
        dec = CachedDecoder(m, max_batch=b, page_size=ps,
                            pages_per_seq=pps)
        k, v = m.init_kv_pools(1 + b * pps, ps)
        tables = make_tables(b, pps)
        _, last, k, v, _ = dec.prefill(
            ids, np.full(b, prompt, np.int32), tables, None, None, k, v)
        np.testing.assert_allclose(np.asarray(last), full[:, -1, :],
                                   rtol=1e-5, atol=1e-6)
        # 4 greedy decode steps vs the growing full forward
        cur = full[:, -1, :].argmax(-1)
        ref_ids = ids
        for step in range(4):
            pos = prompt + step
            _, logits, k, v, _ = dec.decode(
                cur, np.full(b, pos, np.int32), np.ones(b, bool),
                np.full(b, pos + 1, np.int32), tables, None, None, k, v)
            ref_ids = np.concatenate([ref_ids, cur[:, None]], 1)
            ref = m(paddle.to_tensor(ref_ids)).numpy()[:, -1]
            np.testing.assert_allclose(np.asarray(logits), ref,
                                       rtol=1e-4, atol=1e-5)
            assert (np.asarray(logits).argmax(-1) == ref.argmax(-1)).all()
            cur = ref.argmax(-1)

    def test_dead_lanes_do_not_perturb_live_lanes(self):
        """Slot masking: a garbage dead lane must not change a live
        lane's logits (the continuous-batching invariant)."""
        m, cfg = make_model()
        ps, pps = 4, 8
        ids = np.random.RandomState(1).randint(
            0, cfg.vocab_size, (1, 6)).astype("int64")
        outs = []
        for b in (1, 4):
            dec = CachedDecoder(m, max_batch=b, page_size=ps,
                                pages_per_seq=pps)
            k, v = m.init_kv_pools(1 + b * pps, ps)
            tables = make_tables(b, pps)
            ids_b = np.zeros((b, 6), np.int64)
            ids_b[0] = ids[0]
            lens = np.zeros(b, np.int32)
            lens[0] = 6
            _, last, k, v, _ = dec.prefill(ids_b, lens, tables, None, None,
                                           k, v)
            tok = np.zeros(b, np.int64)
            tok[0] = int(np.asarray(last)[0].argmax())
            active = np.zeros(b, bool)
            active[0] = True
            _, logits, k, v, _ = dec.decode(
                tok, np.full(b, 6, np.int32), active,
                np.where(active, 7, 0).astype(np.int32), tables, None, None,
                k, v)
            outs.append(np.asarray(logits)[0])
        np.testing.assert_allclose(outs[0], outs[1], rtol=1e-5,
                                   atol=1e-6)

    def test_supports_cached_decode_contract(self):
        m, _ = make_model()
        assert supports_cached_decode(m)
        from paddle_tpu.models import BertModel, bert_tiny
        assert not supports_cached_decode(BertModel(bert_tiny()))

    def test_decode_step_compiles_once(self):
        m, cfg = make_model()
        with GenerationServer(m, max_batch=4, page_size=8,
                              name="once") as srv:
            futs = [srv.submit_generate([1 + i, 2, 3],
                                        max_new_tokens=4 + i)
                    for i in range(6)]
            for f in futs:
                f.result(timeout=60)
            decode_sigs = [s for s in srv.decoder.compiled_signatures
                           if s[0] == "generate_decode"]
            assert len(decode_sigs) == 1


# ------------------------------------------------------------ sampling
class TestSampling:
    def test_greedy_matches_argmax(self):
        logits = np.random.RandomState(0).randn(4, 9)
        np.testing.assert_array_equal(
            sample_next_tokens(logits, 0.0), logits.argmax(-1))

    def test_mixed_rows_and_determinism(self):
        logits = np.random.RandomState(0).randn(4, 9)
        temps = [0.0, 1.0, 0.0, 0.5]
        a = sample_next_tokens(logits, temps,
                               rng=np.random.RandomState(7))
        b = sample_next_tokens(logits, temps,
                               rng=np.random.RandomState(7))
        np.testing.assert_array_equal(a, b)
        assert a[0] == logits[0].argmax() and a[2] == logits[2].argmax()

    def test_matches_multinomial_distribution(self):
        """Inverse-CDF selection reproduces the softmax distribution."""
        logits = np.log(np.array([[0.7, 0.2, 0.1]]))
        rng = np.random.RandomState(0)
        draws = np.array([
            sample_next_tokens(logits, 1.0, rng=rng)[0]
            for _ in range(3000)])
        freq = np.bincount(draws, minlength=3) / 3000.0
        np.testing.assert_allclose(freq, [0.7, 0.2, 0.1], atol=0.03)


# ------------------------------------------- selection in the program
def twin_model():
    """A model whose every logit has a twin: the tied head's rows 2i
    and 2i + 1 are one vector, so each row's best logit is tied and
    the first of the pair has to win."""
    m, cfg = make_model()
    w = m.gpt.embeddings.word_embeddings.weight
    twins = w.numpy().copy()
    twins[1::2] = twins[0::2]
    w.set_value(twins)
    return m, cfg


def run_selecting(dec, kind, m, temperature=None, uniform=None):
    """One call of ``kind`` over fresh pools, lane 1 dead: ``(tokens,
    logits)`` on the host."""
    b, ps, pps = dec.max_batch, dec.page_size, dec.pages_per_seq
    k, v = m.init_kv_pools(1 + b * pps, ps)
    tables = make_tables(b, pps)
    ids = np.random.RandomState(5).randint(0, 64, (b, 6)).astype(np.int64)
    lens = np.full(b, 6, np.int32)
    lens[1] = 0
    sel = (temperature, uniform)
    if kind == "prefill":
        toks, logits, *_ = dec.prefill(ids, lens, tables, *sel, k, v)
    elif kind == "prefill_chunked":
        toks, logits, *_ = dec.prefill_chunked(
            ids, np.zeros(b, np.int32), lens, tables, *sel, k, v)
    else:
        _, _, k, v, _ = dec.prefill(ids, lens, tables, None, None, k, v)
        toks, logits, *_ = dec.decode(
            ids[:, 0], lens, lens > 0, lens + (lens > 0), tables, *sel,
            k, v)
    return np.asarray(toks), np.asarray(logits)


KINDS = ["prefill", "prefill_chunked", "decode"]


class TestSelectionInTheProgram:
    @pytest.mark.parametrize("kind", KINDS)
    def test_tokens_are_the_argmax_of_the_logits_beside_them(self, kind):
        """Ties go to the first index, as ``np.argmax`` has it, and a
        dead lane's row is chosen from like any other."""
        m, cfg = twin_model()
        dec = CachedDecoder(m, max_batch=3, page_size=4, pages_per_seq=4,
                            donate=False)
        toks, logits = run_selecting(dec, kind, m)
        assert toks.shape == (3,) and toks.dtype == np.int32
        np.testing.assert_array_equal(logits[:, 0::2], logits[:, 1::2])
        np.testing.assert_array_equal(toks, logits.argmax(-1))
        assert (toks % 2 == 0).all()

    @pytest.mark.parametrize("kind", KINDS)
    def test_a_sampled_row_leaves_the_greedy_rows_as_they_were(self, kind):
        m, cfg = make_model()
        dec = CachedDecoder(m, max_batch=3, page_size=4, pages_per_seq=4,
                            donate=False)
        greedy, logits = run_selecting(dec, kind, m)
        temperature = np.array([0.0, 0.0, 5.0], np.float32)
        picks = set()
        for u in (0.05, 0.35, 0.65, 0.95):
            toks, again = run_selecting(
                dec, kind, m, temperature, np.full(3, u, np.float32))
            np.testing.assert_array_equal(again, logits)
            np.testing.assert_array_equal(toks[:2], greedy[:2])
            picks.add(int(toks[2]))
            want = sample_next_tokens(logits, temperature,
                                      uniforms=np.full(3, u))
            np.testing.assert_array_equal(toks, want)
        assert len(picks) > 1           # the third row did sample
        # one program served both: no second signature for sampling
        assert len(dec.compiled_signatures) == (2 if kind == "decode"
                                                else 1)

    def test_sampled_choices_follow_softmax_over_temperature(self):
        """``TestSampling``'s frequency test through the decode
        program: 64 lanes read one context, each draws at its own
        uniform, 40 steps over."""
        m, cfg = make_model()
        b, ps, pps, t = 64, 4, 2, 0.5
        dec = CachedDecoder(m, max_batch=b, page_size=ps,
                            pages_per_seq=pps, donate=False)
        k, v = m.init_kv_pools(1 + b * pps, ps)
        tables = make_tables(b, pps)
        ids = np.tile(np.array([[5, 7, 9]], np.int64), (b, 1))
        lens = np.full(b, 3, np.int32)
        _, _, k, v, _ = dec.prefill(ids, lens, tables, None, None, k, v)
        rng = np.random.RandomState(0)
        draws, same = [], 0
        for _ in range(40):
            u = rng.random_sample(b)
            toks, logits, *_ = dec.decode(
                np.full(b, 2, np.int64), lens, np.ones(b, bool), lens + 1,
                tables, np.full(b, t, np.float32), u, k, v)
            toks, logits = np.asarray(toks), np.asarray(logits)
            same += int((toks == sample_next_tokens(
                logits, t, uniforms=u)).sum())
            draws.extend(toks)
        # float32 in the program, float64 on the host: a draw within a
        # rounding of a boundary may fall on the other side
        assert same >= 0.995 * len(draws)
        z = logits[0].astype(np.float64) / t
        p = np.exp(z - z.max())
        p /= p.sum()
        freq = np.bincount(draws, minlength=p.size) / len(draws)
        top = np.argsort(p)[-3:]
        assert p[top].min() > 0.03
        np.testing.assert_allclose(freq[top], p[top], atol=0.03)

    def test_a_uniform_that_rounds_to_one_takes_the_last_token(self):
        m, cfg = make_model()
        dec = CachedDecoder(m, max_batch=3, page_size=4, pages_per_seq=4,
                            donate=False)
        toks, logits = run_selecting(
            dec, "prefill", m, np.full(3, 1.0, np.float32),
            np.full(3, 1.0 - 1e-12))
        assert (toks == logits.shape[-1] - 1).all()

    def test_a_seeded_stream_is_the_same_alone_and_in_company(self):
        m, cfg = make_model()
        ask = dict(max_new_tokens=10, temperature=0.8, seed=11)
        with GenerationServer(m, max_batch=4, page_size=8,
                              name="company") as srv:
            alone = srv.generate([5, 7, 9], **ask)
            others = [srv.submit_generate([3 + i, 4], max_new_tokens=12,
                                          temperature=0.5 * (i % 2),
                                          seed=i)
                      for i in range(3)]
            beside = srv.submit_generate([5, 7, 9], **ask)
            assert beside.result(timeout=60) == alone
            for f in others:
                assert len(f.result(timeout=60)) == 12

    def test_a_greedy_step_brings_token_ids_and_nothing_else(self):
        """No traffic of a server without a draft fetches a logit: a
        decode step brings ``max_batch`` token ids (and its counters,
        where the model has experts), and says so."""
        m, cfg = make_model()
        with GenerationServer(m, max_batch=4, page_size=8,
                              name="fetch") as srv:
            srv.warmup(seq_buckets=[8])
            assert srv.metrics_snapshot()["engine"]["fetch_bytes"] == 0
            want = self_reference(m, cfg, [5, 7, 9], 6)
            assert srv.generate([5, 7, 9], max_new_tokens=6) == want
            eng = srv.metrics_snapshot()["engine"]
            # no logits came back: six programs' fetches are under a row
            assert eng["fetch_bytes"] < cfg.vocab_size * 4
            # one prefill row, then five steps of four lanes
            assert eng["fetch_bytes"] == 4 * (1 + 5 * 4)
            assert eng["fetch_bytes"] <= 6 * srv.max_batch * 8
            # the logits are there for a caller that asks
            run = srv._runners[0].run(
                "decode", srv._decode_feeds([], [], [])
                + srv._selection_feeds([], [], srv.max_batch),
                host_logits=True)
            assert isinstance(run.logits, np.ndarray)
            assert run.logits.shape == (4, cfg.vocab_size)
            np.testing.assert_array_equal(run.tokens,
                                          run.logits.argmax(-1))
            assert run.fetched_bytes == run.logits.nbytes + 4 * 4

    def test_speculation_judges_from_logits_on_the_host(self):
        """A verify step chooses nothing: its logits come to the host,
        and a draft step's do while a lane samples; a greedy lane's
        proposal is the draft program's own choice."""
        m, cfg = make_model()
        want = self_reference(m, cfg, [5, 7, 9], 8)
        with GenerationServer(m, max_batch=2, page_size=8, draft_model=m,
                              spec_k=3, name="spec-select") as srv:
            assert srv.generate([5, 7, 9], max_new_tokens=8) == want
            greedy = srv.metrics_snapshot()["engine"]["fetch_bytes"]
            # the verify steps' logits alone: every draft step chose in
            # program and brought its lanes' ids (so did both prefills)
            spec = srv.metrics_snapshot()["spec"]
            verify_bytes = 4 * srv.max_batch * (srv.spec_k + 1) \
                * cfg.vocab_size
            draft_bytes = 4 * srv.max_batch * cfg.vocab_size
            assert greedy // verify_bytes * 3 == spec["proposed"]
            assert greedy % verify_bytes >= 4 * (
                2 + srv.max_batch * spec["proposed"])
            a = srv.generate([5, 7, 9], max_new_tokens=8,
                             temperature=0.8, seed=3)
            b = srv.generate([5, 7, 9], max_new_tokens=8,
                             temperature=0.8, seed=3)
            assert a == b and len(a) == 8
            sampled = srv.metrics_snapshot()["engine"]["fetch_bytes"]
            verifies = (srv.metrics_snapshot()["spec"]["proposed"]
                        - spec["proposed"]) // 3
            # now the three draft steps of a round fetch logits too
            assert (sampled - greedy) // (
                verify_bytes + 3 * draft_bytes) == verifies


def self_reference(m, cfg, prompt, n):
    return TestGenerationServer()._reference(m, cfg, prompt, n)


# ----------------------------------------------------- the engine
class TestGenerationServer:
    def _reference(self, m, cfg, prompt, n):
        from paddle_tpu.distributed.fleet.utils import (
            HybridParallelInferenceHelper)
        helper = HybridParallelInferenceHelper(
            m, max_length=cfg.max_seq_len)
        out = helper._full_window_generate(
            np.asarray(prompt, np.int64)[None, :],
            min(cfg.max_seq_len, len(prompt) + n), 0.0, 0)
        return list(out[0, len(prompt):])

    def test_greedy_matches_full_window_reference(self):
        m, cfg = make_model()
        with GenerationServer(m, max_batch=4, page_size=8,
                              name="ref") as srv:
            p = [5, 7, 9, 2]
            got = srv.generate(p, max_new_tokens=6)
            assert got == self._reference(m, cfg, p, 6)

    def test_continuous_join_and_evict_ordering(self):
        """Different-length requests share the in-flight batch; a late
        request joins mid-decode; every stream still matches its
        single-request reference."""
        m, cfg = make_model()
        prompts = [[5, 7, 9], [3, 1, 4, 1, 5], [2, 2]]
        new = [12, 4, 8]
        refs = [self._reference(m, cfg, p, n)
                for p, n in zip(prompts, new)]
        with GenerationServer(m, max_batch=4, page_size=8,
                              name="join") as srv:
            f0 = srv.submit_generate(prompts[0], max_new_tokens=new[0])
            f1 = srv.submit_generate(prompts[1], max_new_tokens=new[1])
            # wait until the first stream is visibly mid-decode, then
            # JOIN a third sequence into the live batch
            deadline = time.monotonic() + 30
            while len(f0.tokens()) < 2 and time.monotonic() < deadline:
                time.sleep(0.002)
            assert not f0.done() or len(f0.tokens()) >= 2
            f2 = srv.submit_generate(prompts[2], max_new_tokens=new[2])
            outs = [f.result(timeout=60) for f in (f0, f1, f2)]
            assert outs == refs
            assert f1.finish_reason == "length"
            snap = srv.metrics_snapshot()
            # overlapped execution: fewer decode iterations than the
            # serial sum of per-sequence steps
            assert snap["batch_occupancy"]["steps"] < sum(new)
            assert snap["counters"]["completed"] == 3
            assert snap["tokens_total"] == sum(new)

    def test_page_reuse_after_eviction(self):
        """Pool sized for ONE sequence: the second request reuses the
        first one's evicted pages and still decodes correctly.
        (prefix_cache off: this pins the LEGACY eager-free accounting;
        the cached-page variant lives in test_prefix_spec.py.)"""
        m, cfg = make_model()
        p1, p2 = [5, 7, 9], [8, 6, 4]
        r1 = self._reference(m, cfg, p1, 6)
        r2 = self._reference(m, cfg, p2, 6)
        # capacity: pages for one sequence of 3+6=9 tokens @ page 4 = 3
        with GenerationServer(m, max_batch=2, page_size=4, num_pages=4,
                              max_seq_len=16, prefix_cache=False,
                              name="reuse") as srv:
            f1 = srv.submit_generate(p1, max_new_tokens=6)
            f2 = srv.submit_generate(p2, max_new_tokens=6)
            assert f1.result(timeout=60) == r1
            assert f2.result(timeout=60) == r2
            assert srv.kv.evicted_pages_total == 6
            assert srv.kv.free_pages == srv.kv.capacity
            snap = srv.metrics_snapshot()
            assert snap["kv_pages"]["evicted_total"] == 6
            assert snap["kv_pages"]["used"] == 0

    def test_streaming_iteration_and_eos(self):
        m, cfg = make_model()
        # use a greedy token as eos: the stream must stop at its FIRST
        # occurrence with reason "eos", eos token included
        ref = self._reference(m, cfg, [5, 7, 9], 8)
        eos = int(ref[2])
        stop = ref.index(eos) + 1
        with GenerationServer(m, max_batch=2, page_size=8,
                              eos_token_id=eos, name="eos") as srv:
            fut = srv.submit_generate([5, 7, 9], max_new_tokens=8)
            streamed = list(fut)
            assert streamed == fut.result(timeout=10)
            assert streamed == ref[:stop]
            assert fut.finish_reason == "eos"

    def test_cancel_mid_stream(self):
        m, cfg = make_model()
        with GenerationServer(m, max_batch=2, page_size=8,
                              name="cancel") as srv:
            fut = srv.submit_generate([5, 7, 9], max_new_tokens=120)
            deadline = time.monotonic() + 30
            while len(fut.tokens()) < 2 and time.monotonic() < deadline:
                time.sleep(0.002)
            assert fut.cancel()
            toks = fut.result(timeout=30)
            assert 2 <= len(toks) < 120
            assert fut.finish_reason == "cancelled"
            assert fut.cancelled()
            assert srv.kv.free_pages == srv.kv.capacity
            # engine still serves after a cancellation
            assert srv.generate([1, 2], max_new_tokens=2) == \
                self._reference(m, cfg, [1, 2], 2)

    def test_deadline_matches_submit_semantics(self):
        m, cfg = make_model()
        srv = GenerationServer(m, max_batch=2, page_size=8,
                               name="deadline", start=False)
        fut = srv.submit_generate([5, 7], max_new_tokens=4,
                                  timeout_ms=5.0)
        time.sleep(0.05)
        srv.start()
        with pytest.raises(DeadlineExceededError):
            fut.result(timeout=30)
        assert fut.finish_reason == "timed_out"
        assert srv.metrics_snapshot()["counters"]["timed_out"] == 1
        srv.shutdown()

    def test_hard_deadline_evicts_inflight_stream(self):
        """Fleet deadline propagation, engine side: a stream whose
        HARD budget (deadline_ms) expires mid-generation is evicted
        at batch re-form — future fails typed, already-emitted tokens
        stay readable, and every page returns to the free list
        instead of the engine burning decode steps to the length
        cap."""
        m, cfg = make_model()
        with GenerationServer(m, max_batch=2, page_size=8,
                              prefix_cache=False,
                              name="hard_deadline") as srv:
            fut = srv.submit_generate([5, 7, 9], max_new_tokens=200,
                                      deadline_ms=120.0)
            with pytest.raises(DeadlineExceededError):
                fut.result(timeout=60)
            assert fut.finish_reason == "deadline"
            assert len(fut.tokens()) < 200     # evicted, not run out
            assert srv.kv.free_pages == srv.kv.capacity
            leak = srv.metrics_snapshot()["kv_leak_check"]
            assert not leak.get("leaked"), leak
            assert srv.metrics_snapshot()[
                "counters"]["timed_out"] == 1
            # the engine still serves after the eviction
            assert srv.generate([1, 2], max_new_tokens=2) == \
                self._reference(m, cfg, [1, 2], 2)

    def test_scheduling_timeout_still_never_evicts_inflight(self):
        """timeout_ms keeps its pre-deadline-propagation contract: it
        gates SCHEDULING only — once decoding, a stream with a tiny
        timeout_ms but no hard budget runs to completion."""
        m, cfg = make_model()
        ref = self._reference(m, cfg, [5, 7], 4)
        with GenerationServer(m, max_batch=2, page_size=8,
                              name="sched_only") as srv:
            fut = srv.submit_generate([5, 7], max_new_tokens=4,
                                      timeout_ms=30000.0)
            assert fut.result(timeout=60) == ref

    def test_queue_full_backpressure(self):
        m, cfg = make_model()
        srv = GenerationServer(m, max_batch=2, page_size=8,
                               queue_capacity=2, name="full",
                               start=False)
        srv.submit_generate([1], max_new_tokens=1)
        srv.submit_generate([2], max_new_tokens=1)
        with pytest.raises(QueueFullError):
            srv.submit_generate([3], max_new_tokens=1)
        assert srv.metrics_snapshot()["counters"]["rejected"] == 1
        srv.shutdown()   # inline drain resolves the two queued streams

    @pytest.mark.parametrize("kind", [
        "prefill", "prefill_chunked", "decode", "verify", "draft_decode"])
    def test_fault_barrier(self, kind):
        """A model error in one program, of whatever kind, fails the
        sequences that were in that call, typed, and no other: a stream
        already decoding outlives a failed prefill, a queued request a
        failed decode step. Their pages return, the pools are live
        arrays, and the worker serves the next request."""
        import jax
        m, cfg = make_model()
        spec = kind in ("verify", "draft_decode")
        pre = list(np.random.RandomState(3).randint(0, cfg.vocab_size, 16))
        bystander = pre + [1]
        # two full pages of the bystander's prompt are shared: the
        # victim's prefill is then the chunked one
        victim = pre + [2] if kind == "prefill_chunked" else [5, 7, 9]
        srv = GenerationServer(
            m, max_batch=2, page_size=8, name=f"fault-{kind}", start=False,
            **(dict(draft_model=m, spec_k=3) if spec else {}))
        with srv:
            decoder, entry = (srv.draft, "decode") \
                if kind == "draft_decode" else (srv.decoder, kind)
            real, armed = getattr(decoder, entry), []

            def bomb(*a, **kw):
                if armed:
                    armed.pop()
                    raise RuntimeError(f"injected {kind} fault")
                return real(*a, **kw)

            setattr(decoder, entry, bomb)
            if kind.startswith("prefill"):
                srv.start()
                ok = srv.submit_generate(bystander, max_new_tokens=24)
                deadline = time.monotonic() + 60
                while not ok.tokens() and time.monotonic() < deadline:
                    time.sleep(0.002)
                assert ok.tokens()      # prefilled and decoding
                armed.append(kind)
                bad = [srv.submit_generate(victim, max_new_tokens=4)]
            else:
                armed.append(kind)
                bad = [srv.submit_generate(p, max_new_tokens=6)
                       for p in (victim, [1, 2, 3])]
                # both lanes are taken: this one waits in the queue
                ok = srv.submit_generate(bystander, max_new_tokens=24)
                srv.start()
            for fut in bad:
                with pytest.raises(RuntimeError, match=f"injected {kind}"):
                    fut.result(timeout=60)
                assert fut.finish_reason == "error"
            assert not armed
            assert ok.result(timeout=60) == \
                self._reference(m, cfg, bystander, 24)
            assert srv.generate(victim, max_new_tokens=6,
                                timeout_ms=None) == \
                self._reference(m, cfg, victim, 6)
            counters = srv.metrics_snapshot()["counters"]
            assert counters["failed"] == len(bad)
            assert counters["completed"] == 2
            srv.clear_prefix_cache()
            srv.kv.assert_no_leaks()
            assert srv.kv.free_pages == srv.kv.capacity
            assert all(not a.is_deleted() for a in
                       jax.tree_util.tree_leaves((srv.kv.k, srv.kv.v)))

    def test_shutdown_no_drain_fails_queued(self):
        from paddle_tpu.serving import ServerClosedError
        m, cfg = make_model()
        srv = GenerationServer(m, max_batch=2, page_size=8,
                               name="abort", start=False)
        fut = srv.submit_generate([5], max_new_tokens=4)
        srv.shutdown(drain=False)
        with pytest.raises(ServerClosedError):
            fut.result(timeout=10)
        with pytest.raises(ServerClosedError):
            srv.submit_generate([1], max_new_tokens=1)

    def test_validation(self):
        m, cfg = make_model()
        srv = GenerationServer(m, max_batch=2, page_size=8,
                               name="valid", start=False)
        with pytest.raises(ValueError, match="no room"):
            srv.submit_generate(np.arange(cfg.max_seq_len),
                                max_new_tokens=2)
        with pytest.raises(ValueError, match="empty"):
            srv.submit_generate([], max_new_tokens=2)
        with pytest.raises(ValueError):
            srv.submit_generate([1], max_new_tokens=0)
        srv.shutdown()

    def test_temperature_streams_are_request_deterministic(self):
        m, cfg = make_model()
        with GenerationServer(m, max_batch=4, page_size=8,
                              name="temp") as srv:
            a = srv.generate([5, 7, 9], max_new_tokens=8,
                             temperature=0.8, seed=3)
            b = srv.generate([5, 7, 9], max_new_tokens=8,
                             temperature=0.8, seed=3)
            assert a == b
            assert len(a) == 8

    def test_metrics_exposition(self):
        from paddle_tpu.observability import prometheus_text
        m, cfg = make_model()
        with GenerationServer(m, max_batch=2, page_size=8,
                              name="expo") as srv:
            srv.generate([5, 7], max_new_tokens=3)
            text = prometheus_text()
            for fam in ("paddle_decode_tokens_total",
                        "paddle_decode_inter_token_ms",
                        "paddle_decode_kv_pages",
                        "paddle_decode_batch_occupancy",
                        "paddle_decode_requests_total"):
                assert fam in text
            snap = srv.metrics_snapshot()
            assert snap["tokens_total"] == 3
            assert snap["step_ms"]["prefill"]["count"] == 1
            assert snap["step_ms"]["decode"]["count"] == 2


# ------------------------------------------------- warmup + manifest
class TestWarmupManifest:
    @pytest.fixture
    def cache_dir(self, tmp_path):
        from paddle_tpu.compile_cache import reset_default_cache
        paddle.set_flags({"FLAGS_compile_cache_dir": str(tmp_path)})
        reset_default_cache()
        yield str(tmp_path)
        paddle.set_flags({"FLAGS_compile_cache_dir": ""})
        reset_default_cache()

    def test_site_tagged_entries_and_filtering(self, tmp_path):
        from paddle_tpu.compile_cache import WarmupManifest
        man = WarmupManifest(str(tmp_path / "m.json"))
        man.record([((4, 16), "float32")])                 # predict
        man.record([((2, 8), "int64")], site="generate_prefill")
        man.record([((2,), "int64")], site="generate_decode")
        assert len(man) == 3
        assert len(man.specs(site="predict")) == 1
        assert len(man.specs(site="generate_prefill")) == 1
        # reload from disk keeps the tags
        man2 = WarmupManifest(str(tmp_path / "m.json"))
        assert {e["site"] for e in man2.specs()} == \
            {"predict", "generate_prefill", "generate_decode"}

    def test_pre_site_manifest_loads_as_predict(self, tmp_path):
        import json
        path = tmp_path / "old.json"
        path.write_text(json.dumps(
            {"version": 1,
             "entries": [{"feeds": [[[4, 16], "float32"]]}]}))
        from paddle_tpu.compile_cache import WarmupManifest
        man = WarmupManifest(str(path))
        assert len(man.specs(site="predict")) == 1

    def test_traffic_records_and_replay_warms(self, cache_dir):
        m, cfg = make_model()
        with GenerationServer(m, max_batch=2, page_size=8,
                              name="man1") as srv:
            srv.generate([5, 7, 9], max_new_tokens=3)
            man = srv.warmup_manifest
            assert man is not None
            sites = {e["site"] for e in man.specs()}
            assert sites == {"generate_prefill", "generate_decode"}
            path = man.path
        # a "restarted" engine replays exactly the observed lattice
        m2, _ = make_model()
        srv2 = GenerationServer(m2, max_batch=2, page_size=8,
                                name="man2", start=False)
        fresh = srv2.warmup_from_manifest(path)
        assert fresh == 2    # one prefill bucket + the decode step
        # traffic after replay adds no new signatures
        srv2.start()
        srv2.generate([5, 7, 9], max_new_tokens=3)
        sigs = srv2.decoder.compiled_signatures
        assert len(sigs) == 2
        srv2.shutdown()

    def test_flag_auto_replay(self, cache_dir):
        m, cfg = make_model()
        with GenerationServer(m, max_batch=2, page_size=8,
                              name="auto1") as srv:
            srv.generate([5, 7], max_new_tokens=2)
        m2, _ = make_model()
        paddle.set_flags({"FLAGS_decode_warmup_from_manifest": True})
        try:
            srv2 = GenerationServer(m2, max_batch=2, page_size=8,
                                    name="auto1", start=False)
            assert len(srv2.decoder.compiled_signatures) == 2
            srv2.shutdown()
        finally:
            paddle.set_flags(
                {"FLAGS_decode_warmup_from_manifest": False})

    def test_inference_server_skips_generate_sites(self, tmp_path):
        """InferenceServer.warmup_from_manifest must ignore decode-
        engine entries — their feeds mean nothing to the Predictor."""
        from paddle_tpu.compile_cache import WarmupManifest
        path = str(tmp_path / "mixed.json")
        man = WarmupManifest(path)
        man.record([((2,), "int64")], site="generate_decode")
        assert man.specs(site="predict") == []


# ------------------------------------------- helper migration (sat. 1)
class TestHybridHelperMigration:
    def test_cached_path_taken_and_matches_full_window(self):
        from paddle_tpu.distributed.fleet.utils import (
            HybridParallelInferenceHelper)
        m, cfg = make_model()
        h = HybridParallelInferenceHelper(m, max_length=32)
        ids = np.random.RandomState(2).randint(
            0, cfg.vocab_size, (3, 5)).astype("int64")
        out = h.generate(ids, max_new_tokens=8)
        assert h._decoders        # the cached decoder was built & used
        ref = h._full_window_generate(ids, 13, 0.0, 0)
        np.testing.assert_array_equal(out, ref)

    def test_eos_early_stop_parity(self):
        from paddle_tpu.distributed.fleet.utils import (
            HybridParallelInferenceHelper)
        m, cfg = make_model()
        probe = HybridParallelInferenceHelper(m, max_length=32)
        ids = np.array([[5, 7, 9]], "int64")
        greedy = probe.generate(ids, max_new_tokens=8)
        eos = int(greedy[0, 5])    # 3rd generated token (may repeat
        # earlier in the greedy stream; parity with the full-window
        # path is what matters, not the absolute stop position)
        h = HybridParallelInferenceHelper(m, max_length=32,
                                          eos_token_id=eos)
        out = h.generate(ids, max_new_tokens=8)
        ref = h._full_window_generate(ids, 11, 0.0, 0)
        np.testing.assert_array_equal(out, ref)
        assert out.shape[1] < 11   # stopped before the full budget

    def test_picks_up_weight_updates_between_calls(self):
        from paddle_tpu.distributed.fleet.utils import (
            HybridParallelInferenceHelper)
        m, cfg = make_model()
        h = HybridParallelInferenceHelper(m, max_length=24)
        ids = np.array([[5, 7, 9]], "int64")
        a = h.generate(ids, max_new_tokens=6)
        w = m.gpt.embeddings.word_embeddings.weight
        w.set_value(np.asarray(w.numpy()) * 0.5
                    + np.random.RandomState(0).randn(
                        *w.shape).astype("float32"))
        b = h.generate(ids, max_new_tokens=6)   # must see new weights
        ref = h._full_window_generate(ids, 9, 0.0, 0)
        np.testing.assert_array_equal(b, ref)
        assert not np.array_equal(a, b)

    def test_fallback_for_cacheless_models(self):
        from paddle_tpu.distributed.fleet.utils import (
            HybridParallelInferenceHelper)

        class Toy:
            """Minimal logits-only model without cache support."""

            def __init__(self):
                self.training = False

            def __call__(self, ids):
                b, s = ids.shape
                base = np.asarray(ids.numpy(), np.float32)[..., None]
                return paddle.to_tensor(
                    np.tile(base, (1, 1, 11)) +
                    np.arange(11, dtype=np.float32))

        h = HybridParallelInferenceHelper(Toy(), max_length=8)
        out = h.generate(np.array([[1, 2]], "int64"), max_new_tokens=3)
        assert out.shape == (1, 5)


# ------------------------------------- the loop runs one step ahead
# (PR 36) ``_decode_iteration`` enqueues step i+1 before it harvests
# step i; each lane's last token stays on the device between the two.
RA_PAGE, RA_SEQ = 4, 64


def _ra_model(kind):
    """A tiny model of each kind of layer the cache manager knows: full
    context alone, window layers that keep a ring a lane, state layers
    that keep a slot a lane."""
    from paddle_tpu import models
    paddle.seed(7)
    if kind == "gpt":
        m = GPTForCausalLM(gpt_tiny(use_flash_attention=False))
    elif kind == "ring":
        pattern = (0, 1, 1, 1)
        m = models.GPTForCausalLM(models.GPTConfig(
            vocab_size=128, hidden_size=32, num_layers=4, num_heads=4,
            num_kv_heads=2, head_dim=8, max_seq_len=256, norm="rmsnorm",
            layer_norm_eps=1e-6, bias=False, position="rope",
            rope_layout=pattern, sliding_window=8,
            sliding_window_layout=pattern, tie_word_embeddings=False,
            use_flash_attention=False))
    else:
        m = models.NemotronHForCausalLM(models.NemotronHConfig(
            vocab_size=128, hidden_size=32, num_layers=4, pattern="ME*M",
            max_seq_len=256, num_heads=4, num_kv_heads=2, head_dim=8,
            mamba_num_heads=4, mamba_head_dim=8, ssm_state_size=8,
            mamba_n_groups=2, chunk_size=8, moe_num_experts=4,
            moe_router_experts=4, moe_top_k=2, moe_latent_size=16,
            moe_intermediate_size=16, moe_shared_intermediate_size=16,
            use_flash_attention=False))
    m.eval()
    return m


def _ra_server(model, **kw):
    kw.setdefault("max_batch", 3)
    return GenerationServer(model, page_size=RA_PAGE, max_seq_len=RA_SEQ,
                            seq_buckets=[8, 16, 32], use_pallas=False,
                            start=False, **kw)


def drive(model, prompt, n, temperature=0.0, seed=None):
    """The stream of one request as a step-by-step drive of
    ``CachedDecoder`` gives it: alone in lane 0, every step fetched
    before the next is formed and its token fed back from the host,
    the uniforms drawn as the engine draws them (one a program, from
    the request's own ``RandomState``)."""
    b = 3
    kv = PagedKVCache(model, num_pages=40, page_size=RA_PAGE, max_batch=b)
    width = kv.table_width(RA_SEQ)
    dec = CachedDecoder(model, max_batch=b, page_size=RA_PAGE,
                        pages_per_seq=width, max_positions=RA_SEQ,
                        donate=False, use_pallas=False)
    total = len(prompt) + n
    tables = np.zeros((b, width), np.int32)
    kv.fill_row(tables[0], kv.alloc(kv.pages_for(total)),
                kv.alloc_window(total), kv.alloc_state() or 0)
    rng = np.random.RandomState(seed)

    def selection(rows):
        t, u = np.zeros(rows, np.float32), np.zeros(rows, np.float32)
        if temperature > 0:
            t[0], u[0] = temperature, rng.random_sample()
        return t, u

    bucket = next(s for s in (8, 16, 32) if s >= len(prompt))
    ids = np.zeros((1, bucket), np.int64)
    ids[0, :len(prompt)] = prompt
    toks, _, k, v, _ = dec.prefill(
        ids, np.array([len(prompt)], np.int32), tables[:1],
        *selection(1), kv.k, kv.v)
    out, ctx = [int(np.asarray(toks)[0])], len(prompt)
    while len(out) < n:
        tokens, pos = np.zeros(b, np.int64), np.zeros(b, np.int32)
        tokens[0], pos[0] = out[-1], ctx
        toks, _, k, v, _ = dec.decode(
            tokens, pos, np.arange(b) == 0, pos + 1, tables,
            *selection(b), k, v)
        out.append(int(np.asarray(toks)[0]))
        ctx += 1
    return out


def until_eos(stream, eos):
    return stream[:stream.index(eos) + 1] if eos in stream else stream


class Recorder:
    """The target runner of ``srv`` with its two halves wrapped: every
    ``enqueue`` and ``harvest`` of a decode step and every emission in
    ``events``, in the order the loop thread made them, and the feeds
    of each enqueued step as they were handed over beside a copy."""

    def __init__(self, srv, before_harvest=None):
        self.events, self.feeds = [], []
        self.before_harvest = before_harvest
        runner = srv._runners[0]
        enqueue, harvest, emit = (runner.enqueue, runner.harvest,
                                  srv._emit_batch)
        self._steps = {}     # id(Enqueued) -> the step's number

        def spy_enqueue(kind, feeds):
            step = enqueue(kind, feeds)
            if kind == "decode":
                n = len(self.feeds)
                self._steps[id(step)] = n
                self.feeds.append((step, feeds, [
                    np.array(f) for f in feeds[1:]]))
                self.events.append(("enqueue", n))
            return step

        def spy_harvest(step, host_logits=False):
            n = self._steps.get(id(step))
            if n is not None:
                if self.before_harvest is not None:
                    self.before_harvest(n)
                self.events.append(("harvest", n))
            return harvest(step, host_logits)

        def spy_emit(seqs, toks):
            self.events.append(("emit", [s.slot for s in seqs]))
            return emit(seqs, toks)

        runner.enqueue, runner.harvest = spy_enqueue, spy_harvest
        srv._emit_batch = spy_emit

    def order(self, what):
        return [i for i, e in enumerate(self.events) if e == what]


class TestRunAhead:
    ASKS = [  # prompt length, max_new, temperature, seed
        (3, 12, 0.0, None),      # 0: hits eos mid-run
        (5, 10, 0.8, 5),         # 1: cancelled after its 4th token
        (6, 14, 0.0, None),      # 2: its deadline passes after its 6th
        (9, 9, 1.1, 9),          # 3: waits, then takes a freed lane
        (13, 6, 0.0, None),      # 4: waits; its prompt is four pages
        (2, 11, 0.7, 2),         # 5: waits
    ]

    @pytest.mark.parametrize("kind", ["gpt", "ring", "state"])
    def test_streams_equal_a_step_by_step_drive(self, kind):
        """Six requests over three lanes, greedy and seeded-sampled:
        one ends by ``eos`` mid-run, one is cancelled, one evicted by
        its deadline, all cross page boundaries, three are admitted
        into lanes freed the step before. Every stream is token for
        token what ``CachedDecoder`` driven one harvested step at a
        time gives that request alone; ``late_lanes`` counts the one
        step each lane ran past an end the host could not know."""
        model = _ra_model(kind)
        rng = np.random.RandomState(3)
        asks = [(rng.randint(1, 100, n), new, t, seed)
                for n, new, t, seed in self.ASKS]
        plain = [drive(model, *ask) for ask in asks]
        # an eos that request 0 reaches mid-run and not at its start,
        # and the two streams ended from outside not before their ends
        eos = next(t for i, t in enumerate(plain[0][3:-2], 3)
                   if t not in plain[0][:i] + plain[1][:6] + plain[2][:8])
        want = [until_eos(s, eos) for s in plain]
        assert 3 < len(want[0]) < len(plain[0]) - 1
        # the two streams ended from outside are mid-run when they are
        assert len(want[1]) > 5 and len(want[2]) > 7
        srv = _ra_server(model, eos_token_id=eos, name=f"ahead-{kind}")
        futs = [srv.submit_generate(p, max_new_tokens=new, temperature=t,
                                    seed=seed) for p, new, t, seed in asks]

        def meddle(step):
            if len(futs[1].tokens()) == 3 and not futs[1]._cancel_requested:
                futs[1].cancel()        # its 4th token is in this harvest
            if len(futs[2].tokens()) == 5:
                for seq in srv._slots:  # its 6th; evicted at the re-form
                    if seq is not None and seq.req.future is futs[2]:
                        seq.req.hard_deadline = 0.0

        rec = Recorder(srv, before_harvest=meddle)
        with srv:
            srv.start()
            got = []
            for i, f in enumerate(futs):
                if i == 2:
                    with pytest.raises(DeadlineExceededError):
                        f.result(timeout=120)
                    got.append(f.tokens())
                else:
                    got.append(f.result(timeout=120))
            srv.shutdown(drain=True)    # the last late step is in by now
            snap = srv.metrics_snapshot()
            assert futs[0].finish_reason == "eos"
            assert futs[1].finish_reason == "cancelled"
            assert futs[2].finish_reason == "deadline"
            want[1], want[2] = want[1][:4], want[2][:6]
            for i, (g, w) in enumerate(zip(got, want)):
                assert g == w, (kind, i)
            # a lane runs one step past an end the host learns of at
            # the harvest: an eos before the last token the request
            # may have (a length's end is known a step early), the
            # cancel and the eviction; an eos in a prefill's token
            # never reached a decode step
            late = 2 + sum(1 for w, (_, new, _, _) in zip(want, asks)
                           if w[-1] == eos and 1 < len(w) < new)
            assert late >= 3
            ahead = snap["engine"]["run_ahead"]
            assert ahead["late_lanes"] == late
            steps = snap["batch_occupancy"]["steps"]
            assert ahead["ahead"] + ahead["drained"] == steps
            assert ahead["drained"] == 1      # the first step alone
            srv.kv.assert_no_leaks()
            assert srv.kv.used_pages == 0 or srv.prefix is not None

    def test_only_an_eos_costs_a_lane_step(self):
        """What the host knows a step early it uses: streams that end
        by length waste nothing, and the one that ends by ``eos`` runs
        exactly one more position, whose token nobody sees."""
        model = _ra_model("gpt")
        ref = drive(model, [5, 7, 9], 8)
        eos = next(t for i, t in enumerate(ref[2:-2], 2)
                   if t not in ref[:i])
        with _ra_server(model, name="by-length") as srv:
            srv.start()
            futs = [srv.submit_generate([5, 7, 9 + i], max_new_tokens=4 + i)
                    for i in range(5)]
            assert [len(f.result(timeout=60)) for f in futs] == \
                [4, 5, 6, 7, 8]
            assert srv.metrics_snapshot()["engine"]["run_ahead"][
                "late_lanes"] == 0
        with _ra_server(model, eos_token_id=eos, name="by-eos") as srv:
            srv.start()
            assert srv.generate([5, 7, 9], max_new_tokens=8) == \
                until_eos(ref, eos)
        # (the stream ends at the harvest of its last step; the step
        # after it is harvested an iteration later: read after the end)
        snap = srv.metrics_snapshot()
        assert snap["engine"]["run_ahead"]["late_lanes"] == 1
        # the dropped token was not counted as generated
        assert snap["tokens_total"] == len(until_eos(ref, eos))

    def test_enqueue_precedes_the_harvest_before_it(self):
        """With a recording runner: step i+1 is enqueued before step i
        is harvested, step i is emitted before step i+2 is enqueued,
        and every step but the first is enqueued with its predecessor
        in flight (the counter says so)."""
        model = _ra_model("gpt")
        srv = _ra_server(model, name="order")
        rec = Recorder(srv)
        with srv:
            srv.start()
            srv.generate([5, 7, 9], max_new_tokens=7)
        n = len(rec.feeds)
        assert n == 6                      # 7 tokens: a prefill, 6 steps
        emits = [i for i, e in enumerate(rec.events) if e[0] == "emit"]
        assert len(emits) == 1 + n         # the prefill's, then a step's
        for i in range(n):
            (enq,), (har,) = rec.order(("enqueue", i)), rec.order(
                ("harvest", i))
            assert enq < har
            if i + 1 < n:
                assert rec.order(("enqueue", i + 1))[0] < har
            if i + 2 < n:
                # emission follows the fetch directly
                assert rec.events[har + 1][0] == "emit"
                assert har + 1 < rec.order(("enqueue", i + 2))[0]
        ahead = srv.metrics_snapshot()["engine"]["run_ahead"]
        assert ahead == {"ahead": n - 1, "drained": 1, "late_lanes": 0}

    def test_the_next_step_takes_its_tokens_from_the_device(self):
        """A step enqueued behind another is handed that step's tokens
        where they lie (or ``lane_tokens`` of them after an admission),
        never an array of the host's; both forms are one signature."""
        import jax
        model = _ra_model("gpt")
        want = drive(model, [4, 2], 4)
        srv = _ra_server(model, name="resident")
        with srv:
            srv.warmup(seq_buckets=[8])
            rec = Recorder(srv)
            misses = srv.metrics_snapshot()["compile_cache"]["misses"]
            srv.start()
            # (long enough that the second joins it for certain)
            first = srv.submit_generate([5, 7, 9], max_new_tokens=48)
            while len(first.tokens()) < 3:
                time.sleep(0.002)
            late = srv.submit_generate([4, 2], max_new_tokens=4)
            assert len(first.result(timeout=60)) == 48
            assert late.result(timeout=60) == want
            assert srv.metrics_snapshot()["compile_cache"][
                "misses"] == misses
        forms = [type(feeds[0]) for _, feeds, _ in rec.feeds]
        assert forms[0] is np.ndarray and forms[0] is not forms[1]
        assert all(isinstance(feeds[0], jax.Array)
                   for _, feeds, _ in rec.feeds[1:])
        for (prev, _, _), (_, feeds, _) in zip(rec.feeds, rec.feeds[1:]):
            assert feeds[0].dtype == np.int32
            assert feeds[0].shape == prev.tokens.shape
        # the step after the admission is not its predecessor's vector
        # itself: the prefill's token was written over it
        merged = [feeds[0] is not prev.tokens for (prev, _, _), (_, feeds, _)
                  in zip(rec.feeds, rec.feeds[1:])]
        assert sum(merged) == 1

    def test_warm_leaves_no_signature_for_traffic(self):
        """``_warm("decode")`` runs both forms of the tokens operand
        (and ``lane_tokens``): traffic compiles nothing."""
        from jax._src import monitoring
        model = _ra_model("gpt")
        compiles = []
        with _ra_server(model, name="warm-forms") as srv:
            assert srv._warm("decode", srv.max_batch) == 1
            assert srv._warm("decode", srv.max_batch) == 0
            srv.warmup(seq_buckets=[8])
            warmed = srv.metrics_snapshot()["compile_cache"]["misses"]

            def listen(name, *a, **kw):
                if "backend_compile" in name:
                    compiles.append(name)

            monitoring.register_event_duration_secs_listener(listen)
            try:
                srv.start()
                a = srv.submit_generate([5, 7, 9], max_new_tokens=6)
                b = srv.submit_generate([4, 2], max_new_tokens=3,
                                        temperature=0.9, seed=1)
                c = srv.submit_generate([8], max_new_tokens=5)
                for f in (a, b, c):
                    f.result(timeout=60)
                d = srv.generate([3, 3, 3], max_new_tokens=4)
            finally:
                monitoring.unregister_event_duration_listener(listen)
            assert len(d) == 4
            snap = srv.metrics_snapshot()
            assert snap["compile_cache"]["misses"] == warmed
            assert compiles == []

    def test_feeds_in_flight_outlive_a_release(self):
        """A step's feeds are its own: ``_release`` zeroes the lane's
        row of the engine's tables in place, and ``fill_row`` rewrites
        it for the next owner, while the step that was handed that row
        may still be running."""
        model = _ra_model("gpt")
        srv = _ra_server(model, max_batch=2, name="snapshot")
        rec = Recorder(srv)
        with srv:
            srv.start()
            futs = [srv.submit_generate([5, 7, 9], max_new_tokens=3),
                    srv.submit_generate([1, 2], max_new_tokens=9),
                    srv.submit_generate([6, 6, 6, 6, 6], max_new_tokens=4)]
            for f in futs:
                f.result(timeout=60)
            assert not srv._tables.any()        # every row was zeroed
        assert len(rec.feeds) >= 8
        for _, feeds, copies in rec.feeds:
            assert feeds[4] is not srv._tables
            assert not np.shares_memory(feeds[4], srv._tables)
            for handed, copy in zip(feeds[1:], copies):
                np.testing.assert_array_equal(handed, copy)
            assert feeds[4][feeds[2]].any(axis=1).all()  # live rows named

    @pytest.mark.parametrize("where", ["enqueue", "harvest"])
    def test_a_fault_on_either_side_fails_both_steps(self, where):
        """A program that raises when it is enqueued, and one whose
        error surfaces at its harvest with a successor already in
        flight on the pools it returned: the sequences of both steps
        fail, typed, their pages and lanes return, and the worker
        serves the next request."""
        import jax
        model = _ra_model("gpt")
        # (the references first: a drive traces the model, and so does
        # a live server's first program of a shape, in another thread)
        want_ok, want_again = (drive(model, [4, 4], 5),
                               drive(model, [5, 7, 9], 12))
        srv = _ra_server(model, max_batch=2, name=f"fault-{where}")
        runner = srv._runners[0]
        real, calls = getattr(runner, where), []

        def bomb(*a, **kw):
            if a[0] == "decode" or where == "harvest":
                calls.append(1)
                if len(calls) == 3:
                    raise RuntimeError(f"injected at {where}")
            return real(*a, **kw)

        with srv:
            bad = [srv.submit_generate(p, max_new_tokens=12)
                   for p in ([5, 7, 9], [1, 2, 3])]
            ok = srv.submit_generate([4, 4], max_new_tokens=5)
            setattr(runner, where, bomb)
            srv.start()
            for f in bad:
                with pytest.raises(RuntimeError, match="injected at"):
                    f.result(timeout=60)
                assert f.finish_reason == "error"
                assert 1 <= len(f.tokens()) < 12
            assert ok.result(timeout=60) == want_ok
            assert srv._inflight is None
            counters = srv.metrics_snapshot()["counters"]
            assert (counters["failed"], counters["completed"]) == (2, 1)
            assert srv.generate([5, 7, 9], max_new_tokens=12) == want_again
            srv.clear_prefix_cache()
            srv.kv.assert_no_leaks()
            assert srv.kv.free_pages == srv.kv.capacity
            assert all(not a.is_deleted() for a in
                       jax.tree_util.tree_leaves((srv.kv.k, srv.kv.v)))

    @pytest.mark.parametrize("what", ["park", "shutdown", "refresh_params",
                                      "clear_prefix_cache", "abort"])
    def test_whatever_touches_a_lane_drains_first(self, what):
        """A park, a drained shutdown, a weight swap, a prefix-cache
        clear and an abort all find the pipe empty: every enqueued
        step has been harvested (and its token emitted) when they act."""
        from paddle_tpu.serving.scheduling import (AdmissionController,
                                                   SchedulerPolicy,
                                                   TenantPolicy)
        model = _ra_model("gpt")
        want_long, want_gold = (drive(model, [5, 7, 9], 50),
                                drive(model, [1, 2, 3], 6))
        kw = {}
        if what == "park":
            kw = dict(num_pages=1 + 14, prefix_cache=False,
                      scheduler=AdmissionController(
                          policy=SchedulerPolicy(tenants={
                              "gold": TenantPolicy("gold",
                                                   priority="realtime"),
                              "bulk": TenantPolicy("bulk",
                                                   priority="batch")}),
                          name="t_drain_park"))
        srv = _ra_server(model, max_batch=2, name=f"drain-{what}", **kw)
        rec = Recorder(srv)
        seen = []

        def pipe():
            enq = sum(1 for e in rec.events if e[0] == "enqueue")
            har = sum(1 for e in rec.events if e[0] == "harvest")
            seen.append((enq, har, srv._inflight))

        for name in ("_park", "_do_abort", "_clear_prefix"):
            real = getattr(srv, name)

            def spy(*a, _real=real, **kw):
                if _real.__name__ != "_do_abort":
                    pipe()
                out = _real(*a, **kw)
                if _real.__name__ == "_do_abort":
                    pipe()      # it drains itself, then fails the rest
                return out

            setattr(srv, name, spy)
        refresh = srv.decoder.refresh_params
        srv.decoder.refresh_params = lambda: (pipe(), refresh())[1]
        srv.start()
        tenant = dict(tenant="bulk") if what == "park" else {}
        long = srv.submit_generate([5, 7, 9], max_new_tokens=50, **tenant)
        while len(long.tokens()) < 4:
            time.sleep(0.002)
        if what == "park":
            # all 14 pages are the long stream's: the realtime request
            # needs it parked
            gold = srv.submit_generate([1, 2, 3], max_new_tokens=6,
                                       tenant="gold")
            assert gold.result(timeout=60) == want_gold
            assert long.result(timeout=60) == want_long
            assert srv.metrics_snapshot()["counters"]["parked"] == 1
        elif what == "refresh_params":
            srv.refresh_params()
            assert long.result(timeout=60) == want_long
        elif what == "clear_prefix_cache":
            srv.clear_prefix_cache()
            assert long.result(timeout=60) == want_long
        if what == "abort":
            srv.shutdown(drain=False)
            assert long.finish_reason == "shutdown"
            assert long.tokens() == want_long[:len(long.tokens())]
        else:
            srv.shutdown(drain=True)
            assert long.finish_reason == "length"
        if what == "shutdown":
            pipe()
        assert seen, what
        for enq, har, inflight in seen:
            assert enq == har and inflight is None
        assert srv._inflight is None
        srv.kv.assert_no_leaks()

    def test_a_server_with_a_draft_never_runs_ahead(self):
        """Speculation judges each round's proposals on the host, so
        it harvests every program before it forms the next: nothing is
        in flight between iterations, and the counter stays at 0."""
        model = _ra_model("gpt")
        want = drive(model, [5, 7, 9], 8)
        srv = _ra_server(model, max_batch=2, draft_model=model, spec_k=3,
                         name="draft-drains")
        rec = Recorder(srv)
        with srv:
            srv.start()
            assert srv.generate([5, 7, 9], max_new_tokens=8) == want
            assert srv._inflight is None
            assert srv.metrics_snapshot()["engine"]["run_ahead"] == {
                "ahead": 0, "drained": 0, "late_lanes": 0}
        # every decode program the target ran was harvested at once
        assert not rec.feeds or all(
            rec.order(("harvest", i))[0] == rec.order(("enqueue", i))[0] + 1
            for i in range(len(rec.feeds)))
