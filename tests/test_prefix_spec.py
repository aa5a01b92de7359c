"""Shared-prefix KV caching (radix index + copy-on-write pages) and
speculative decoding (paddle_tpu/serving/generation/{prefix_cache,
spec_decode}.py + the engine wiring)."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import GPTForCausalLM, gpt_tiny
from paddle_tpu.serving.generation import (GenerationServer, PagedKVCache,
                                           PrefixCache, accept_tokens)
from paddle_tpu.serving.generation.model_fns import CachedDecoder


def make_model(seed=0, **kw):
    paddle.seed(seed)
    cfg = gpt_tiny(use_flash_attention=False, **kw)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m, cfg


def reference_stream(m, cfg, prompt, n):
    from paddle_tpu.distributed.fleet.utils import (
        HybridParallelInferenceHelper)
    helper = HybridParallelInferenceHelper(m, max_length=cfg.max_seq_len)
    out = helper._full_window_generate(
        np.asarray(prompt, np.int64)[None, :],
        min(cfg.max_seq_len, len(prompt) + n), 0.0, 0)
    return list(out[0, len(prompt):])


# ------------------------------------------------- allocator refcounts
class TestRefcounts:
    def test_shared_page_free_decrements_not_double_frees(self):
        """THE eviction-accounting fix: freeing a shared page drops one
        reference; the page returns to the free list only at zero."""
        m, _ = make_model()
        kv = PagedKVCache(m, num_pages=6, page_size=4)
        a = kv.alloc(2)
        kv.retain(a)                      # a second sequence shares both
        assert [kv.refcount(p) for p in a] == [2, 2]
        assert kv.free(a) == 0            # first free: nothing freed
        assert kv.free_pages == 3
        assert kv.evicted_pages_total == 0
        assert kv.free(a) == 2            # last reference: pages free
        assert kv.free_pages == 5
        assert kv.evicted_pages_total == 2
        with pytest.raises(RuntimeError, match="double free"):
            kv.free(a)
        kv.assert_no_leaks()

    def test_retain_requires_allocated_page(self):
        m, _ = make_model()
        kv = PagedKVCache(m, num_pages=4, page_size=4)
        with pytest.raises(ValueError, match="unallocated"):
            kv.retain([2])

    def test_leak_check_catches_lost_page(self):
        m, _ = make_model()
        kv = PagedKVCache(m, num_pages=4, page_size=4)
        kv.alloc(2)
        kv.assert_no_leaks()              # allocated-but-referenced: ok
        kv._ref.popitem()                 # simulate a lost reference
        assert not kv.leak_check()["ok"]
        with pytest.raises(AssertionError, match="leak"):
            kv.assert_no_leaks()


# ---------------------------------------------------------- radix index
class TestPrefixCacheIndex:
    def _kv(self, num_pages=10, page_size=4):
        m, _ = make_model()
        return PagedKVCache(m, num_pages=num_pages, page_size=page_size)

    def test_match_is_page_aligned_and_strict(self):
        kv = self._kv()
        pc = PrefixCache(kv)
        pages = kv.alloc(3)
        toks = list(range(12))
        pc.publish(toks, pages, n_tokens=12)     # 3 full pages
        # identical prompt: matched tokens must stay < len(prompt),
        # so only 2 of the 3 cached pages are shared
        n, shared = pc.match(toks)
        assert n == 8 and shared == pages[:2]
        # prompt one token longer: all 3 full pages match
        n, shared = pc.match(toks + [99])
        assert n == 12 and shared == pages[:3]
        # diverging second page: only the first matches
        toks2 = toks[:4] + [77] + toks[5:]
        n, shared = pc.match(toks2 + [99])
        assert n == 4 and shared == pages[:1]
        # sub-page prompt never matches
        assert pc.match(toks[:3]) == (0, [])

    def test_first_writer_wins_on_duplicate_content(self):
        kv = self._kv()
        pc = PrefixCache(kv)
        a = kv.alloc(1)
        b = kv.alloc(1)
        toks = [1, 2, 3, 4]
        assert pc.publish(toks, a, n_tokens=4) == 1
        assert pc.publish(toks, b, n_tokens=4) == 0   # duplicate kept out
        assert kv.refcount(a[0]) == 2     # owner + index
        assert kv.refcount(b[0]) == 1     # still private
        n, shared = pc.match(toks + [9])
        assert shared == a

    def test_lru_leaf_first_eviction_and_pinning(self):
        kv = self._kv()
        pc = PrefixCache(kv)
        pages = kv.alloc(3)
        toks = list(range(12))
        pc.publish(toks, pages, n_tokens=12)
        kv.release(pages)                 # sequence done: index-only refs
        assert kv.free_pages == 6
        # a second chain, touched later (more recently used)
        pages2 = kv.alloc(1)
        pc.publish([50, 51, 52, 53], pages2, n_tokens=4)
        kv.release(pages2)
        # evicting ONE page must take the first chain's LEAF (deepest,
        # least-recently-touched), never an interior node
        assert pc.evict(1) == 1
        n, shared = pc.match(toks + [99])
        assert n == 8 and shared == pages[:2]     # interior chain intact
        assert pc.match([50, 51, 52, 53, 9])[0] == 4
        # a page shared with a live sequence is pinned: retaining the
        # remaining chain pages blocks their eviction
        kv.retain(pages[:2])
        assert pc.evict(10) == 1          # only the unpinned 2nd chain
        kv.release(pages[:2])
        assert pc.evict(10) == 2          # unpinned now: chain drains
        assert kv.free_pages == kv.capacity
        kv.assert_no_leaks()


# ------------------------------------------- copy-on-write correctness
class TestCopyOnWrite:
    def test_shared_vs_private_chunked_prefill_bitwise_equal(self):
        """The COW invariant at the device level: a suffix prefill
        reading its prefix from SHARED pages is bit-identical to the
        same suffix prefill reading a PRIVATE copy of that prefix
        (same executables, different page ids)."""
        m, cfg = make_model()
        ps, pps = 4, 8
        dec = CachedDecoder(m, max_batch=2, page_size=ps,
                            pages_per_seq=pps)
        k, v = m.init_kv_pools(1 + 2 * pps, ps)
        rng = np.random.RandomState(3)
        prefix = rng.randint(0, cfg.vocab_size, 8)        # 2 full pages
        suffix = rng.randint(0, cfg.vocab_size, 5)
        # write the prefix twice, into disjoint page ranges, with the
        # same plain-prefill executable (bitwise-equal pool content)
        t_shared = np.zeros((2, pps), np.int32)
        t_private = np.zeros((2, pps), np.int32)
        t_shared[0, :pps] = 1 + np.arange(pps)
        t_private[0, :pps] = 1 + pps + np.arange(pps)
        ids = prefix[None, :].astype(np.int64).repeat(2, 0)
        lens = np.array([8, 0], np.int32)
        for tbl in (t_shared, t_private):
            _, _, k, v, _ = dec.prefill(ids, lens, tbl, None, None, k, v)
        outs = []
        for tbl in (t_shared, t_private):
            sid = np.zeros((2, 8), np.int64)
            sid[0, :5] = suffix
            _, last, k, v, _ = dec.prefill_chunked(
                sid, np.array([8, 0], np.int32),
                np.array([5, 0], np.int32), tbl, None, None, k, v)
            outs.append(np.asarray(last)[0])
        np.testing.assert_array_equal(outs[0], outs[1])

    def test_chunked_from_zero_matches_plain_prefill(self):
        """kind="chunked" at start=0 computes the same math as the
        windowed prefill path (gather vs in-window attention)."""
        m, cfg = make_model()
        ps, pps = 4, 8
        dec = CachedDecoder(m, max_batch=1, page_size=ps,
                            pages_per_seq=pps)
        k, v = m.init_kv_pools(1 + 2 * pps, ps)
        ids = np.random.RandomState(0).randint(
            0, cfg.vocab_size, (1, 7)).astype(np.int64)
        t1 = 1 + np.arange(pps, dtype=np.int32)[None, :]
        t2 = 1 + pps + np.arange(pps, dtype=np.int32)[None, :]
        _, last_a, k, v, _ = dec.prefill(
            ids, np.array([7], np.int32), t1, None, None, k, v)
        _, last_b, k, v, _ = dec.prefill_chunked(
            ids, np.zeros(1, np.int32), np.array([7], np.int32),
            t2, None, None, k, v)
        np.testing.assert_allclose(np.asarray(last_a),
                                   np.asarray(last_b),
                                   rtol=1e-5, atol=1e-6)

    def test_engine_divergent_streams_match_private_references(self):
        """Two sequences sharing a prefix then diverging both produce
        the exact private-cache greedy streams; the second admission is
        a recorded prefix hit."""
        m, cfg = make_model()
        rng = np.random.RandomState(1)
        shared = list(rng.randint(0, cfg.vocab_size, 16))
        pa = shared + [3, 1]
        pb = shared + [9, 9, 4]
        ra = reference_stream(m, cfg, pa, 8)
        rb = reference_stream(m, cfg, pb, 8)
        with GenerationServer(m, max_batch=2, page_size=8,
                              name="cow") as srv:
            assert srv.generate(pa, max_new_tokens=8) == ra
            assert srv.generate(pb, max_new_tokens=8) == rb
            snap = srv.metrics_snapshot()
            assert snap["prefix"]["hits"] == 1
            assert snap["prefix"]["tokens_reused"] == 16
            assert snap["kv_leak_check"]["ok"]
            # the shared preamble's suffix went through the chunked
            # path, not a full-window prefill
            sites = {s[0] for s in srv.decoder.compiled_signatures}
            assert "generate_chunked" in sites


# -------------------------------------------- refcount lifecycle (engine)
class TestEngineLifecycle:
    def test_admit_share_finish_evict_leaves_zero_leaks(self):
        m, cfg = make_model()
        rng = np.random.RandomState(2)
        pre = list(rng.randint(0, cfg.vocab_size, 24))
        with GenerationServer(m, max_batch=4, page_size=8,
                              name="leak") as srv:
            futs = [srv.submit_generate(pre + [i], max_new_tokens=6)
                    for i in range(6)]
            for f in futs:
                f.result(timeout=120)
            snap = srv.metrics_snapshot()
            # the first admission round (up to max_batch requests)
            # prefills cold — pages publish only after the write — so
            # the LATE JOINERS are the ones sharing, with sequences
            # still in flight
            assert snap["prefix"]["hits"] >= 2
            assert snap["prefix"]["tokens_reused"] == \
                24 * snap["prefix"]["hits"]
            assert snap["kv_leak_check"]["ok"]
            assert srv.active_sequences == 0
            srv.kv.assert_no_leaks()
            # every non-cached page is back on the free list
            cached = srv.prefix.cached_pages
            assert srv.kv.free_pages == srv.kv.capacity - cached

    def test_cache_eviction_under_pool_pressure(self):
        """Pool sized for ONE sequence: completed pages stay cached
        until the next admission reclaims them LRU — the cached twin of
        test_decode_serving's legacy page-reuse test."""
        m, cfg = make_model()
        p1, p2 = [5, 7, 9, 2, 8], [8, 6, 4, 1, 3]
        r1 = reference_stream(m, cfg, p1, 6)
        r2 = reference_stream(m, cfg, p2, 6)
        with GenerationServer(m, max_batch=2, page_size=4, num_pages=4,
                              max_seq_len=12, name="pressure") as srv:
            assert srv.generate(p1, max_new_tokens=6) == r1
            cached_before = srv.prefix.cached_pages
            assert cached_before > 0          # full pages stayed behind
            assert srv.generate(p2, max_new_tokens=6) == r2
            assert srv.prefix.pages_evicted >= 1
            assert srv.metrics_snapshot()["kv_leak_check"]["ok"]

    def test_refresh_params_invalidates_prefix_cache(self):
        """Weight swap: cached prefix K/V was computed under the OLD
        weights; refresh_params must clear the index so a hit can
        never serve stale state."""
        m, cfg = make_model()
        pre = list(np.random.RandomState(8).randint(
            0, cfg.vocab_size, 16))
        with GenerationServer(m, max_batch=2, page_size=8,
                              name="swap") as srv:
            srv.generate(pre + [1], max_new_tokens=4)
            assert srv.prefix.cached_pages > 0
            w = m.gpt.embeddings.word_embeddings.weight
            w.set_value(np.asarray(w.numpy()) * 0.7)
            srv.refresh_params()
            assert srv.prefix.cached_pages == 0
            ref = reference_stream(m, cfg, pre + [1], 4)
            assert srv.generate(pre + [1], max_new_tokens=4) == ref
            assert srv.metrics_snapshot()["kv_leak_check"]["ok"]

    def test_prefix_cache_off_engine_keeps_legacy_accounting(self):
        m, cfg = make_model()
        with GenerationServer(m, max_batch=2, page_size=4,
                              prefix_cache=False, name="off") as srv:
            srv.generate([5, 7, 9, 1, 2, 6], max_new_tokens=6)
            assert srv.prefix is None
            assert srv.kv.free_pages == srv.kv.capacity
            snap = srv.metrics_snapshot()
            assert snap["prefix"]["hits"] == 0
            assert snap["kv_leak_check"]["ok"]


# ------------------------------------------------- speculative decoding
class TestSpeculativeDecoding:
    def _draft(self, seed=7):
        m, _ = make_model(seed=seed)
        return m

    def test_greedy_parity_spec_on_off(self):
        """Spec on/off produce IDENTICAL greedy token streams, even
        with an uncorrelated draft (acceptance near zero)."""
        m, cfg = make_model()
        draft = self._draft()
        prompts = [[5, 7, 9, 2, 11], [3, 1, 4], [2, 6, 2, 6, 2, 6]]
        refs = []
        with GenerationServer(m, max_batch=4, page_size=8,
                              name="nospec") as srv:
            refs = [srv.generate(p, max_new_tokens=12) for p in prompts]
        with GenerationServer(m, max_batch=4, page_size=8,
                              draft_model=draft, spec_k=3,
                              name="spec") as srv:
            got = [srv.generate(p, max_new_tokens=12) for p in prompts]
            snap = srv.metrics_snapshot()
        assert got == refs
        assert snap["spec"]["proposed"] > 0
        assert 0.0 <= snap["spec"]["acceptance_rate"] <= 1.0

    def test_self_draft_full_acceptance_and_parity(self):
        """Draft == target: every proposal must be accepted (k + 1
        tokens per verify step) and the stream still matches."""
        m, cfg = make_model()
        ref = reference_stream(m, cfg, [5, 7, 9], 16)
        with GenerationServer(m, max_batch=2, page_size=8,
                              draft_model=m, spec_k=3,
                              name="selfspec") as srv:
            assert srv.generate([5, 7, 9], max_new_tokens=16) == ref
            snap = srv.metrics_snapshot()
            assert snap["spec"]["acceptance_rate"] == 1.0
            # 16 tokens at 4/step = 4 verify iterations
            assert snap["step_ms"]["decode"]["count"] == 4
            assert snap["kv_leak_check"]["ok"]

    def test_sampled_streams_request_deterministic(self):
        m, cfg = make_model()
        with GenerationServer(m, max_batch=2, page_size=8,
                              draft_model=self._draft(), spec_k=2,
                              name="specdet") as srv:
            a = srv.generate([5, 7, 9], max_new_tokens=10,
                             temperature=0.8, seed=3)
            b = srv.generate([5, 7, 9], max_new_tokens=10,
                             temperature=0.8, seed=3)
            assert a == b and len(a) == 10

    def test_eos_mid_speculation_stops_stream(self):
        m, cfg = make_model()
        ref = reference_stream(m, cfg, [5, 7, 9], 12)
        eos = int(ref[4])
        stop = ref.index(eos) + 1
        with GenerationServer(m, max_batch=2, page_size=8,
                              draft_model=m, spec_k=4,
                              eos_token_id=eos, name="speceos") as srv:
            fut = srv.submit_generate([5, 7, 9], max_new_tokens=12)
            assert fut.result(timeout=60) == ref[:stop]
            assert fut.finish_reason == "eos"

    def test_budget_cap_respected(self):
        """max_new smaller than a full acceptance round: the emission
        cap truncates, finish reason is length."""
        m, cfg = make_model()
        ref = reference_stream(m, cfg, [5, 7, 9], 2)
        with GenerationServer(m, max_batch=2, page_size=8,
                              draft_model=m, spec_k=6,
                              name="speccap") as srv:
            fut = srv.submit_generate([5, 7, 9], max_new_tokens=2)
            assert fut.result(timeout=60) == ref
            assert fut.finish_reason == "length"
            assert srv.metrics_snapshot()["kv_leak_check"]["ok"]

    def test_spec_with_prefix_sharing(self):
        """Speculation over shared prefix pages: the draft pool rides
        the same block tables, so hits stay bit-exact."""
        m, cfg = make_model()
        pre = list(np.random.RandomState(4).randint(
            0, cfg.vocab_size, 16))
        pa, pb = pre + [1], pre + [2]
        ra = reference_stream(m, cfg, pa, 8)
        rb = reference_stream(m, cfg, pb, 8)
        with GenerationServer(m, max_batch=2, page_size=8,
                              draft_model=m, spec_k=3,
                              name="specpfx") as srv:
            assert srv.generate(pa, max_new_tokens=8) == ra
            assert srv.generate(pb, max_new_tokens=8) == rb
            snap = srv.metrics_snapshot()
            assert snap["prefix"]["hits"] == 1
            assert snap["spec"]["acceptance_rate"] == 1.0

    def test_draft_shorter_context_rejected(self):
        m, cfg = make_model()
        short, _ = make_model(seed=9, max_seq_len=32)
        with pytest.raises(ValueError, match="max_seq_len"):
            GenerationServer(m, max_batch=2, page_size=8,
                             draft_model=short, spec_k=2,
                             name="specbad", start=False)


# --------------------------------------- accept/resample distribution
class TestAcceptResample:
    def test_greedy_walk(self):
        v = 8
        logits = np.full((4, v), -5.0)
        logits[0, 2] = logits[1, 3] = logits[2, 5] = logits[3, 6] = 5.0
        rng = np.random.RandomState(0)
        # all proposals match the argmax: k accepted + bonus
        toks, acc = accept_tokens(logits, np.array([2, 3, 5]), None,
                                  0.0, rng, max_emit=10)
        assert toks == [2, 3, 5, 6] and acc == 3
        # mismatch at the second proposal: emit argmax, stop
        toks, acc = accept_tokens(logits, np.array([2, 4, 5]), None,
                                  0.0, rng, max_emit=10)
        assert toks == [2, 3] and acc == 1
        # budget cap truncates mid-walk
        toks, acc = accept_tokens(logits, np.array([2, 3, 5]), None,
                                  0.0, rng, max_emit=2)
        assert toks == [2, 3] and acc == 2

    def test_eos_stops_walk(self):
        v = 8
        logits = np.full((3, v), -5.0)
        logits[0, 2] = logits[1, 3] = logits[2, 5] = 5.0
        toks, acc = accept_tokens(logits, np.array([2, 3]), None, 0.0,
                                  np.random.RandomState(0),
                                  max_emit=10, eos_token_id=2)
        assert toks == [2] and acc == 1

    def test_single_step_distribution_matches_target(self):
        """The Leviathan identity: accept-or-resample over a draft
        distribution reproduces the TARGET distribution exactly."""
        rng = np.random.RandomState(0)
        p_target = np.array([0.6, 0.3, 0.1])
        p_draft = np.array([0.2, 0.5, 0.3])
        t_logits = np.log(p_target)[None, :].repeat(2, 0)
        counts = np.zeros(3)
        n = 6000
        for _ in range(n):
            d = int(rng.choice(3, p=p_draft))
            toks, _ = accept_tokens(
                t_logits, np.array([d]), p_draft[None, :], 1.0, rng,
                max_emit=1)
            counts[toks[0]] += 1
        np.testing.assert_allclose(counts / n, p_target, atol=0.03)


# ------------------------------------ steady-state compile + manifest
class TestSteadyStateCompiles:
    def test_no_new_signatures_after_warmup_with_prefix_and_spec(self):
        """The decode-compiles-once invariant, extended: traffic that
        includes prefix-hit (chunked) admissions and verify steps adds
        ZERO signatures after warmup — for the target AND the draft."""
        m, cfg = make_model()
        srv = GenerationServer(m, max_batch=2, page_size=8,
                               draft_model=m, spec_k=3,
                               name="steady", start=False)
        srv.warmup()
        target_sigs = set(srv.decoder.compiled_signatures)
        draft_sigs = set(srv.draft.compiled_signatures)
        srv.start()
        pre = list(np.random.RandomState(5).randint(
            0, cfg.vocab_size, 16))
        srv.generate(pre + [1], max_new_tokens=6)        # cold prefill
        srv.generate(pre + [2], max_new_tokens=6)        # chunked hit
        assert srv.metrics_snapshot()["prefix"]["hits"] == 1
        assert set(srv.decoder.compiled_signatures) == target_sigs
        assert set(srv.draft.compiled_signatures) == draft_sigs
        verify_sigs = [s for s in target_sigs
                       if s[0] == "generate_verify"]
        assert len(verify_sigs) == 1
        srv.shutdown()


class TestWarmupManifestSites:
    @pytest.fixture
    def cache_dir(self, tmp_path):
        from paddle_tpu.compile_cache import reset_default_cache
        paddle.set_flags({"FLAGS_compile_cache_dir": str(tmp_path)})
        reset_default_cache()
        yield str(tmp_path)
        paddle.set_flags({"FLAGS_compile_cache_dir": ""})
        reset_default_cache()

    def test_verify_and_chunked_sites_replay(self, cache_dir):
        """Cold-start parity: a restarted engine replays the recorded
        chunked and verify signatures from the manifest, so traffic
        compiles nothing."""
        m, cfg = make_model()
        pre = list(np.random.RandomState(6).randint(
            0, cfg.vocab_size, 16))
        with GenerationServer(m, max_batch=2, page_size=8,
                              draft_model=m, spec_k=3,
                              name="man-pfx") as srv:
            srv.generate(pre + [1], max_new_tokens=6)
            srv.generate(pre + [2], max_new_tokens=6)
            man = srv.warmup_manifest
            sites = {e["site"] for e in man.specs()}
            assert sites == {"generate_prefill", "generate_chunked",
                             "generate_verify"}
            path = man.path
        m2, _ = make_model()
        srv2 = GenerationServer(m2, max_batch=2, page_size=8,
                                draft_model=m2, spec_k=3,
                                name="man-pfx2", start=False)
        srv2.warmup_from_manifest(path)
        sigs = set(srv2.decoder.compiled_signatures)
        assert any(s[0] == "generate_verify" for s in sigs)
        assert any(s[0] == "generate_chunked" for s in sigs)
        srv2.start()
        srv2.generate(pre + [1], max_new_tokens=6)
        srv2.generate(pre + [2], max_new_tokens=6)
        assert set(srv2.decoder.compiled_signatures) == sigs
        srv2.shutdown()

    @pytest.mark.parametrize("site", [
        "generate_prefill", "generate_chunked", "generate_decode",
        "generate_verify"])
    def test_manifest_feeds_are_the_compiled_signatures(self, cache_dir,
                                                        site):
        """The manifest's feeds are derived from the arrays a program
        was given: for every site traffic took, its specs name exactly
        the shapes and dtypes ``CachedDecoder.compiled_signatures``
        recorded there, and a second server warmed from the manifest
        compiles nothing new under the same traffic."""
        m, cfg = make_model()
        pre = list(np.random.RandomState(7).randint(
            0, cfg.vocab_size, 16))

        def server(model, name, **kw):
            if site == "generate_verify":    # only speculation verifies
                kw.update(draft_model=model, spec_k=3)
            return GenerationServer(model, max_batch=2, page_size=8,
                                    name=name, **kw)

        def traffic(srv):
            srv.generate(pre + [1], max_new_tokens=6)    # cold prefill
            srv.generate(pre + [2], max_new_tokens=6)    # chunked hit
            srv.generate([5, 7, 9], max_new_tokens=3)    # another bucket

        with server(m, f"sig-{site}") as srv:
            traffic(srv)
            man = srv.warmup_manifest
            recorded = sorted(
                [(tuple(shape), dtype) for shape, dtype in e["feeds"]]
                for e in man.specs(site=site))
            assert recorded
            # a signature is (site, feeds..., the pools' leaves)
            n = len(recorded[0])
            compiled = sorted(list(sig[1:1 + n]) for sig
                              in srv.decoder.compiled_signatures
                              if sig[0] == site)
            assert recorded == compiled
            path = man.path
        srv2 = server(make_model()[0], f"sig2-{site}", start=False)
        srv2.warmup_from_manifest(path)
        warmed = set(srv2.decoder.compiled_signatures)
        assert {sig for sig in warmed if sig[0] == site} == {
            sig for sig in srv.decoder.compiled_signatures
            if sig[0] == site}
        srv2.start()
        traffic(srv2)
        assert set(srv2.decoder.compiled_signatures) == warmed
        srv2.shutdown()


# ------------------------------------------------- tracing hookup
class TestTracingHookup:
    def test_prefix_attrs_and_verify_spans(self):
        """generate::prefill spans carry prefix-hit attrs; each
        speculative iteration records a generate::verify span."""
        import time

        from paddle_tpu.observability import tracing
        m, cfg = make_model()
        pre = list(np.random.RandomState(11).randint(
            0, cfg.vocab_size, 16))
        with GenerationServer(m, max_batch=2, page_size=8,
                              draft_model=m, spec_k=2,
                              name="trspec") as srv:
            srv.generate(pre + [1], max_new_tokens=4)   # cold: publish
            ctx = tracing.new_context(sampled=True)
            with tracing.use_context(ctx):
                fut = srv.submit_generate(pre + [2], max_new_tokens=4)
            fut.result(timeout=60)
            buf = tracing.default_buffer()
            deadline = time.monotonic() + 5
            while time.monotonic() < deadline and not [
                    s for s in buf.snapshot(trace_id=ctx.trace_id)
                    if s["stage"] == "request"]:
                time.sleep(0.02)
            spans = buf.snapshot(trace_id=ctx.trace_id)
            pf = next(s for s in spans if s["stage"] == "prefill")
            assert pf["attrs"]["prefix_hit"] is True
            assert pf["attrs"]["tokens_reused"] == 16
            vs = [s for s in spans if s["stage"] == "verify"]
            assert vs
            assert all(s["name"] == "generate::verify" for s in vs)
            assert all(s["attrs"]["proposed"] == 2
                       and "accepted" in s["attrs"]
                       and "draft_ms" in s["attrs"] for s in vs)


# ------------------------------------------------------------ statusz
class TestStatusz:
    def test_engines_statusz_reports_leak_check(self):
        from paddle_tpu.serving.generation import engines_statusz
        m, cfg = make_model()
        with GenerationServer(m, max_batch=2, page_size=8,
                              name="statz") as srv:
            srv.generate([5, 7, 9], max_new_tokens=3)
            snap = engines_statusz()
            assert "statz" in snap
            assert snap["statz"]["kv_leak_check"]["ok"]
            assert "prefix_cache" in snap["statz"]

    def test_httpd_statusz_includes_decode_engines(self):
        import json
        import urllib.request

        from paddle_tpu import observability
        m, cfg = make_model()
        with GenerationServer(m, max_batch=2, page_size=8,
                              name="statz-http") as srv:
            srv.generate([5, 7], max_new_tokens=2)
            httpd = observability.start_telemetry_server(port=0)
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{httpd.port}/statusz",
                        timeout=10) as r:
                    doc = json.loads(r.read())
                assert "decode_engines" in doc
                assert doc["decode_engines"]["statz-http"][
                    "kv_leak_check"]["ok"]
            finally:
                pass


# ------------------------------------------- admission exception safety
class TestAdmissionExceptionSafety:
    """Regression for the pdlint RP001 finding (pdlint v2): an
    exception raised between taking the page reservation and
    publishing it into ``self._slots`` leaked the pages — they never
    returned to the free list, so the pool drained request by request
    until admission wedged forever. The admission path now releases
    every reference on its exception paths."""

    def _server(self):
        m, _ = make_model()
        return GenerationServer(m, max_batch=2, page_size=4,
                                max_seq_len=16, prefix_cache=True,
                                name="adm-exc", start=False)

    def test_prefix_accounting_failure_releases_reservation(self):
        srv = self._server()
        srv.submit_generate([1, 2, 3], max_new_tokens=4)
        free0 = srv.kv.free_pages

        def boom(matched):
            raise RuntimeError("index corrupted")

        srv.prefix.note_admission = boom
        with pytest.raises(RuntimeError, match="index corrupted"):
            srv._admit_and_prefill()
        del srv.prefix.note_admission   # restore the class method
        assert srv.kv.free_pages == free0, \
            "admission failure leaked KV pages"
        srv.kv.assert_no_leaks()
        assert all(s is None for s in srv._slots)
        srv.shutdown(drain=False)

    def test_retain_failure_releases_fresh_pages(self):
        srv = self._server()
        srv.submit_generate([1, 2, 3], max_new_tokens=4)
        free0 = srv.kv.free_pages

        def boom(pages):
            raise RuntimeError("retain blew up")

        srv.kv.retain = boom
        with pytest.raises(RuntimeError, match="retain blew up"):
            srv._admit_and_prefill()
        del srv.kv.retain               # restore the class method
        assert srv.kv.free_pages == free0, \
            "retain failure leaked the fresh allocation"
        srv.kv.assert_no_leaks()
        srv.shutdown(drain=False)

    def test_admission_still_works_after_recovered_failure(self):
        """The barrier returns the pool to a state a later admission
        can use: after one rigged failure, the same request admits
        cleanly once the fault clears."""
        srv = self._server()
        srv.submit_generate([1, 2, 3], max_new_tokens=2)
        calls = {"n": 0}
        real = srv.prefix.note_admission

        def flaky(matched):
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError("transient")
            return real(matched)

        srv.prefix.note_admission = flaky
        with pytest.raises(RuntimeError):
            srv._admit_and_prefill()
        srv.kv.assert_no_leaks()
        srv.start()
        try:
            toks = srv.generate([1, 2, 3], max_new_tokens=2)
            assert len(toks) == 2
        finally:
            srv.shutdown()
        srv.kv.assert_no_leaks()
