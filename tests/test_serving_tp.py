"""Tensor-parallel serving (paddle_tpu/serving/mesh.py): mesh-sharded
decode over all four jit entry points, heads-sharded paged KV pools,
the fingerprint/compile-cache contract (a live mesh changes every key,
a 1-device mesh changes NOTHING), and the engine's prefix-cache /
refcount accounting under a sharded pool.

Runs on the 8-way virtual CPU device mesh tests/conftest.py forces."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed.mesh_utils import (build_mesh, get_global_mesh,
                                               set_global_mesh)
from paddle_tpu.models import GPTForCausalLM, gpt_tiny
from paddle_tpu.ops.paged_attention import kv_pool_shape
from paddle_tpu.serving.generation import GenerationServer
from paddle_tpu.serving.generation.model_fns import CachedDecoder
from paddle_tpu.serving.mesh import ServingMesh, serving_mesh_from_flags


def make_model(num_heads=8, **kw):
    """gpt_tiny with 8 heads so 'mp' up to the full 8-device mesh
    divides evenly (head_dim 64/8 = 8)."""
    paddle.seed(0)
    cfg = gpt_tiny(num_heads=num_heads, vocab_size=128, max_seq_len=64,
                   use_flash_attention=False, **kw)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m, cfg


def run_entry_points(model, mesh, use_pallas, kv_dtype=""):
    """Drive prefill, decode, chunked-prefill and verify through one
    CachedDecoder; returns the four logits arrays (host-side)."""
    dec = CachedDecoder(model, max_batch=2, page_size=8, pages_per_seq=4,
                        donate=False, max_positions=64,
                        use_pallas=use_pallas, kv_dtype=kv_dtype,
                        mesh=mesh)
    k, v = model.init_kv_pools(9, 8, kv_dtype or None)
    k, v = ServingMesh(mesh).place_pools(k, v)
    ids = np.array([[5, 6, 7, 8, 0, 0, 0, 0],
                    [9, 10, 11, 12, 13, 14, 0, 0]], np.int64)
    plens = np.array([4, 6], np.int32)
    tables = np.array([[1, 2, 3, 4], [5, 6, 7, 8]], np.int32)
    t0, last, k, v, _ = dec.prefill(ids, plens, tables, None, None, k, v)
    toks = np.array([3, 4], np.int64)
    act = np.array([True, True])
    ctx = plens + 1
    t1, dc, k, v, _ = dec.decode(toks, plens.copy(), act, ctx, tables,
                                 None, None, k, v)
    suffix = np.array([[20, 21, 0, 0], [22, 23, 24, 0]], np.int64)
    start = ctx.astype(np.int32)
    slens = np.array([2, 3], np.int32)
    t2, ck, k, v, _ = dec.prefill_chunked(suffix, start, slens, tables,
                                          None, None, k, v)
    draft = np.array([[30, 31], [32, 33]], np.int64)
    vstart = (start + slens).astype(np.int32)
    vlens = np.array([2, 2], np.int32)
    vf, k, v, _ = dec.verify(draft, vstart, vlens, tables, k, v)
    # the programs choose after the gather of a vocabulary-sharded head
    for chosen, logits in ((t0, last), (t1, dc), (t2, ck)):
        np.testing.assert_array_equal(np.asarray(chosen),
                                      np.asarray(logits).argmax(-1))
    return [np.asarray(x) for x in (last, dc, ck, vf)]


SITES = ("prefill", "decode", "chunked", "verify")


# ------------------------------------------------------------- parity
class TestShardedParity:
    """mp-sharded logits must match the single-shard path tightly on
    every entry point — same math, different partitioning."""

    def _assert_parity(self, use_pallas, kv_dtype="", stacked=False):
        m, _ = make_model(stacked=stacked)
        base = run_entry_points(m, None, use_pallas, kv_dtype)
        tp = run_entry_points(m, build_mesh({"mp": 8}), use_pallas,
                              kv_dtype)
        for site, a, b in zip(SITES, base, tp):
            np.testing.assert_allclose(
                a, b, rtol=2e-5, atol=2e-5,
                err_msg=f"{site} diverged under the mp=8 mesh")

    def test_pure_jax_parity_all_entry_points(self):
        self._assert_parity(use_pallas=False)

    def test_pallas_shard_map_matches_pure_jax_oracle(self):
        """The Pallas kernels dispatch PER SHARD under shard_map; the
        GSPMD-partitioned pure-JAX path is the oracle. Stacked, so the
        dispatch inside the layer scan is the one exercised."""
        m, _ = make_model(stacked=True)
        mesh = build_mesh({"mp": 8})
        oracle = run_entry_points(m, mesh, use_pallas=False)
        pallas = run_entry_points(m, mesh, use_pallas=True)
        for site, a, b in zip(SITES, oracle, pallas):
            np.testing.assert_allclose(
                a, b, rtol=2e-5, atol=2e-5,
                err_msg=f"{site}: sharded Pallas != sharded pure-JAX")

    def test_pallas_parity_all_entry_points(self):
        self._assert_parity(use_pallas=True)

    def test_stacked_scan_parity(self):
        self._assert_parity(use_pallas=False, stacked=True)

    def test_int8_quantized_pool_parity(self):
        self._assert_parity(use_pallas=True, kv_dtype="int8",
                            stacked=True)

    def test_pool_leaves_shard_heads_axis(self):
        """Per-shard pool leaves carry heads/mp — the whole point of
        the layout: one chip holds 1/mp of the KV bytes."""
        import jax
        m, _ = make_model()
        smesh = ServingMesh(build_mesh({"mp": 8}))
        k, v = m.init_kv_pools(9, 8, None)
        k, v = smesh.place_pools(k, v)
        spec = m.kv_cache_spec()
        for leaf in jax.tree_util.tree_leaves((k, v)):
            full = tuple(leaf.shape)
            assert full[-3:] == kv_pool_shape(
                9, 8, spec["num_kv_heads"], spec["head_dim"])
            local = tuple(leaf.addressable_shards[0].data.shape)
            # heads are folded into the last axis, a chip's share of it
            # is whole heads
            assert local[-1] == full[-1] // 8 \
                == spec["num_kv_heads"] // 8 * spec["head_dim"], \
                f"heads not sharded: {local} vs {full}"
            assert local[:-1] == full[:-1]

    def test_int8_pool_scales_shard_with_values(self):
        import jax
        m, _ = make_model()
        smesh = ServingMesh(build_mesh({"mp": 8}))
        k, v = m.init_kv_pools(9, 8, "int8")
        k, v = smesh.place_pools(k, v)
        for leaf in jax.tree_util.tree_leaves((k, v)):
            local = tuple(leaf.addressable_shards[0].data.shape)
            # values [..., H*D] and scale planes [..., H] alike
            assert local[-1] == leaf.shape[-1] // 8
            assert local[:-1] == tuple(leaf.shape[:-1])


# ------------------------------------------------------------- guards
class TestMeshGuards:
    def test_heads_must_divide_mp(self):
        m, _ = make_model(num_heads=4)      # 4 % 8 != 0
        with pytest.raises(ValueError, match="head"):
            CachedDecoder(m, max_batch=2, page_size=8, pages_per_seq=4,
                          donate=False, mesh=build_mesh({"mp": 8}))

    def test_dp_only_global_mesh_does_not_raise(self):
        """Regression: the old guard rejected ANY live global mesh from
        cached decode, including pure data-parallel — dp replicas serve
        independently and are fine."""
        m, _ = make_model()
        assert get_global_mesh() is None
        set_global_mesh(build_mesh({"dp": 2}))
        try:
            out = run_entry_points(m, None, use_pallas=False)
            assert all(np.isfinite(x).all() for x in out)
        finally:
            set_global_mesh(None)

    @pytest.mark.parametrize("axis", ["pp", "sep"])
    def test_unsupported_axis_raises_naming_it(self, axis):
        """pp/sep genuinely cannot cross the paged-pool scan; the error
        must name the offending axis, not blanket-reject meshes. The
        guard sits in the stacked layer scan — the path whose carried
        pool state pp/sep would actually break."""
        m, _ = make_model(stacked=True)
        set_global_mesh(build_mesh({axis: 2}))
        try:
            with pytest.raises(NotImplementedError, match=f"'{axis}'"):
                run_entry_points(m, None, use_pallas=False)
        finally:
            set_global_mesh(None)


# ------------------------------------- fingerprints & compile-cache keys
class TestCacheIdentity:
    def _decoder(self, m, mesh):
        return CachedDecoder(m, max_batch=2, page_size=8,
                             pages_per_seq=4, donate=False,
                             use_pallas=False, mesh=mesh)

    def test_one_device_mesh_is_byte_identical(self):
        """A 1-device mesh must degrade to the single-shard path with
        the SAME fingerprint and compile-cache key — no recompiles, no
        cold persistent cache after enabling the mesh config knob on a
        single-chip host."""
        import jax

        from paddle_tpu.compile_cache import cache_key
        m, _ = make_model()
        meshless = self._decoder(m, None)
        one_dev = self._decoder(m, build_mesh({"mp": 1},
                                              jax.devices()[:1]))
        assert not one_dev.serving_mesh.live
        assert meshless.fingerprint() == one_dev.fingerprint()
        args = (np.zeros((2, 8), np.int64),)
        k_a, _ = cache_key(meshless.fingerprint(), args,
                           mesh=meshless.serving_mesh.mesh_for_cache_key())
        k_b, _ = cache_key(one_dev.fingerprint(), args,
                           mesh=one_dev.serving_mesh.mesh_for_cache_key())
        assert k_a == k_b

    def test_live_mesh_misses_every_key(self):
        """mesh change => compile-cache miss: meshless, mp=4 and mp=8
        all produce distinct fingerprints AND distinct cache keys."""
        import jax

        from paddle_tpu.compile_cache import cache_key
        m, _ = make_model()
        decs = [self._decoder(m, None),
                self._decoder(m, build_mesh({"mp": 4},
                                            jax.devices()[:4])),
                self._decoder(m, build_mesh({"mp": 8}))]
        fps = [d.fingerprint() for d in decs]
        assert len(set(fps)) == 3
        args = (np.zeros((2, 8), np.int64),)
        keys = [cache_key(d.fingerprint(), args,
                          mesh=d.serving_mesh.mesh_for_cache_key())[0]
                for d in decs]
        assert len(set(keys)) == 3

    def test_spec_tree_joins_live_fingerprint_only(self):
        m, _ = make_model()
        inert = ServingMesh(None)
        live = ServingMesh(build_mesh({"mp": 8}))
        assert inert.fingerprint_parts(m) is None
        parts = live.fingerprint_parts(m)
        assert parts["axes"] == {"mp": 8}
        assert parts["spec_hash"]


# ------------------------------------------------- engine under a mesh
class TestEngineUnderMesh:
    def test_prefix_hit_cow_divergence_and_leak_check(self):
        """The host-side radix index, COW divergence and refcount
        accounting are layout-agnostic: under a sharded pool the
        prefix hit still lands, the divergent streams still match the
        meshless engine's, and leak_check() stays clean across
        admit/share/finish."""
        m, cfg = make_model()
        rng = np.random.RandomState(1)
        shared = list(rng.randint(0, cfg.vocab_size, 16))
        pa = shared + [3, 1]
        pb = shared + [9, 9, 4]
        with GenerationServer(m, max_batch=2, page_size=8,
                              name="tp-ref") as ref_srv:
            ra = ref_srv.generate(pa, max_new_tokens=6)
            rb = ref_srv.generate(pb, max_new_tokens=6)
        mesh = build_mesh({"mp": 8})
        with GenerationServer(m, max_batch=2, page_size=8,
                              mesh=mesh, name="tp-cow") as srv:
            assert srv.generate(pa, max_new_tokens=6) == ra
            assert srv.generate(pb, max_new_tokens=6) == rb
            snap = srv.metrics_snapshot()
            assert snap["prefix"]["hits"] == 1
            assert snap["prefix"]["tokens_reused"] == 16
            assert snap["kv_leak_check"]["ok"]
            srv.kv.assert_no_leaks()

    def test_statusz_reports_mesh_and_per_chip_bytes(self):
        m, _ = make_model()
        mesh = build_mesh({"mp": 8})
        with GenerationServer(m, max_batch=2, page_size=8,
                              mesh=mesh, name="tp-statusz") as srv:
            srv.generate([5, 6, 7], max_new_tokens=2)
            sz = srv.statusz()
            ms = sz["serving_mesh"]
            assert ms["live"] and ms["axes"] == {"mp": 8}
            assert ms["devices"] == 8
            assert ms["per_chip_kv_pool_bytes"] * 8 == \
                srv.kv.pool_bytes()

    def test_meshless_statusz_has_no_mesh_section(self):
        m, _ = make_model()
        with GenerationServer(m, max_batch=2, page_size=8,
                              name="tp-nomesh") as srv:
            assert "serving_mesh" not in srv.statusz()


# ----------------------------------------------------------- flag knob
class TestServingMeshFlag:
    def test_default_flag_is_inert(self):
        assert not serving_mesh_from_flags().live

    def test_flag_builds_mp_mesh(self):
        paddle.set_flags({"FLAGS_serving_mesh_mp": 8})
        try:
            sm = serving_mesh_from_flags()
            assert sm.live and sm.mp == 8
        finally:
            paddle.set_flags({"FLAGS_serving_mesh_mp": 1})
