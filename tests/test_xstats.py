"""Executable cost & roofline observability (PR 13).

Covers the xstats registry populated from every compile site, the
cost-model MFU join with the continuous step profiler (including the
acceptance cross-check against bench.py's hand-derived 6ND MFU), the
``/execz`` and ``/profilez`` HTTP surfaces on the telemetry httpd /
replica workers / fleet router, anomaly-triggered profile capture, and
the endpoint conformance contract across every documented surface.
"""
import json
import os
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.framework.flags import flag_value, set_flags
from paddle_tpu.observability import stepprof, xstats
from paddle_tpu.observability.httpd import TelemetryServer
from paddle_tpu.observability.registry import default_registry

_FLAG_NAMES = (
    "FLAGS_xstats_enable", "FLAGS_xstats_max_entries",
    "FLAGS_device_peak_flops", "FLAGS_device_peak_bytes_per_s",
    "FLAGS_profile_dir", "FLAGS_profile_ring", "FLAGS_profile_max_ms",
    "FLAGS_profile_min_interval_s", "FLAGS_profile_on_anomaly",
    "FLAGS_profile_anomaly_ms", "FLAGS_compile_cache_dir",
)


@pytest.fixture()
def fresh_xstats():
    """Fresh registry + capture ring and restored flags per test."""
    saved = {n: flag_value(n) for n in _FLAG_NAMES}
    xstats.reset_for_tests()
    yield
    set_flags(saved)
    xstats.reset_for_tests()


def _jit_pair(shape=(8, 16)):
    """A compiled function + its operands for registry unit tests."""
    import jax
    import jax.numpy as jnp

    def f(x, w):
        return jnp.tanh(x @ w)

    x = jnp.ones(shape)
    w = jnp.ones((shape[1], shape[1]))
    return jax.jit(f), (x, w)


def _gauge_value(name, **labels):
    fam = default_registry().get(name)
    if fam is None:
        return None
    for lab, child in fam.collect():
        if all(lab.get(k) == v for k, v in labels.items()):
            return child.value
    return None


# ========================================================== registry
class TestExecRegistry:
    def test_register_dedupes_and_merges_provenance(self, fresh_xstats):
        jf, args = _jit_pair()
        sig = xstats.signature_of(args)
        e1 = xstats.register_executable(
            "train_step", sig, provenance={"cache": "off"})
        e2 = xstats.register_executable(
            "train_step", sig, provenance={"cache": "hit"})
        assert e1 is e2
        assert e1.provenance["cache"] == "hit"
        assert len(xstats.default_exec_registry().entries()) == 1

    def test_compiled_tier_analysis(self, fresh_xstats):
        jf, args = _jit_pair()
        compiled = jf.lower(*args).compile()
        ent = xstats.register_executable(
            "train_step", xstats.signature_of(args), compiled=compiled)
        ana = xstats.default_exec_registry().ensure_analysis(ent)
        assert ana["source"] == "compiled"
        assert ana["flops"] > 0 and ana["bytes_accessed"] > 0
        # memory_analysis fields present on the compiled tier
        assert ana["arg_bytes"] > 0 and ana["out_bytes"] > 0
        # the executable handle is dropped once analysis landed
        assert ent._compiled is None

    def test_thunk_tier_analysis_is_lazy(self, fresh_xstats):
        jf, args = _jit_pair()
        calls = []

        def thunk():
            calls.append(1)
            return jf.lower(*args)

        ent = xstats.register_executable(
            "generate_decode", xstats.signature_of(args),
            lower_thunk=thunk)
        assert not calls          # registration never lowers
        ana = xstats.default_exec_registry().ensure_analysis(ent)
        assert calls == [1]
        assert ana["source"] == "lowered" and ana["flops"] > 0
        # signature-derived operand bytes stand in for memory_analysis
        assert ana["arg_bytes"] == ent.sig_arg_bytes > 0

    def test_eviction_bound(self, fresh_xstats):
        set_flags({"FLAGS_xstats_max_entries": 3})
        for i in range(5):
            xstats.register_executable(
                "jit", ((((i,), "float32"),)))
        reg = xstats.default_exec_registry()
        assert len(reg.entries()) == 3
        shapes = [e.signature[0][0] for e in reg.entries()]
        assert shapes == [(2,), (3,), (4,)]     # oldest evicted

    def test_disabled_flag_short_circuits(self, fresh_xstats):
        set_flags({"FLAGS_xstats_enable": False})
        assert xstats.register_executable("jit", ()) is None
        xstats.on_step_envelope({"kind": "train", "wall_ms": 5.0})
        assert xstats.default_exec_registry().entries() == []

    def test_device_peaks_flag_override(self, fresh_xstats):
        set_flags({"FLAGS_device_peak_flops": 1e12,
                   "FLAGS_device_peak_bytes_per_s": 1e11})
        peaks = xstats.device_peaks()
        assert peaks == {"flops": 1e12, "bytes_per_s": 1e11,
                         "source": "flag", "platform": "cpu",
                         "device_kind": "cpu"}

    def test_device_peaks_unknown_on_bare_cpu(self, fresh_xstats):
        set_flags({"FLAGS_device_peak_flops": 0.0,
                   "FLAGS_device_peak_bytes_per_s": 0.0})
        peaks = xstats.device_peaks()
        assert peaks["source"] == "unknown"
        assert peaks["flops"] == 0.0

    def test_roofline_classification(self, fresh_xstats):
        set_flags({"FLAGS_device_peak_flops": 1e12,
                   "FLAGS_device_peak_bytes_per_s": 1e9})  # ridge 1000
        ent = xstats.register_executable("train_step", ())
        ent.analysis = {"flops": 1e9, "bytes_accessed": 1e5}  # 10000
        assert ent.roofline()["classification"] == "compute_bound"
        ent.analysis = {"flops": 1e6, "bytes_accessed": 1e5}  # 10
        r = ent.roofline()
        assert r["classification"] == "memory_bound"
        assert r["ridge"] == 1000.0


# ====================================================== stepprof join
class TestStepprofJoin:
    def test_envelope_sets_mfu_and_bw_gauges(self, fresh_xstats):
        set_flags({"FLAGS_device_peak_flops": 1e9,
                   "FLAGS_device_peak_bytes_per_s": 1e9})
        jf, args = _jit_pair()
        compiled = jf.lower(*args).compile()
        ent = xstats.register_executable(
            "train_step", xstats.signature_of(args), compiled=compiled)
        reg = xstats.default_exec_registry()
        ana = reg.ensure_analysis(ent)
        env = {"kind": "train", "wall_ms": 10.0}
        xstats.on_step_envelope(env)
        expect = ana["flops"] / (0.010 * 1e9)
        assert _gauge_value("paddle_mfu", kind="train") == \
            pytest.approx(expect)
        assert env["mfu"] == pytest.approx(expect, rel=1e-3)
        assert _gauge_value("paddle_exec_bw_util", kind="train") == \
            pytest.approx(ana["bytes_accessed"] / (0.010 * 1e9))
        kinds = xstats.execz_payload(compute=False)["kinds"]
        assert kinds["train"]["steps"] == 1
        assert kinds["train"]["roofline"] in ("compute_bound",
                                              "memory_bound")

    def test_join_never_computes_analysis_on_hot_path(self,
                                                      fresh_xstats):
        jf, args = _jit_pair()
        ent = xstats.register_executable(
            "train_step", xstats.signature_of(args),
            lower_thunk=lambda: jf.lower(*args))
        xstats.on_step_envelope({"kind": "train", "wall_ms": 5.0})
        assert ent.analysis is None          # untouched
        assert xstats.execz_payload(compute=False)["kinds"] == {}

    def test_stepprof_record_step_flows_into_join(self, fresh_xstats):
        set_flags({"FLAGS_device_peak_flops": 1e9})
        jf, args = _jit_pair()
        ent = xstats.register_executable(
            "generate_decode", xstats.signature_of(args),
            compiled=jf.lower(*args).compile())
        xstats.default_exec_registry().ensure_analysis(ent)
        prof = stepprof.StepProfiler(min_samples=1000)
        env = prof.record_step(4.0, kind="decode")
        assert "mfu" in env
        assert _gauge_value("paddle_mfu", kind="decode") > 0


# ==================================== MFU vs hand-derived 6ND (bench)
class TestMFUAgreement:
    def test_train_mfu_agrees_with_hand_6nd_within_15pct(
            self, fresh_xstats):
        """The acceptance cross-check: paddle_mfu{kind=train} computed
        from registry FLOPs x stepprof durations must agree with the
        bench.py hand formula (6*N + 12*L*H*S FLOPs/token over the
        same measured duration) within 15% on the CPU test preset,
        with the peak overridden via flag."""
        from paddle_tpu.jit.train_step import TrainStep
        from paddle_tpu.models.gpt import (GPTConfig, GPTForCausalLM,
                                           GPTPretrainingCriterion)
        peak = 1e12
        set_flags({"FLAGS_device_peak_flops": peak})
        prev = stepprof.set_default_profiler(
            stepprof.StepProfiler(min_samples=10_000))
        try:
            paddle.seed(0)
            b, s = 8, 64
            cfg = GPTConfig(vocab_size=256, hidden_size=128,
                            num_layers=2, num_heads=4, max_seq_len=s,
                            use_flash_attention=False)
            model = GPTForCausalLM(cfg)
            crit = GPTPretrainingCriterion()
            opt = paddle.optimizer.AdamW(
                learning_rate=1e-4, parameters=model.parameters())
            step = TrainStep(model, lambda out, y: crit(out, y), opt)
            ids = paddle.to_tensor(
                np.random.randint(0, 256, (b, s)).astype("int64"))
            step(ids, ids)                    # compile + register
            xstats.execz_payload()            # materialize analysis
            step(ids, ids)                    # joined step
            envs = stepprof.default_profiler().envelopes(kind="train")
            env = envs[-1]
            mfu_gauge = _gauge_value("paddle_mfu", kind="train")
            assert mfu_gauge is not None and mfu_gauge > 0
            assert env["mfu"] == pytest.approx(mfu_gauge, abs=1e-6)
            # bench.py's hand-derived MFU over the SAME measured step
            n_params = model.num_params()
            attn = 12 * cfg.num_layers * cfg.hidden_size * s
            flops_per_token = 6 * n_params + attn
            wall_s = env["wall_ms"] / 1e3
            hand_mfu = (b * s * flops_per_token) / (wall_s * peak)
            assert mfu_gauge == pytest.approx(hand_mfu, rel=0.15)
        finally:
            stepprof.set_default_profiler(prev)


# =================================================== compile sites
class TestCompileSites:
    def test_all_sites_register_with_nonzero_flops_and_memory(
            self, fresh_xstats, tmp_path):
        """Acceptance: /execz over HTTP shows every compile site with
        nonzero FLOPs and memory — StaticFunction (jit), TrainStep
        (train_step), Predictor (serving), and the CachedDecoder
        prefill/decode entry points."""
        from paddle_tpu import nn
        from paddle_tpu.jit.train_step import TrainStep
        from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny
        from paddle_tpu.serving.generation import GenerationServer
        from tools.bench_serving import build_predictor

        # jit site (to_static)
        lin = nn.Linear(8, 8)
        sf = paddle.jit.to_static(lin)
        with paddle.no_grad():
            sf(paddle.to_tensor(np.ones((2, 8), np.float32)))

        # train_step site
        m = nn.Linear(8, 8)
        opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                     parameters=m.parameters())
        step = TrainStep(m, lambda out, y: ((out - y) ** 2).mean(),
                         opt)
        x = paddle.to_tensor(np.ones((4, 8), np.float32))
        step(x, x)

        # serving site (Predictor.dispatch_many)
        pred = build_predictor(str(tmp_path / "pred"))
        pred.run_many([[np.ones((1, 64), np.float32)]])

        # generate_prefill / generate_decode sites
        paddle.seed(0)
        gm = GPTForCausalLM(gpt_tiny(use_flash_attention=False))
        gm.eval()
        with GenerationServer(gm, max_batch=2, page_size=8,
                              name="xstats-sites") as srv:
            srv.submit_generate([1, 2, 3], max_new_tokens=3).result(
                timeout=120)

        with TelemetryServer(port=0) as tsrv:
            with urllib.request.urlopen(tsrv.url("/execz")) as r:
                assert r.status == 200
                doc = json.loads(r.read())
        sites = doc["sites"]
        for site in ("jit", "train_step", "serving",
                     "generate_prefill", "generate_decode"):
            assert site in sites, f"{site} missing from /execz"
            assert sites[site]["flops"] > 0, site
        for e in doc["entries"]:
            assert e["analysis"], (e["site"], e["analysis_error"])
            assert e["analysis"]["flops"] > 0, e["site"]
            assert e["analysis"]["arg_bytes"] > 0, e["site"]
        # provenance present: without a cache dir every site is "off"
        assert {e["provenance"].get("cache")
                for e in doc["entries"]} == {"off"}

    def test_cache_hit_miss_provenance(self, fresh_xstats, tmp_path):
        """Through the persistent cache, get_or_compile stamps
        miss/hit provenance (and the stored tier) on the entry."""
        from paddle_tpu import compile_cache as cc
        from paddle_tpu.jit.train_step import TrainStep
        from paddle_tpu import nn
        set_flags({"FLAGS_compile_cache_dir": str(tmp_path / "cc")})
        cc.reset_default_cache()
        try:
            def make_step():
                paddle.seed(0)
                m = nn.Linear(8, 8)
                opt = paddle.optimizer.AdamW(
                    learning_rate=1e-3, parameters=m.parameters())
                return TrainStep(
                    m, lambda out, y: ((out - y) ** 2).mean(), opt)

            x = paddle.to_tensor(np.ones((4, 8), np.float32))
            make_step()(x, x)
            ents = [e for e in
                    xstats.default_exec_registry().entries()
                    if e.site == "train_step"]
            assert len(ents) == 1
            assert ents[0].provenance["cache"] == "miss"
            assert ents[0].provenance.get("tier") in (
                "executable", "stablehlo")
            assert ents[0].dispatches == 1
            # a fresh TrainStep (fresh memo) re-registers the same
            # signature as a HIT served from the persistent cache
            xstats.reset_for_tests()
            make_step()(x, x)
            ents = [e for e in
                    xstats.default_exec_registry().entries()
                    if e.site == "train_step"]
            assert len(ents) == 1
            assert ents[0].provenance["cache"] == "hit"
            ana = xstats.default_exec_registry().ensure_analysis(
                ents[0])
            assert ana and ana["flops"] > 0
        finally:
            set_flags({"FLAGS_compile_cache_dir": ""})
            cc.reset_default_cache()


# ===================================================== profile capture
class TestProfileCapture:
    def test_capture_listed_and_loadable(self, fresh_xstats, tmp_path):
        set_flags({"FLAGS_profile_dir": str(tmp_path / "ring"),
                   "FLAGS_profile_min_interval_s": 0.0})
        got = xstats.capture_profile(20, reason="manual")
        assert got is not None
        meta, doc = got
        assert os.path.exists(meta["path"])
        assert doc["paddle_profilez"]["reason"] == "manual"
        listed = xstats.profilez_payload()["artifacts"]
        assert [a["id"] for a in listed] == [meta["id"]]
        from paddle_tpu.profiler import load_profiler_result
        res = load_profiler_result(meta["path"])
        assert res.time_range_summary()["n_events"] == meta["events"]

    def test_ring_bound_evicts_oldest_artifact_file(self, fresh_xstats,
                                                    tmp_path):
        set_flags({"FLAGS_profile_dir": str(tmp_path / "ring"),
                   "FLAGS_profile_min_interval_s": 0.0,
                   "FLAGS_profile_ring": 2})
        metas = [xstats.capture_profile(5)[0] for _ in range(3)]
        arts = xstats.profilez_payload()["artifacts"]
        assert [a["id"] for a in arts] == [m["id"] for m in metas[1:]]
        assert not os.path.exists(metas[0]["path"])
        assert all(os.path.exists(m["path"]) for m in metas[1:])

    def test_rate_limit_refuses_second_capture(self, fresh_xstats,
                                               tmp_path):
        set_flags({"FLAGS_profile_dir": str(tmp_path / "ring"),
                   "FLAGS_profile_min_interval_s": 3600.0})
        assert xstats.capture_profile(5) is not None
        assert xstats.capture_profile(5) is None
        with TelemetryServer(port=0) as srv:
            req = urllib.request.Request(
                srv.url("/profilez?duration_ms=5"))
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(req)
            assert ei.value.code == 429

    def test_duration_clamped_to_max(self, fresh_xstats, tmp_path):
        set_flags({"FLAGS_profile_dir": str(tmp_path / "ring"),
                   "FLAGS_profile_min_interval_s": 0.0,
                   "FLAGS_profile_max_ms": 25.0})
        meta, _ = xstats.capture_profile(60_000)
        assert meta["duration_ms"] == 25.0

    def test_anomaly_triggers_exactly_one_rate_limited_capture(
            self, fresh_xstats, tmp_path):
        """Acceptance: an injected stepprof straggler produces exactly
        ONE auto-capture (rate-limited across the burst) whose
        artifact is listed by /profilez, linked to the promoted
        straggler span's trace id, and loadable by
        load_profiler_result."""
        from paddle_tpu.observability import tracing
        set_flags({"FLAGS_profile_dir": str(tmp_path / "ring"),
                   "FLAGS_profile_min_interval_s": 3600.0,
                   "FLAGS_profile_on_anomaly": True,
                   "FLAGS_profile_anomaly_ms": 20.0})
        buf = tracing.SpanBuffer()
        prev_buf = tracing.set_default_buffer(buf)
        prof = stepprof.StepProfiler(min_samples=8, anomaly_k=4.0,
                                     window=64)
        try:
            for i in range(16):
                prof.record_step(10.0, kind="train", step=i)
            for i in range(3):              # straggler burst
                env = prof.record_step(400.0, kind="train",
                                       step=100 + i)
                assert "anomaly" in env
            xstats.wait_captures(timeout=30.0)
        finally:
            tracing.set_default_buffer(prev_buf)
        arts = xstats.profilez_payload()["artifacts"]
        anomaly_arts = [a for a in arts if a["reason"] == "anomaly"]
        assert len(anomaly_arts) == 1       # burst -> ONE capture
        art = anomaly_arts[0]
        stragglers = [s for s in buf.snapshot()
                      if s["name"] == "stepprof::straggler"]
        assert art["trace_id"] in {s["trace_id"] for s in stragglers}
        from paddle_tpu.profiler import load_profiler_result
        res = load_profiler_result(art["path"])
        assert res.time_range_summary()["n_events"] >= 0

    def test_anomaly_capture_stays_dark_unless_armed(self,
                                                     fresh_xstats,
                                                     tmp_path):
        set_flags({"FLAGS_profile_dir": str(tmp_path / "ring"),
                   "FLAGS_profile_min_interval_s": 0.0,
                   "FLAGS_profile_on_anomaly": False})
        prof = stepprof.StepProfiler(min_samples=4, anomaly_k=4.0)
        for i in range(8):
            prof.record_step(10.0, kind="train", step=i)
        assert "anomaly" in prof.record_step(500.0, kind="train")
        xstats.wait_captures(timeout=5.0)
        assert xstats.profilez_payload()["artifacts"] == []


# ======================================================== fleet surfaces
class TestFleetSurfaces:
    def _fleet(self, n=2):
        from paddle_tpu.serving import fleet
        factory = fleet.ThreadReplicaFactory(
            lambda rid: fleet.StubBackend(device_ms=1.0))
        sup = fleet.ReplicaSupervisor(factory, n,
                                      poll_interval_s=0.05).start()
        router = fleet.FleetRouter(supervisor=sup, name="t_xstats")
        return sup, router

    def test_router_execz_merges_two_replicas(self, fresh_xstats):
        """Acceptance: the RouterApp /execz aggregation merges >=2
        replicas (thread replicas share this process's registry; the
        fan-out and stitch are the real HTTP path either way)."""
        from paddle_tpu.serving import fleet
        jf, args = _jit_pair()
        ent = xstats.register_executable(
            "serving", xstats.signature_of(args),
            compiled=jf.lower(*args).compile())
        xstats.default_exec_registry().ensure_analysis(ent)
        sup, router = self._fleet()
        app = fleet.RouterApp(router, host="127.0.0.1").start()
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{app.port}/execz") as r:
                assert r.status == 200
                doc = json.loads(r.read())
            assert doc["fleet"]["replicas_merged"] >= 2
            assert len(doc["replicas"]) >= 2
            for payload in doc["replicas"].values():
                assert payload["sites"]["serving"]["flops"] > 0
            assert doc["fleet"]["sites"]["serving"]["entries"] >= 2
        finally:
            app.stop()
            router.shutdown()
            sup.stop()

    def test_router_profilez_fanout_stitches_bundle(self, fresh_xstats,
                                                    tmp_path):
        from paddle_tpu.serving import fleet
        set_flags({"FLAGS_profile_dir": str(tmp_path / "ring"),
                   "FLAGS_profile_min_interval_s": 0.0})
        sup, router = self._fleet()
        app = fleet.RouterApp(router, host="127.0.0.1").start()
        try:
            # list-view fan-out reaches every replica
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{app.port}/profilez") as r:
                doc = json.loads(r.read())
            assert doc["replicas_merged"] >= 2
            assert all("artifacts" in p
                       for p in doc["replicas"].values())
            # capture fan-out: thread replicas share one ring, so the
            # single-flight guard lets one through; the bundle still
            # carries every replica's response
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{app.port}"
                    f"/profilez?duration_ms=10") as r:
                doc = json.loads(r.read())
            assert doc["captured"] and len(doc["replicas"]) >= 2
            assert any("traceEvents" in p
                       for p in doc["replicas"].values())
        finally:
            app.stop()
            router.shutdown()
            sup.stop()


# ================================================= endpoint conformance
_SURFACES = ("/metrics", "/healthz", "/readyz", "/statusz", "/tracez",
             "/goodputz", "/sloz", "/schedz", "/execz", "/profilez",
             "/numericsz")


class TestEndpointConformance:
    """Every documented HTTP surface must answer on every server kind
    — a new endpoint cannot silently miss a surface."""

    @staticmethod
    def _check(base_url):
        for path in _SURFACES:
            try:
                r = urllib.request.urlopen(base_url + path)
                status, headers = r.status, r.headers
            except urllib.error.HTTPError as e:
                # the liveness/readiness probes legitimately answer
                # 503 on a cold replica — still a conforming response
                assert path in ("/healthz", "/readyz"), path
                assert e.code == 503, path
                r, status, headers = e, e.code, e.headers
            with r:
                ctype = headers.get("Content-Type", "")
                if path == "/metrics":
                    assert ctype.startswith("text/plain"), path
                else:
                    assert ctype.startswith("application/json"), path
                body = r.read()
                assert body, path
                if not path == "/metrics":
                    json.loads(body)        # every JSON page parses

    def test_telemetry_httpd_serves_every_surface(self, fresh_xstats):
        with TelemetryServer(port=0) as srv:
            self._check(srv.url("").rstrip("/"))

    def test_replica_app_serves_every_surface(self, fresh_xstats):
        from paddle_tpu.serving import fleet
        be = fleet.StubBackend(device_ms=1.0)
        app = fleet.ReplicaApp(be).start()
        try:
            self._check(f"http://127.0.0.1:{app.port}")
        finally:
            app.stop()

    def test_router_app_serves_every_surface(self, fresh_xstats):
        from paddle_tpu.serving import fleet
        factory = fleet.ThreadReplicaFactory(
            lambda rid: fleet.StubBackend(device_ms=1.0))
        sup = fleet.ReplicaSupervisor(factory, 1,
                                      poll_interval_s=0.05).start()
        router = fleet.FleetRouter(supervisor=sup, name="t_conf")
        app = fleet.RouterApp(router, host="127.0.0.1").start()
        try:
            self._check(f"http://127.0.0.1:{app.port}")
        finally:
            app.stop()
            router.shutdown()
            sup.stop()


# ================================================= statusz + metrics
class TestStatuszAndMetrics:
    def test_statusz_compile_cache_section(self, fresh_xstats):
        import paddle_tpu.compile_cache  # noqa: F401 - lazy section
        with TelemetryServer(port=0) as srv:
            with urllib.request.urlopen(srv.url("/statusz")) as r:
                doc = json.loads(r.read())
        sec = doc["compile_cache"]
        for key in ("hits", "misses", "fallbacks", "entries", "bytes",
                    "enabled"):
            assert key in sec

    def test_exec_metric_families_exposed(self, fresh_xstats):
        from paddle_tpu.observability import prometheus_text
        jf, args = _jit_pair()
        ent = xstats.register_executable(
            "train_step", xstats.signature_of(args),
            compiled=jf.lower(*args).compile())
        xstats.note_dispatch(ent)
        xstats.default_exec_registry().ensure_analysis(ent)
        set_flags({"FLAGS_device_peak_flops": 1e9})
        xstats.on_step_envelope({"kind": "train", "wall_ms": 5.0})
        text = prometheus_text(default_registry())
        for name in ("paddle_exec_registered_total",
                     "paddle_exec_dispatches_total",
                     "paddle_exec_entries", "paddle_exec_flops",
                     "paddle_mfu"):
            assert name in text, name
