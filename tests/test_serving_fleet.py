"""paddle_tpu.serving.fleet — multi-replica serving (ISSUE 8).

Tier-1 tests run the REAL router/supervisor/worker-app code over
in-process replicas (ReplicaApp threads on localhost sockets, the
accelerator-emulating StubBackend) so the failure paths — crash
mid-request, shed/retry accounting, rolling swap under concurrent
traffic, respawn — are fast and deterministic; the multi-process
end-to-end versions (real worker subprocesses, real Predictor
replicas) are marked ``slow``.
"""
import json
import os
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.serving import fleet
from paddle_tpu.serving.fleet import codec
from paddle_tpu.serving.request import (DeadlineExceededError,
                                        QueueFullError,
                                        ServerClosedError)

_OPENER = urllib.request.build_opener(
    urllib.request.ProxyHandler({}))


# ------------------------------------------------------------- helpers
def _stub_replica(**kw):
    """One in-process replica: StubBackend behind the real HTTP app,
    warmed unless warmup_s says otherwise."""
    be = fleet.StubBackend(**kw)
    app = fleet.ReplicaApp(be).start()
    if not kw.get("warmup_s"):
        be.warmup()
    return be, app


@pytest.fixture()
def one_replica():
    be, app = _stub_replica(device_ms=1.0)
    router = fleet.FleetRouter({0: app.url}, name="t_one",
                               start=False)
    router.poll_replicas()
    yield be, app, router
    router.shutdown()
    app.stop()


def _feed(v=1.0, rows=1):
    return [np.full((rows, 4), v, np.float32)]


# ------------------------------------------------------------- codec
class TestCodec:
    def test_batch_roundtrip_mixed_dtypes(self):
        feeds = [
            [np.arange(6, dtype=np.float32).reshape(2, 3),
             np.array([True, False])],
            [np.ones((1, 3), np.int64), np.float64(3.5).reshape(())],
        ]
        data = codec.encode_batch(feeds)
        assert codec.peek_batch_size(data) == 2
        back = codec.decode_batch(data)
        for want, got in zip(feeds, back):
            for w, g in zip(want, got):
                assert np.asarray(w).dtype == g.dtype
                np.testing.assert_array_equal(np.asarray(w), g)

    def test_results_roundtrip_errors_keep_types(self):
        res = codec.encode_results([
            [np.zeros((2, 2), np.float32)],
            QueueFullError("full"),
            DeadlineExceededError("late"),
            ServerClosedError("closed"),
            ValueError("boom"),
        ])
        back = codec.decode_results(res)
        assert isinstance(back[0], list)
        assert isinstance(back[1], QueueFullError)
        assert isinstance(back[2], DeadlineExceededError)
        assert isinstance(back[3], ServerClosedError)
        assert isinstance(back[4], RuntimeError)
        assert "boom" in str(back[4])

    def test_truncated_and_garbage_payloads_raise(self):
        data = codec.encode_batch([_feed()])
        with pytest.raises(codec.CodecError):
            codec.decode_batch(data[:-3])
        with pytest.raises(codec.CodecError):
            codec.decode_batch(b"NOPE" + data[4:])
        with pytest.raises(codec.CodecError):
            codec.peek_batch_size(b"xx")

    def test_size_mismatch_rejected(self):
        # header claims more bytes than shape*dtype: must not be
        # silently reshaped
        data = bytearray(codec.encode_batch([_feed()]))
        # nbytes field sits right before the raw buffer (16 floats)
        idx = len(data) - 16 - 8
        data[idx:idx + 8] = (99).to_bytes(8, "little")
        with pytest.raises(codec.CodecError):
            codec.decode_batch(bytes(data))


# ------------------------------------------------------------- metrics
class TestMergedMetrics:
    def test_replica_label_injection_and_header_dedup(self):
        t0 = ("# HELP m_total doc\n# TYPE m_total counter\n"
              'm_total{server="a"} 3\nplain 1\n')
        t1 = ("# HELP m_total doc\n# TYPE m_total counter\n"
              'm_total{server="a"} 5\n')
        merged = fleet.merge_prometheus_texts({"r0": t0, "r1": t1})
        assert merged.count("# HELP m_total doc") == 1
        assert 'm_total{replica="r0",server="a"} 3' in merged
        assert 'm_total{replica="r1",server="a"} 5' in merged
        assert 'plain{replica="r0"} 1' in merged

    def test_router_merged_view_includes_replicas(self, one_replica):
        _, _, router = one_replica
        merged = router.merged_metrics()
        assert 'replica="0"' in merged


# ------------------------------------------------------------- routing
class TestRouting:
    def test_submit_roundtrip_and_metrics(self, one_replica):
        be, _, router = one_replica
        futs = router.submit_many([_feed(2.0) for _ in range(5)])
        for f in futs:
            out = f.result(timeout=30)
            np.testing.assert_allclose(
                out[0], np.full((1, 4), 2.0) * be._scale)
        snap = router.metrics_snapshot()
        assert snap["counters"]["routed"] == 5
        assert snap["counters"]["completed"] == 5
        assert snap["counters"]["failed"] == 0

    def test_routes_only_to_ready_replicas(self):
        cold, cold_app = _stub_replica(device_ms=1.0, warmup_s=60.0)
        warm, warm_app = _stub_replica(device_ms=1.0)
        router = fleet.FleetRouter({"cold": cold_app.url,
                                    "warm": warm_app.url},
                                   name="t_ready", start=False)
        try:
            router.poll_replicas()
            states = {s["replica"]: s
                      for s in router.replica_states()}
            assert states["cold"]["alive"] and \
                not states["cold"]["ready"]
            assert states["warm"]["ready"]
            futs = router.submit_many([_feed() for _ in range(6)])
            for f in futs:
                f.result(timeout=30)
            assert cold.dispatches == 0
            assert warm.dispatches > 0
        finally:
            router.shutdown()
            cold_app.stop()
            warm_app.stop()

    def test_no_ready_replica_raises(self):
        cold, app = _stub_replica(device_ms=1.0, warmup_s=60.0)
        router = fleet.FleetRouter({0: app.url}, name="t_cold",
                                   start=False)
        try:
            router.poll_replicas()
            fut = router.submit(_feed())
            with pytest.raises(fleet.NoReadyReplicaError):
                fut.result(timeout=30)
            assert router.metrics_snapshot()["counters"]["shed"] == 1
        finally:
            router.shutdown()
            app.stop()

    def test_load_spreads_across_replicas(self):
        reps = [_stub_replica(device_ms=2.0) for _ in range(2)]
        router = fleet.FleetRouter(
            {i: app.url for i, (_, app) in enumerate(reps)},
            name="t_spread", start=False)
        try:
            router.poll_replicas()
            futs = []
            for _ in range(12):
                futs.extend(router.submit_many([_feed()] * 2))
            for f in futs:
                f.result(timeout=30)
            assert all(be.dispatches > 0 for be, _ in reps)
        finally:
            router.shutdown()
            for _, app in reps:
                app.stop()

    def test_shed_retries_on_other_replica(self):
        # tiny replica sheds (capacity 1 vs 4-request batch); the
        # roomy one absorbs the retry
        tiny, tiny_app = _stub_replica(device_ms=1.0,
                                       queue_capacity=1)
        roomy, roomy_app = _stub_replica(device_ms=1.0)
        router = fleet.FleetRouter({"tiny": tiny_app.url,
                                    "roomy": roomy_app.url},
                                   name="t_shed", start=False)
        try:
            router.poll_replicas()
            # drive until the pick lands on tiny at least once
            for _ in range(6):
                futs = router.submit_many([_feed()] * 4)
                for f in futs:
                    f.result(timeout=30)
            snap = router.metrics_snapshot()
            assert snap["counters"]["failed"] == 0
            assert snap["retries"]["queue_full"] >= 1
        finally:
            router.shutdown()
            tiny_app.stop()
            roomy_app.stop()

    def test_all_replicas_full_sheds_with_queue_full(self):
        be, app = _stub_replica(device_ms=1.0, queue_capacity=1)
        router = fleet.FleetRouter({0: app.url}, name="t_full",
                                   retries=1, start=False)
        try:
            router.poll_replicas()
            fut = router.submit_many([_feed()] * 4)[0]
            with pytest.raises(QueueFullError):
                fut.result(timeout=30)
            snap = router.metrics_snapshot()
            assert snap["counters"]["shed"] == 4
            assert snap["retries"]["queue_full"] >= 1
        finally:
            router.shutdown()
            app.stop()

    def test_submit_after_shutdown_and_dict_feed(self, one_replica):
        _, _, router = one_replica
        with pytest.raises(TypeError):
            router.submit_many([{"x": np.zeros((1, 4))}])
        router.shutdown()
        with pytest.raises(ServerClosedError):
            router.submit(_feed())


class TestCrashMidRequest:
    def test_inflight_fails_others_survive(self):
        crashy, crashy_app = _stub_replica(
            device_ms=1.0, crash_value=666.0, crash_mode="drop")
        safe, safe_app = _stub_replica(device_ms=1.0)
        router = fleet.FleetRouter({"crashy": crashy_app.url,
                                    "safe": safe_app.url},
                                   name="t_crash", start=False)
        try:
            # phase 1: only the crashy replica is known, so the
            # poison request deterministically lands on it
            router.remove_replica("safe")
            router.poll_replicas()
            bad = router.submit(_feed(666.0))
            with pytest.raises((fleet.ReplicaError,
                                ServerClosedError)):
                bad.result(timeout=30)
            # phase 2: the healthy replica joins the fleet
            router.add_replica("safe", safe_app.url)
            # the crashed replica leaves the routable set...
            router.poll_replicas()
            routable = {s["replica"]
                        for s in router.replica_states()
                        if s["ready"]}
            assert "crashy" not in routable
            # ...and healthy traffic keeps flowing on the survivor
            futs = router.submit_many([_feed() for _ in range(4)])
            for f in futs:
                f.result(timeout=30)
            assert router.metrics_snapshot()[
                "counters"]["failed"] >= 1
        finally:
            router.shutdown()
            crashy_app.stop()
            safe_app.stop()


class TestRollingSwap:
    def test_swap_under_traffic_loses_nothing(self):
        import threading
        reps = [_stub_replica(device_ms=1.0) for _ in range(2)]
        router = fleet.FleetRouter(
            {i: app.url for i, (_, app) in enumerate(reps)},
            name="t_swap", start=False)
        stats = {"done": 0, "failed": 0}
        stop = threading.Event()

        def _traffic():
            while not stop.is_set():
                futs = router.submit_many([_feed()] * 2)
                for f in futs:
                    try:
                        f.result(timeout=30)
                        stats["done"] += 1
                    except Exception:  # noqa: BLE001 - counted
                        stats["failed"] += 1
                time.sleep(0.001)

        try:
            router.poll_replicas()
            threads = [threading.Thread(target=_traffic)
                       for _ in range(2)]
            for t in threads:
                t.start()
            time.sleep(0.1)
            report = router.swap_weights("models/v1",
                                         drain_timeout_s=10)
            time.sleep(0.1)
            stop.set()
            for t in threads:
                t.join()
            assert stats["failed"] == 0
            assert stats["done"] > 0
            assert len(report["replicas"]) == 2
            assert all(be.version == "v1" for be, _ in reps)
            # post-swap traffic carries the new version's scale
            out = router.submit(_feed(1.0)).result(timeout=30)
            np.testing.assert_allclose(
                out[0], np.full((1, 4),
                                fleet.StubBackend._scale_of("v1")))
            snap = router.metrics_snapshot()
            assert snap["swaps"]["replica_reloaded"] == 2
            assert snap["swaps"]["completed"] == 1
        finally:
            stop.set()
            router.shutdown()
            for _, app in reps:
                app.stop()

    def test_swap_drains_before_reload(self):
        # a slow in-flight batch must finish BEFORE its replica
        # reloads: drain_ms in the report proves the wait happened
        be, app = _stub_replica(device_ms=300.0)
        router = fleet.FleetRouter({0: app.url}, name="t_drain",
                                   start=False)
        try:
            router.poll_replicas()
            fut = router.submit(_feed())
            time.sleep(0.05)    # let the dispatch reach the stub
            report = router.swap_weights("models/v2",
                                         drain_timeout_s=30)
            assert fut.result(timeout=30)  # completed, not failed
            assert report["replicas"][0]["drain_ms"] > 100
        finally:
            router.shutdown()
            app.stop()


class TestGenerateRouting:
    def test_stream_through_router(self, one_replica):
        _, _, router = one_replica
        fut = router.submit_generate([7], max_new_tokens=5)
        assert list(fut) == [8, 9, 10, 11, 12]
        assert fut.finish_reason == "length"
        assert fut.result(timeout=5) == [8, 9, 10, 11, 12]

    def test_generate_shed_when_cold(self):
        cold, app = _stub_replica(device_ms=1.0, warmup_s=60.0)
        router = fleet.FleetRouter({0: app.url}, name="t_gcold",
                                   start=False)
        try:
            router.poll_replicas()
            fut = router.submit_generate([1], max_new_tokens=3)
            with pytest.raises(ServerClosedError):
                fut.result(timeout=30)
        finally:
            router.shutdown()
            app.stop()


# ------------------------------------------------------------- http
class TestRouterHTTP:
    def test_data_plane_passthrough_and_status(self, one_replica):
        be, _, router = one_replica
        app = fleet.RouterApp(router, host="127.0.0.1").start()
        try:
            body = codec.encode_batch([_feed(3.0)] * 2)
            req = urllib.request.Request(
                app.url("/submit_many"), data=body)
            with _OPENER.open(req, timeout=30) as resp:
                results = codec.decode_results(resp.read())
            assert len(results) == 2
            np.testing.assert_allclose(
                results[0][0], np.full((1, 4), 3.0) * be._scale)
            with _OPENER.open(app.url("/readyz"),
                              timeout=10) as resp:
                assert json.loads(resp.read())["ready"] is True
            with _OPENER.open(app.url("/statusz"),
                              timeout=10) as resp:
                status = json.loads(resp.read())
            assert status["replicas"][0]["ready"] is True
            with _OPENER.open(app.url("/metrics?merged=1"),
                              timeout=10) as resp:
                text = resp.read().decode()
            assert "paddle_fleet_requests_total" in text
            assert 'replica="0"' in text
        finally:
            app.stop()

    def test_http_shed_maps_to_429_and_cold_to_503(self):
        be, rep_app = _stub_replica(device_ms=1.0, queue_capacity=1)
        router = fleet.FleetRouter({0: rep_app.url}, name="t_http2",
                                   retries=0, start=False)
        router.poll_replicas()
        app = fleet.RouterApp(router, host="127.0.0.1").start()
        try:
            body = codec.encode_batch([_feed()] * 8)
            with pytest.raises(urllib.error.HTTPError) as ei:
                _OPENER.open(urllib.request.Request(
                    app.url("/submit_many"), data=body), timeout=30)
            assert ei.value.code == 429
            ei.value.read()
            router.remove_replica(0)
            with pytest.raises(urllib.error.HTTPError) as ei:
                _OPENER.open(urllib.request.Request(
                    app.url("/submit_many"), data=body), timeout=30)
            assert ei.value.code == 503
            ei.value.read()
            with pytest.raises(urllib.error.HTTPError) as ei:
                _OPENER.open(app.url("/readyz"), timeout=10)
            assert ei.value.code == 503
            ei.value.read()
        finally:
            app.stop()
            router.shutdown()
            rep_app.stop()

    def test_generate_over_http(self, one_replica):
        _, _, router = one_replica
        app = fleet.RouterApp(router, host="127.0.0.1").start()
        try:
            req = urllib.request.Request(
                app.url("/generate"),
                data=json.dumps({"prompt": [3],
                                 "max_new_tokens": 4}).encode())
            with _OPENER.open(req, timeout=30) as resp:
                events = [json.loads(line)
                          for line in resp if line.strip()]
            toks = [e["t"] for e in events if "t" in e]
            assert toks == [4, 5, 6, 7]
            assert events[-1]["done"] is True
            assert events[-1]["finish_reason"] == "length"
        finally:
            app.stop()


# ------------------------------------------------------------- supervisor
class TestSupervisor:
    def test_respawn_after_kill(self):
        fac = fleet.ThreadReplicaFactory(
            lambda rid: fleet.StubBackend(device_ms=1.0))
        sup = fleet.ReplicaSupervisor(fac, 2, restart_backoff_ms=10,
                                      poll_interval_s=0.01).start()
        try:
            assert len(sup.endpoints()) == 2
            fac.spawned[0].kill()       # SIGKILL stand-in
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                if sup.restart_counts().get(0) == 1 and \
                        len(sup.endpoints()) == 2:
                    break
                time.sleep(0.02)
            assert sup.restart_counts()[0] == 1
            assert len(sup.endpoints()) == 2
            # the respawned replica is a NEW app on a new port
            assert len(fac.spawned) == 3
        finally:
            sup.stop()

    def test_restart_metric_counts(self):
        fac = fleet.ThreadReplicaFactory(
            lambda rid: fleet.StubBackend(device_ms=1.0))
        metrics = fleet.FleetMetrics("t_restarts")
        sup = fleet.ReplicaSupervisor(
            fac, 1, restart_backoff_ms=10, poll_interval_s=0.01,
            metrics=metrics).start()
        try:
            fac.spawned[0].kill()
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                if metrics.snapshot()["restarts"] >= 1:
                    break
                time.sleep(0.02)
            assert metrics.snapshot()["restarts"] == 1
        finally:
            sup.stop()

    def test_scale_up_and_down(self):
        fac = fleet.ThreadReplicaFactory(
            lambda rid: fleet.StubBackend(device_ms=1.0))
        sup = fleet.ReplicaSupervisor(fac, 1,
                                      poll_interval_s=0.01).start()
        try:
            assert len(sup.endpoints()) == 1
            sup.scale_to(3)
            assert len(sup.endpoints()) == 3
            sup.scale_to(1)
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                if len(sup.endpoints()) == 1:
                    break
                time.sleep(0.02)
            assert len(sup.endpoints()) == 1
            assert sup.replica_ids == [0]
        finally:
            sup.stop()

    # LD002 regression (pdlint lock_order): the factory used to run
    # INSIDE the supervisor lock, so a slow spawn (subprocess.Popen,
    # model warmup) blocked endpoints()/the monitor/stop() for its
    # whole duration. Spawns now happen outside the critical section
    # against a published pending slot.
    class _FakeProc:
        def __init__(self, rid):
            self.rid = rid
            self.terminated = False

        def poll(self):
            return 0 if self.terminated else None

        def url(self):
            return None if self.terminated else f"mock://{self.rid}"

        def terminate(self):
            self.terminated = True

        def kill(self):
            self.terminated = True

        def wait(self, timeout=None):
            return 0

    def test_slow_spawn_does_not_block_discovery(self):
        unwedge = threading.Event()

        def factory(rid):
            if rid > 0:
                unwedge.wait(5)          # second spawn wedges
            return self._FakeProc(rid)

        sup = fleet.ReplicaSupervisor(
            factory, 1, poll_interval_s=0.01).start()
        t = threading.Thread(target=sup.scale_to, args=(2,))
        try:
            t.start()
            time.sleep(0.05)             # factory now blocked
            t0 = time.monotonic()
            eps = sup.endpoints()
            ids = sup.replica_ids
            counts = sup.restart_counts()
            dt = time.monotonic() - t0
            assert dt < 0.25, (
                f"discovery blocked {dt:.2f}s behind an in-flight "
                f"spawn — factory must run outside the lock")
            assert eps == {0: "mock://0"}   # pending slot invisible
            assert ids == [0, 1]            # ...but reserved
            assert counts == {0: 0, 1: 0}
        finally:
            unwedge.set()
            t.join(5)
            sup.stop()
        assert not t.is_alive()
        assert sup.endpoints() == {}

    def test_stop_during_spawn_terminates_orphan(self):
        unwedge = threading.Event()
        spawned = []

        def factory(rid):
            unwedge.wait(5)
            p = self._FakeProc(rid)
            spawned.append(p)
            return p

        sup = fleet.ReplicaSupervisor(factory, 1,
                                      poll_interval_s=0.01)
        t = threading.Thread(target=sup.start)
        t.start()
        try:
            time.sleep(0.05)             # spawn in flight, lock free
            t0 = time.monotonic()
            sup.stop(timeout=1)
            assert time.monotonic() - t0 < 1.0, \
                "stop() must not wait behind an in-flight spawn"
        finally:
            unwedge.set()
            t.join(5)
        assert not t.is_alive()
        # the late-arriving proc was orphaned and must be terminated
        assert spawned and spawned[0].terminated

    def test_router_follows_supervisor(self):
        fac = fleet.ThreadReplicaFactory(
            lambda rid: fleet.StubBackend(device_ms=1.0))
        sup = fleet.ReplicaSupervisor(fac, 1, restart_backoff_ms=10,
                                      poll_interval_s=0.01).start()
        router = fleet.FleetRouter(supervisor=sup, name="t_follow",
                                   start=False)
        try:
            router.poll_replicas()
            assert len(router._routable()) == 1
            sup.scale_to(2)         # warm scale-out: router sees it
            router.poll_replicas()
            assert len(router._routable()) == 2
            futs = router.submit_many([_feed()] * 4)
            for f in futs:
                f.result(timeout=30)
        finally:
            router.shutdown()
            sup.stop()


# ------------------------------------------------------------- readiness
class TestReadinessSplit:
    def test_observability_readyz_vacuous_and_gated(self):
        from paddle_tpu import observability as obs
        ok, detail = obs.readyz()
        base = len(detail["checks"])
        obs.add_readiness_check("t_fleet_gate", lambda: False)
        try:
            ok, detail = obs.readyz()
            assert not ok
            assert len(detail["checks"]) == base + 1
            # liveness is NOT affected by a readiness gate
            h_ok, h_detail = obs.healthz()
            assert "t_fleet_gate" not in h_detail["checks"]
        finally:
            obs.remove_readiness_check("t_fleet_gate")
        assert obs.readyz()[0] or base > 0

    def test_inference_server_ready_gate(self, tmp_path):
        import paddle_tpu.nn as nn
        from paddle_tpu import inference, serving
        paddle.seed(0)
        net = nn.Sequential(nn.Linear(8, 8), nn.Tanh()).eval()
        prefix = str(tmp_path / "m")
        paddle.jit.save(net, prefix, input_spec=[
            paddle.static.InputSpec([None, 8], "float32", "x")])
        pred = inference.create_predictor(inference.Config(prefix))
        srv = serving.InferenceServer(
            pred, max_batch_size=4, name="t_gate",
            ready_requires_warmup=True, start=False)
        try:
            assert srv.ready is False       # gated, not warmed
            srv.warmup()
            assert srv.ready is True
        finally:
            srv.shutdown()
        assert srv.ready is False           # closed = never ready

    def test_ungated_server_ready_immediately(self, tmp_path):
        import paddle_tpu.nn as nn
        from paddle_tpu import inference, serving
        paddle.seed(0)
        net = nn.Sequential(nn.Linear(8, 8), nn.Tanh()).eval()
        prefix = str(tmp_path / "m")
        paddle.jit.save(net, prefix, input_spec=[
            paddle.static.InputSpec([None, 8], "float32", "x")])
        pred = inference.create_predictor(inference.Config(prefix))
        srv = serving.InferenceServer(pred, max_batch_size=4,
                                      name="t_ungated", start=False)
        try:
            assert srv.ready is True    # default: no warmup gate
        finally:
            srv.shutdown()

    def test_worker_readyz_flips_after_warmup(self):
        be, app = _stub_replica(device_ms=1.0, warmup_s=60.0)
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                _OPENER.open(app.url + "/readyz", timeout=10)
            assert ei.value.code == 503
            ei.value.read()
            # liveness is already green while readiness is not
            with _OPENER.open(app.url + "/healthz",
                              timeout=10) as resp:
                assert json.loads(resp.read())["ok"] is True
            with be._lock:
                be._warmed = True
            with _OPENER.open(app.url + "/readyz",
                              timeout=10) as resp:
                assert json.loads(resp.read())["ready"] is True
        finally:
            app.stop()


# ------------------------------------------------------------- e2e
def _wait(predicate, timeout=60.0, interval=0.05):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(interval)
    return predicate()


# ------------------------------------------------------- resilience
class TestCodecDeadlineTrailer:
    def test_roundtrip_alongside_trace_trailer(self):
        body = codec.encode_batch([_feed(), _feed()])
        stamped = codec.attach_trace_trailer(
            body, ["00-" + "a" * 32 + "-" + "b" * 16 + "-01", None])
        stamped = codec.attach_deadline_trailer(stamped, [42.5, None])
        feeds, tps, dls = codec.decode_batch_trailers(stamped)
        assert len(feeds) == 2
        assert tps[1] is None and tps[0].startswith("00-")
        assert dls == [42.5, None]
        # deadline-only payloads work too, and the 2-tuple decode
        # shape survives for trailer-blind callers
        d_only = codec.attach_deadline_trailer(body, [7.0, 7.0])
        assert codec.decode_batch_trailers(d_only)[2] == [7.0, 7.0]
        assert codec.decode_batch_ex(d_only)[1] is None
        assert codec.peek_batch_size(d_only) == 2

    def test_attach_is_idempotent_and_validates(self):
        body = codec.encode_batch([_feed()])
        stamped = codec.attach_deadline_trailer(body, [9.0])
        assert codec.attach_deadline_trailer(stamped, [1.0]) == \
            stamped
        with pytest.raises(codec.CodecError):
            codec.attach_deadline_trailer(body, [1.0, 2.0])

    def test_wedged_error_round_trips(self):
        from paddle_tpu.serving.fleet.resilience import \
            ReplicaWedgedError
        back = codec.decode_results(codec.encode_results(
            [ReplicaWedgedError("device hung")]))
        assert isinstance(back[0], ReplicaWedgedError)
        assert "device hung" in str(back[0])


class TestCircuitBreaker:
    def test_slow_but_alive_replica_drained_then_readmitted(self):
        """The readiness-is-insufficient scenario: a replica serving
        100x latency stays /readyz-GREEN, but its latency-aware
        breaker opens and traffic drains to the healthy replica; when
        it recovers, the half-open probe re-admits it."""
        slow, slow_app = _stub_replica(device_ms=80.0)
        fast, fast_app = _stub_replica(device_ms=1.0)
        router = fleet.FleetRouter(
            {"slow": slow_app.url, "fast": fast_app.url},
            name="t_breaker", start=False,
            breaker_window=8, breaker_failure_ratio=0.5,
            breaker_min_samples=2, breaker_open_ms=300.0,
            breaker_latency_ms=30.0)
        try:
            router.poll_replicas()
            for _ in range(8):
                router.submit(_feed()).result(timeout=30)
            states = {s["replica"]: s
                      for s in router.replica_states()}
            assert states["slow"]["ready"], \
                "readyz must stay green — slowness is invisible to it"
            assert states["slow"]["breaker"]["state"] == "open"
            assert states["fast"]["breaker"]["state"] == "closed"
            # drained: new traffic all lands on the healthy replica
            drained_before = slow.dispatches
            for _ in range(4):
                router.submit(_feed()).result(timeout=30)
            assert slow.dispatches == drained_before
            # recovery: half-open probe re-admits after the cooldown
            slow.device_ms = 1.0

            def _probe_and_check():
                router.submit(_feed()).result(timeout=30)
                states = {s["replica"]: s["breaker"]["state"]
                          for s in router.replica_states()}
                return states["slow"] == "closed"

            assert _wait(_probe_and_check, timeout=30)
            assert slow.dispatches > drained_before
            snap = {s["replica"]: s["breaker"]
                    for s in router.replica_states()}
            assert snap["slow"]["opens"] >= 1
        finally:
            router.shutdown()
            slow_app.stop()
            fast_app.stop()

    def test_breaker_opens_on_shed_storm(self):
        """Repeated 429s trip the breaker even though the replica is
        alive and ready — fast-fail instead of hammering it."""
        tiny, tiny_app = _stub_replica(device_ms=1.0,
                                       queue_capacity=1)
        router = fleet.FleetRouter(
            {"tiny": tiny_app.url}, name="t_storm", retries=1,
            start=False, retry_backoff_ms_=0.0,
            breaker_window=8, breaker_failure_ratio=0.5,
            breaker_min_samples=2, breaker_open_ms=10000.0)
        try:
            router.poll_replicas()
            # one 6-request batch vs capacity 1: dispatch + retry both
            # shed 429 -> the batch fails QueueFullError and the two
            # recorded failures open the breaker
            futs = router.submit_many([_feed()] * 6)
            for f in futs:
                with pytest.raises(QueueFullError):
                    f.result(timeout=30)
            st = router.replica_states()[0]["breaker"]["state"]
            assert st == "open"
            # open breaker = no routable target = typed shed
            with pytest.raises(fleet.NoReadyReplicaError):
                router.submit(_feed()).result(timeout=30)
        finally:
            router.shutdown()
            tiny_app.stop()


class TestHedging:
    def test_hedged_submit_covers_slow_replica(self):
        """With one slow and one fast replica, the hedge fires after
        the peers' latency quantile and the fast replica's answer
        wins; the accounting (fired >= won) is exposed."""
        slow, slow_app = _stub_replica(device_ms=250.0)
        fast, fast_app = _stub_replica(device_ms=1.0)
        router = fleet.FleetRouter(
            {"slow": slow_app.url, "fast": fast_app.url},
            name="t_hedge", start=False,
            breaker_failure_ratio=1.1, breaker_latency_ms=0.0,
            hedge_ms=20.0, hedge_quantile=0.5)
        try:
            router.poll_replicas()
            t0 = time.perf_counter()
            # sequential singles: ties round-robin, so half the
            # dispatches pick the slow replica and get hedged
            for _ in range(6):
                router.submit(_feed()).result(timeout=30)
            elapsed_ms = (time.perf_counter() - t0) * 1e3
            snap = router.metrics_snapshot()
            assert snap["hedges"]["fired"] >= 1
            assert snap["hedges"]["won"] >= 1
            assert snap["hedges"]["won"] <= snap["hedges"]["fired"]
            # 6 un-hedged requests would cost >= 3 * 250 ms
            assert elapsed_ms < 3 * 250.0, elapsed_ms
            assert snap["counters"]["failed"] == 0
        finally:
            router.shutdown()
            slow_app.stop()
            fast_app.stop()

    def test_generate_never_hedges(self):
        """The stream path is not idempotent: even with hedging
        configured, submit_generate fires no hedges."""
        be, app = _stub_replica(device_ms=50.0)
        router = fleet.FleetRouter(
            {0: app.url}, name="t_nohedge", start=False,
            hedge_ms=1.0, hedge_quantile=0.5)
        try:
            router.poll_replicas()
            fut = router.submit_generate([7], max_new_tokens=3)
            assert list(fut) == [8, 9, 10]
            assert router.metrics_snapshot()["hedges"]["fired"] == 0
        finally:
            router.shutdown()
            app.stop()


class TestDeadlinePropagation:
    def test_router_fails_exhausted_budget_locally(self):
        be, app = _stub_replica(device_ms=1.0)
        router = fleet.FleetRouter({0: app.url}, name="t_ddl",
                                   start=False)
        try:
            router.poll_replicas()
            fut = router.submit(_feed(), timeout_ms=0.0001)
            with pytest.raises(DeadlineExceededError):
                fut.result(timeout=30)
            snap = router.metrics_snapshot()
            assert snap["deadline_rejects"]["router"] == 1
        finally:
            router.shutdown()
            app.stop()

    def test_worker_rejects_expired_before_dispatch(self):
        """The acceptance scenario: a batch arriving with an
        exhausted budget is answered typed WITHOUT a device dispatch
        (the stub's dispatch counter is the witness); live requests
        in the same batch still run."""
        be, app = _stub_replica(device_ms=1.0)
        try:
            body = codec.attach_deadline_trailer(
                codec.encode_batch([_feed(), _feed(3.0)]),
                [-5.0, 5000.0])
            req = urllib.request.Request(
                app.url + "/submit_many", data=body)
            with _OPENER.open(req, timeout=30) as resp:
                results = codec.decode_results(resp.read())
            assert isinstance(results[0], DeadlineExceededError)
            assert isinstance(results[1], list)      # peer survived
            np.testing.assert_allclose(
                results[1][0], np.full((1, 4), 3.0) * be._scale)
            assert be.dispatches == 1   # one batch, expired row gone
        finally:
            app.stop()

    def test_generate_deadline_evicts_and_stays_typed(self):
        """An in-flight routed stream whose budget expires fails with
        DeadlineExceededError (typed across the ndjson wire), reason
        "deadline"."""
        be, app = _stub_replica(device_ms=1.0, token_ms=30.0)
        router = fleet.FleetRouter({0: app.url}, name="t_gddl",
                                   start=False)
        try:
            router.poll_replicas()
            fut = router.submit_generate([7], max_new_tokens=50,
                                         deadline_ms=100.0)
            with pytest.raises(DeadlineExceededError):
                fut.result(timeout=60)
            assert fut.finish_reason == "deadline"
            assert 0 < len(fut.tokens()) < 50
        finally:
            router.shutdown()
            app.stop()


class TestWedgeWatchdog:
    def test_hang_flips_readyz_and_fails_waiters_typed(self):
        """Thread-mode wedge drill: a hang poison wedges the device;
        the watchdog flips /readyz, the queued waiter fails with the
        typed ReplicaWedgedError (not an eternal block), and the
        wedge is counted."""
        from paddle_tpu.serving.fleet.resilience import \
            ReplicaWedgedError
        be = fleet.StubBackend(device_ms=1.0, hang_value=777.0)
        be.warmup()
        app = fleet.ReplicaApp(be).start()
        wd = fleet.arm_wedge_watchdog(be, app, timeout_ms=150.0,
                                      restart=False, name="t_wedge")
        assert wd is not None
        try:
            import threading
            poison_err = []

            def _poison():
                try:
                    req = urllib.request.Request(
                        app.url + "/submit_many",
                        data=codec.encode_batch([_feed(777.0)]))
                    _OPENER.open(req, timeout=30).read()
                except Exception as e:  # noqa: BLE001 - expected
                    poison_err.append(e)

            t = threading.Thread(target=_poison, daemon=True)
            t.start()
            # the poison holds the device before the waiter is sent
            # (however long a busy machine takes to get it there)
            assert _wait(lambda: be._hang.is_set()
                         and be._device.locked(), timeout=30,
                         interval=0.005)
            # the waiter queued behind the wedge fails TYPED once the
            # watchdog fires — never blocks past the bound
            req = urllib.request.Request(
                app.url + "/submit_many",
                data=codec.encode_batch([_feed()]))
            with pytest.raises(urllib.error.HTTPError) as ei:
                _OPENER.open(req, timeout=30)
            assert ei.value.code == 503
            assert b"wedged" in ei.value.read()
            assert wd.wedged and wd.wedge_count == 1
            # /readyz red, /healthz reports the wedge
            with pytest.raises(urllib.error.HTTPError) as ei:
                _OPENER.open(app.url + "/readyz", timeout=10)
            assert ei.value.code == 503
            body = json.loads(ei.value.read())
            assert body.get("wedged") is True
            t.join(timeout=30)
            assert poison_err, "the hung dispatch must fail, not " \
                               "return"
        finally:
            wd.stop()
            app.stop()

    def test_wedge_triggers_supervisor_respawn(self):
        """restart=True: the watchdog requests shutdown, the thread
        replica exits, and the supervisor respawns a fresh one — the
        process-mode recovery path, in-process."""
        def _factory(rid):
            be = fleet.StubBackend(device_ms=1.0, hang_value=777.0)
            rep = fleet.ThreadReplicaFactory(lambda r: be)(rid)
            fleet.arm_wedge_watchdog(be, rep.app, timeout_ms=150.0,
                                     restart=True,
                                     name=f"t_resp{rid}")
            return rep

        sup = fleet.ReplicaSupervisor(_factory, 1,
                                      restart_backoff_ms=10,
                                      poll_interval_s=0.01).start()
        router = fleet.FleetRouter(supervisor=sup, name="t_wresp",
                                   start=False)
        try:
            router.poll_replicas()
            assert len(router._routable()) == 1
            fut = router.submit(_feed(777.0))
            with pytest.raises(Exception):
                fut.result(timeout=30)
            assert _wait(lambda: sup.restart_counts().get(0, 0) >= 1,
                         timeout=30)
            assert _wait(lambda: (router.poll_replicas() or
                                  len(router._routable()) >= 1),
                         timeout=30)
            router.submit(_feed()).result(timeout=30)
        finally:
            router.shutdown()
            sup.stop()


class TestGenerateCancelPropagation:
    def test_cancel_routed_stream_frees_replica_pages(self):
        """Satellite regression: cancel() on a ROUTED stream must
        reach the replica's engine — the sequence is evicted and its
        KV pages return to the free list, not just client-side
        iteration stopping."""
        import paddle_tpu as paddle_
        from paddle_tpu.models import GPTForCausalLM, gpt_tiny
        from paddle_tpu.serving.generation import GenerationServer
        paddle_.seed(0)
        engine = GenerationServer(
            GPTForCausalLM(gpt_tiny(use_flash_attention=False)),
            max_batch=2, page_size=8, prefix_cache=False,
            name="t_routed_cancel")

        class _GenBackend:
            def generate(self, prompt, max_new_tokens, temperature,
                         timeout_ms, seed, deadline_ms=None):
                return engine.submit_generate(
                    prompt, max_new_tokens=max_new_tokens,
                    temperature=temperature, timeout_ms=timeout_ms,
                    seed=seed, deadline_ms=deadline_ms)

            def submit_many(self, *a, **k):
                raise NotImplementedError

            def warmup(self):
                return 0

            def ready(self):
                return True

            def health(self):
                return True, {}

            def info(self):
                return {"backend": "gen", "version": "v0"}

            def shutdown(self, drain=True):
                pass

        app = fleet.ReplicaApp(_GenBackend()).start()
        router = fleet.FleetRouter({0: app.url}, name="t_cancelgen",
                                   start=False)
        try:
            router.poll_replicas()
            fut = router.submit_generate([5, 7, 9],
                                         max_new_tokens=200)
            assert _wait(lambda: len(fut.tokens()) >= 2, timeout=60)
            assert fut.cancel()
            assert _wait(fut.done, timeout=30)
            assert fut.finish_reason == "cancelled"
            # the ENGINE evicted the sequence: pages back on the
            # free list, nothing leaked — the bug was client-side-
            # only cancellation leaving the replica decoding
            assert _wait(lambda: engine.kv.free_pages ==
                         engine.kv.capacity, timeout=30), \
                engine.kv.leak_check()
            assert engine.active_sequences == 0
        finally:
            router.shutdown()
            app.stop()
            engine.shutdown(drain=False)


@pytest.mark.slow
class TestMultiProcessE2E:
    def test_stub_worker_crash_respawn_and_traffic(self):
        fac = fleet.ProcessReplicaFactory(
            extra_args=["--stub", "--stub-device-ms", "2",
                        "--stub-crash-value", "666",
                        "--stub-crash-mode", "exit"],
            env={"JAX_PLATFORMS": "cpu"})
        sup = fleet.ReplicaSupervisor(fac, 2,
                                      restart_backoff_ms=50).start()
        router = fleet.FleetRouter(supervisor=sup, name="t_e2e",
                                   health_interval_ms=100)
        try:
            assert router.wait_ready(2, timeout=60)
            futs = router.submit_many([_feed() for _ in range(6)])
            for f in futs:
                f.result(timeout=60)
            # kill one replica mid-request via the poison value
            bad = router.submit(_feed(666.0))
            with pytest.raises((fleet.ReplicaError,
                                ServerClosedError)):
                bad.result(timeout=60)
            # traffic keeps flowing on the survivor
            futs = router.submit_many([_feed() for _ in range(4)])
            for f in futs:
                f.result(timeout=60)
            # and the supervisor brings the dead one back
            assert _wait(lambda: sum(
                sup.restart_counts().values()) >= 1 and
                len(router._routable()) >= 2, timeout=60)
        finally:
            router.shutdown()
            sup.stop()

    def test_real_worker_parity_warm_manifest_and_reload(
            self, tmp_path):
        import paddle_tpu.nn as nn
        from paddle_tpu import inference

        def _save(name, seed):
            paddle.seed(seed)
            net = nn.Sequential(nn.Linear(8, 16), nn.Tanh(),
                                nn.Linear(16, 4)).eval()
            prefix = str(tmp_path / name)
            paddle.jit.save(net, prefix, input_spec=[
                paddle.static.InputSpec([None, 8], "float32",
                                        "x")])
            return prefix

        v1, v2 = _save("model_v1", 0), _save("model_v2", 7)
        cache = str(tmp_path / "cache")
        fac = fleet.ProcessReplicaFactory(
            extra_args=["--model-prefix", v1, "--warmup", "auto",
                        "--max-batch-size", "8"],
            env={"JAX_PLATFORMS": "cpu",
                 "FLAGS_compile_cache_dir": cache})
        sup = fleet.ReplicaSupervisor(fac, 1).start()
        router = fleet.FleetRouter(supervisor=sup, name="t_real",
                                   health_interval_ms=100)
        try:
            assert router.wait_ready(1, timeout=120), \
                router.replica_states()
            x = np.random.RandomState(0).randn(2, 8).astype(
                "float32")
            out = router.submit([x]).result(timeout=120)
            ref = inference.create_predictor(
                inference.Config(v1)).run([x])[0]
            np.testing.assert_allclose(out[0], ref, rtol=1e-5,
                                       atol=1e-6)
            # rolling hot swap to v2, then verify the new weights
            report = router.swap_weights(v2)
            assert report["replicas"][0]["version"].startswith(
                "model_v2")
            out2 = router.submit([x]).result(timeout=120)
            ref2 = inference.create_predictor(
                inference.Config(v2)).run([x])[0]
            np.testing.assert_allclose(out2[0], ref2, rtol=1e-5,
                                       atol=1e-6)
            assert np.abs(out2[0] - out[0]).max() > 1e-6
        finally:
            router.shutdown()
            sup.stop()
