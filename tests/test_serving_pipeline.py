"""Pipelined serving executor (ISSUE 2 tentpole).

Covers the pipeline-specific acceptance criteria: per-request output
ordering under overlap, deadline expiry, the fault barrier across
in-flight batches (an error in batch N must not poison batch N+1 or
kill the completion thread), >=2 shape buckets in flight, the
staging-buffer pool, warmup exclusion from traffic metrics, the
host_ms/device_ms stage split in metrics_json, and a fast-tier smoke
that the pipelined executor overlaps the batches the serial-batched one
runs one after another.
"""
import json
import time

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu import inference, serving


def _export(tmp_path, spec_shape, name, width=16):
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(8, width), nn.Tanh(),
                        nn.Linear(width, 4)).eval()
    p = str(tmp_path / name)
    paddle.jit.save(net, p, input_spec=[
        paddle.static.InputSpec(spec_shape, "float32", "x")])
    return inference.create_predictor(inference.Config(p))


@pytest.fixture()
def predictor(tmp_path):
    return _export(tmp_path, [None, 8], "m2d")


@pytest.fixture()
def seq_predictor(tmp_path):
    return _export(tmp_path, [None, None, 8], "m3d")


class TestPipelineCorrectness:
    def test_results_and_response_ordering(self, predictor):
        """Overlapped execution must keep request->response ordering:
        with one signature, futures resolve in submission order."""
        rng = np.random.RandomState(0)
        reqs = [rng.randn(1, 8).astype("float32") for _ in range(24)]
        refs = [predictor.run([r])[0] for r in reqs]
        done_order = []
        srv = serving.InferenceServer(predictor, max_batch_size=4,
                                      max_wait_ms=2, pipeline_depth=2,
                                      queue_capacity=64,
                                      name="t_pl_order", start=False)
        futs = srv.submit_many([[r] for r in reqs])
        for i, f in enumerate(futs):
            f.add_done_callback(lambda _f, i=i: done_order.append(i))
        srv.start()
        for f, ref in zip(futs, refs):
            np.testing.assert_allclose(f.result(timeout=60)[0], ref,
                                       rtol=1e-5, atol=1e-6)
        assert done_order == sorted(done_order)
        snap = srv.metrics.snapshot()
        assert 0 < snap["counters"]["batches"] < len(reqs)
        assert snap["counters"]["completed"] == len(reqs)
        srv.shutdown()

    def test_pipelined_matches_sync_executor(self, predictor):
        """pipeline_depth=0 (the pre-pipeline synchronous path) and
        depth=3 produce identical outputs for identical traffic."""
        rng = np.random.RandomState(1)
        reqs = [rng.randn(rng.randint(1, 4), 8).astype("float32")
                for _ in range(10)]
        outs = {}
        for depth in (0, 3):
            srv = serving.InferenceServer(
                predictor, max_batch_size=8, max_wait_ms=5,
                pipeline_depth=depth, name=f"t_pl_eq{depth}",
                start=False)
            futs = srv.submit_many([[r] for r in reqs])
            srv.start()
            outs[depth] = [f.result(timeout=60)[0] for f in futs]
            srv.shutdown()
        for a, b in zip(outs[0], outs[3]):
            np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)

    def test_seq_bucket_unpad_still_holds(self, seq_predictor):
        rng = np.random.RandomState(2)
        shapes = [(1, 3), (2, 5), (1, 7), (2, 2), (1, 4)]
        reqs = [rng.randn(b, s, 8).astype("float32") for b, s in shapes]
        refs = [seq_predictor.run([r])[0] for r in reqs]
        srv = serving.InferenceServer(seq_predictor, max_batch_size=4,
                                      max_wait_ms=5, pipeline_depth=2,
                                      seq_buckets=[4, 8], seq_axis=1,
                                      name="t_pl_seq", start=False)
        futs = srv.submit_many([[r] for r in reqs])
        srv.start()
        for f, ref in zip(futs, refs):
            out = f.result(timeout=60)[0]
            assert out.shape == ref.shape
            np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)
        srv.shutdown()

    def test_staging_pool_reused_not_regrown(self, predictor):
        """The staging pool allocates one ring per (signature,
        padded_rows) and reuses it — more traffic of the same shape
        must not grow the pool."""
        rng = np.random.RandomState(3)
        srv = serving.InferenceServer(predictor, max_batch_size=4,
                                      max_wait_ms=1, pipeline_depth=2,
                                      name="t_pl_pool", start=False)
        srv.start()
        for _ in range(3):
            futs = srv.submit_many(
                [[rng.randn(4, 8).astype("float32")] for _ in range(4)])
            for f in futs:
                f.result(timeout=60)
        n_keys = len(srv._staging)
        assert n_keys >= 1
        for _ in range(3):
            futs = srv.submit_many(
                [[rng.randn(4, 8).astype("float32")] for _ in range(4)])
            for f in futs:
                f.result(timeout=60)
        assert len(srv._staging) == n_keys   # reused, not reallocated
        srv.shutdown()


class TestPipelineRobustness:
    def test_deadline_expiry_pipelined(self, predictor):
        rng = np.random.RandomState(4)
        srv = serving.InferenceServer(predictor, pipeline_depth=2,
                                      name="t_pl_dl", start=False)
        fut = srv.submit([rng.randn(1, 8).astype("float32")],
                         timeout_ms=1)
        time.sleep(0.03)                # expire while queued
        srv.start()
        with pytest.raises(serving.DeadlineExceededError):
            fut.result(timeout=60)
        assert srv.metrics.snapshot()["counters"]["timed_out"] == 1
        srv.shutdown()

    def test_fault_barrier_across_inflight_batches(self, predictor):
        """A poisoned batch fails ONLY its own requests: batches queued
        behind it (and already in flight ahead of it) still complete,
        and the completion thread survives to serve more traffic."""
        rng = np.random.RandomState(5)
        srv = serving.InferenceServer(predictor, max_batch_size=2,
                                      max_wait_ms=1, pipeline_depth=2,
                                      name="t_pl_err", start=False)
        good_before = srv.submit_many(
            [[rng.randn(2, 8).astype("float32")] for _ in range(3)])
        bad = srv.submit([rng.randn(1, 5).astype("float32")])  # bad dim
        good_after = srv.submit_many(
            [[rng.randn(2, 8).astype("float32")] for _ in range(3)])
        srv.start()
        for f in good_before + good_after:
            assert f.result(timeout=60)[0].shape == (2, 4)
        with pytest.raises(Exception):
            bad.result(timeout=60)
        # completion thread survived; the server still serves
        late = srv.submit([rng.randn(1, 8).astype("float32")])
        assert late.result(timeout=60)[0].shape == (1, 4)
        snap = srv.metrics.snapshot()
        assert snap["counters"]["failed"] == 1
        assert snap["counters"]["completed"] == 7
        srv.shutdown()

    def test_two_buckets_in_flight(self, seq_predictor):
        """Two shape buckets' worth of traffic interleaved: the batcher
        dispatches a FULL bucket even while an older, still-open window
        is gathering a different signature, and the pipeline keeps both
        apart. Asserted by order and counts, not by a duration: the
        older window cannot close by itself within this test."""
        rng = np.random.RandomState(6)
        srv = serving.InferenceServer(seq_predictor, max_batch_size=2,
                                      max_wait_ms=120_000,
                                      pipeline_depth=2,
                                      seq_buckets=[4, 8], seq_axis=1,
                                      name="t_pl_2bkt", start=False)
        # one request in the seq=4 bucket opens a LONG window...
        slow = srv.submit([rng.randn(1, 3, 8).astype("float32")])
        # ...then a FULL seq=8 bucket arrives behind it
        fast = srv.submit_many(
            [[rng.randn(1, 7, 8).astype("float32")] for _ in range(2)])
        srv.start()
        for f in fast:
            assert f.result(timeout=60)[0].shape == (1, 7, 4)
        # the full bucket went through, and did not wait out (or
        # close) the window of the older, incompatible head-of-line
        # request, which is still gathering
        assert not slow.done()
        assert srv.metrics.snapshot()["counters"]["batches"] == 1
        # a second seq=4 request fills that window's bucket: the two
        # leave at once, as one batch
        filler = srv.submit([rng.randn(1, 2, 8).astype("float32")])
        assert slow.result(timeout=60)[0].shape == (1, 3, 4)
        assert filler.result(timeout=60)[0].shape == (1, 2, 4)
        counters = srv.metrics.snapshot()["counters"]
        assert counters["batches"] == 2 and counters["completed"] == 4
        srv.shutdown()

    def test_drain_completes_inflight(self, predictor):
        rng = np.random.RandomState(7)
        reqs = [rng.randn(1, 8).astype("float32") for _ in range(8)]
        srv = serving.InferenceServer(predictor, max_wait_ms=20,
                                      pipeline_depth=3,
                                      name="t_pl_drain", start=False)
        futs = srv.submit_many([[r] for r in reqs])
        srv.start()
        srv.shutdown(drain=True)
        for f in futs:
            assert f.done() and f.exception() is None

    def test_never_started_inline_drain(self, predictor):
        rng = np.random.RandomState(8)
        srv = serving.InferenceServer(predictor, pipeline_depth=2,
                                      name="t_pl_inline", start=False)
        fut = srv.submit([rng.randn(1, 8).astype("float32")])
        srv.shutdown()                  # inline drain, no worker thread
        assert fut.result(timeout=10)[0].shape == (1, 4)


class TestPipelineMetrics:
    def test_warmup_excluded_from_traffic_metrics(self, predictor):
        rng = np.random.RandomState(9)
        srv = serving.InferenceServer(predictor, max_batch_size=4,
                                      max_wait_ms=1, pipeline_depth=2,
                                      name="t_pl_warm", start=False)
        fresh = srv.warmup()
        snap = srv.metrics.snapshot()
        # compile accounting DOES see warmup...
        assert fresh == len(srv.bucket_specs())
        assert snap["compile_cache"]["misses"] == fresh
        # ...traffic metrics do NOT
        assert snap["counters"]["completed"] == 0
        assert snap["counters"]["batches"] == 0
        assert snap["batch_size_hist"] == {}
        assert snap["latency_ms"]["count"] == 0
        assert snap["stage_ms"]["count"] == 0
        assert snap["padding"]["padded_elements"] == 0
        srv.start()
        futs = srv.submit_many(
            [[rng.randn(1, 8).astype("float32")] for _ in range(4)])
        for f in futs:
            f.result(timeout=60)
        snap = srv.metrics.snapshot()
        assert snap["counters"]["completed"] == 4
        assert snap["compile_cache"]["hits"] >= 1
        srv.shutdown()

    def test_stage_ms_host_device_split_schema(self, predictor):
        rng = np.random.RandomState(10)
        srv = serving.InferenceServer(predictor, max_wait_ms=1,
                                      pipeline_depth=2,
                                      name="t_pl_stage", start=False)
        futs = srv.submit_many(
            [[rng.randn(2, 8).astype("float32")] for _ in range(6)])
        srv.start()
        for f in futs:
            f.result(timeout=60)
        snap = json.loads(srv.metrics_json())
        st = snap["stage_ms"]
        assert st["count"] == snap["counters"]["batches"] > 0
        for stage in ("assembly", "dispatch", "device_wait", "fetch",
                      "host", "device"):
            for q in ("p50", "p95", "p99", "max"):
                assert st[stage][q] >= 0.0, (stage, q)
        assert st["host"]["p50"] > 0.0
        assert 0.0 <= st["host_fraction"] <= 1.0
        srv.shutdown()

    def test_donation_flag_is_safe_on_cpu(self, predictor):
        """FLAGS_serving_donate_inputs falls back silently where the
        backend has no donation (CPU) — results identical."""
        rng = np.random.RandomState(11)
        x = rng.randn(2, 8).astype("float32")
        ref = predictor.run([x])[0]
        srv = serving.InferenceServer(predictor, max_wait_ms=1,
                                      pipeline_depth=2,
                                      donate_inputs=True,
                                      name="t_pl_donate", start=False)
        fut = srv.submit([x])
        srv.start()
        np.testing.assert_allclose(fut.result(timeout=60)[0], ref,
                                   rtol=1e-5, atol=1e-6)
        srv.shutdown()
        import jax
        if jax.default_backend() == "cpu":
            # donation coerced off on CPU: both variants resolve to the
            # same non-donating jitted call
            assert predictor._serving_call(True) \
                is predictor._serving_call(False)


class TestPipelineThroughputSmoke:
    def test_pipelined_not_slower_than_sync_batched(self, tmp_path):
        """Fast-tier smoke for the perf claim, as far as a shared CPU
        can carry it: on the same traffic the pipelined executor forms
        the same batches and answers the same as the serial-batched
        one, and it OVERLAPS them (batch N + 1 is dispatched while
        batch N is not yet completed, never more than the depth
        allows), which the serial one never does. That overlap is
        where the throughput comes from; how much is a chip's to say
        (tools/bench_serving.py --pipeline), not a wall clock's
        here."""
        pred = _export(tmp_path, [None, 8], "m_smoke", width=256)
        rng = np.random.RandomState(12)
        reqs = [[rng.randn(1, 8).astype("float32")] for _ in range(96)]

        def run(depth, name):
            srv = serving.InferenceServer(
                pred, max_batch_size=8, max_wait_ms=2,
                pipeline_depth=depth, queue_capacity=len(reqs) + 1,
                name=name, start=False)
            srv.warmup()
            inflight, complete = [], srv._complete

            def spy(inf):
                # the first batch is held back until the loop thread
                # has dispatched the second (it must not need the
                # first's completion for that)
                deadline = time.monotonic() + 60
                while depth and not inflight \
                        and srv.inflight_batches < 2 \
                        and time.monotonic() < deadline:
                    time.sleep(0.001)
                inflight.append(srv.inflight_batches)
                return complete(inf)

            srv._complete = spy
            futs = srv.submit_many(reqs)
            srv.start()
            outs = [f.result(timeout=120)[0] for f in futs]
            batches = srv.metrics.snapshot()["counters"]["batches"]
            srv.shutdown()
            return outs, batches, inflight

        sync_outs, sync_batches, sync_inflight = run(0, "t_pl_smoke_sync")
        pipe_outs, pipe_batches, pipe_inflight = run(2, "t_pl_smoke_pipe")
        for a, b in zip(sync_outs, pipe_outs):
            np.testing.assert_array_equal(a, b)
        assert sync_batches == pipe_batches == len(reqs) // 8
        assert len(sync_inflight) == len(pipe_inflight) == sync_batches
        assert max(sync_inflight) == 0       # nothing is ever in flight
        assert 2 <= max(pipe_inflight) <= 2 + 1, pipe_inflight
