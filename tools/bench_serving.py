"""CPU micro-bench: dynamic-batched serving vs serial Predictor.run.

Acceptance gauge for ISSUE 1: at batchable load (many outstanding
single-row requests) the InferenceServer must deliver >= 2x the
throughput of a serial one-request-at-a-time loop over the same
Predictor — the host-overhead amortization VERDICT.md said the serving
story was missing. Runs on CPU (JAX_PLATFORMS=cpu) so it measures the
dispatch/coalescing machinery, not accelerator speed.

``--pipeline`` is the ISSUE 2 gauge: the 3-stage pipelined executor
(host assembly overlapping device compute via the completion thread)
against the synchronous batched executor (``pipeline_depth=0``) on the
same traffic, reporting the per-batch host_ms/device_ms stage split
from the serving metrics. Target >= 1.3x pipelined over batched-serial.
On multi-core hosts a wider model (``--hidden 1024``) also shows the
overlap of host assembly with device compute; the default 256 keeps
the gauge meaningful on single-core CI boxes where serial device
compute would drown the executor delta.

    python tools/bench_serving.py [--requests 256] [--batch 16] [--json]
    python tools/bench_serving.py --pipeline [--depth 2] [--trials 3]
"""
import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

import paddle_tpu as paddle  # noqa: E402
import paddle_tpu.nn as nn  # noqa: E402
from paddle_tpu import inference, serving  # noqa: E402


def build_predictor(tmpdir, hidden=256, layers=2):
    paddle.seed(0)
    blocks = [nn.Linear(64, hidden), nn.Tanh()]
    for _ in range(layers - 1):
        blocks += [nn.Linear(hidden, hidden), nn.Tanh()]
    blocks.append(nn.Linear(hidden, 16))
    net = nn.Sequential(*blocks).eval()
    prefix = os.path.join(tmpdir, "bench_model")
    paddle.jit.save(net, prefix, input_spec=[
        paddle.static.InputSpec([None, 64], "float32", "x")],
        pdmodel_format=False)
    return inference.create_predictor(inference.Config(prefix))


def bench_serial(pred, reqs):
    # warm the shape so serial pays no compile inside the timed region
    pred.run([reqs[0]])
    t0 = time.perf_counter()
    for r in reqs:
        pred.run([r])
    dt = time.perf_counter() - t0
    return len(reqs) / dt, dt


def bench_server(pred, reqs, max_batch, wait_ms, pipeline_depth=None,
                 name="bench", cls=None, start_first=False):
    """``start_first`` (the --pipeline regime) starts the worker before
    submitting, so the submission loop overlaps execution — the live-
    traffic shape where executor speed is the bottleneck. The default
    (PR 1's regime) pre-loads the whole queue, so every batch is full."""
    kw = {} if pipeline_depth is None \
        else {"pipeline_depth": pipeline_depth}
    srv = (cls or serving.InferenceServer)(
        pred, max_batch_size=max_batch, max_wait_ms=wait_ms,
        queue_capacity=len(reqs) + 1, name=name, start=False, **kw)
    srv.warmup()                      # full pow2 lattice: no compiles
    t0 = time.perf_counter()          # inside the timed region
    if start_first:
        srv.start()
        futs = srv.submit_many([[r] for r in reqs])
    else:
        futs = srv.submit_many([[r] for r in reqs])
        srv.start()
    for f in futs:
        f.result(timeout=600)
    dt = time.perf_counter() - t0
    snap = srv.metrics.snapshot()
    srv.shutdown()
    return len(reqs) / dt, dt, snap


class _PR1Server(serving.InferenceServer):
    """PR 1's batched-serial executor, reconstructed verbatim as the
    --pipeline comparison baseline: per-batch np.concatenate of the
    request feeds, fresh np.zeros pad blocks, the EAGER exported.call
    (no jit fast path, no donation), one blocking device_get — the
    execution path the pipelined executor replaces. Built with
    ``pipeline_depth=0`` so the worker routes through this _execute."""

    def submit_many(self, feeds, timeout_ms=None):
        # PR 1's submit_many verbatim: a per-request submit loop —
        # one batcher lock + condvar notify + monitor stat per request
        return [self.submit(f, timeout_ms=timeout_ms) for f in feeds]

    def _execute(self, batch, record_latency=True, record_traffic=True):
        rows = sum(r.rows for r in batch)
        padded_rows = self.policy.bucket_batch(rows)
        if record_traffic:
            sig = batch[0].signature
            per_row = self.policy.elements_per_row(sig)
            real = sum(int(np.prod(a.shape)) if a.ndim else 1
                       for r in batch for a in r.feeds)
            self.metrics.observe_batch(rows, real, padded_rows * per_row)
        feeds_list = [r.feeds for r in batch]
        n_pad = padded_rows - rows
        if n_pad:
            feeds_list = feeds_list + [
                [np.zeros((n_pad,) + tuple(a.shape[1:]), a.dtype)
                 for a in batch[0].feeds]]
        t0 = time.perf_counter()
        per_req = [[np.asarray(a) for a in feeds] for feeds in feeds_list]
        arrays = [jax.device_put(
            np.concatenate([r[i] for r in per_req], axis=0)
            if len(per_req) > 1 else per_req[0][i])
            for i in range(len(per_req[0]))]
        t1 = time.perf_counter()
        out = self.predictor._artifact(*arrays)     # eager exported.call
        outs = list(out) if isinstance(out, (list, tuple)) else [out]
        t2 = time.perf_counter()
        host = jax.device_get(outs)
        t3 = time.perf_counter()
        total = padded_rows
        ofs = 0
        for r in batch:
            outs_r = [h[ofs:ofs + r.rows]
                      if getattr(h, "ndim", 0) and h.shape[0] == total
                      else np.asarray(h) for h in host]
            ofs += r.rows
            if r.future.set_running_or_notify_cancel():
                r.future.set_result(outs_r)
                if record_traffic:
                    self.metrics.count("completed")
                if record_latency:
                    self.metrics.observe_latency(r.latency_ms())
        if record_traffic:
            self.metrics.observe_stage_times(
                (t1 - t0) * 1e3, (t2 - t1) * 1e3, 0.0, (t3 - t2) * 1e3)
        return 0


def scrape_check(server_name, snap, expected_requests):
    """End-to-end check of the exposition path (ISSUE 3): start the
    shared telemetry endpoint, scrape /metrics over HTTP, and assert the
    scraped serving counters equal the bench's own request accounting
    (and the ServingMetrics snapshot). Registry families outlive the
    shut-down server, so scraping after the run sees the full totals."""
    import re
    import urllib.request

    from paddle_tpu import observability

    tel = observability.start_telemetry_server(port=0)
    text = urllib.request.urlopen(tel.url("/metrics"),
                                  timeout=10).read().decode()

    def scraped(event):
        m = re.search(
            rf'paddle_serving_requests_total\{{event="{event}",'
            rf'server="{server_name}"\}} (\d+)', text)
        return int(m.group(1)) if m else -1

    detail, ok = {}, True
    for ev in ("submitted", "completed", "batches"):
        got, want = scraped(ev), snap["counters"][ev]
        detail[ev] = {"scraped": got, "snapshot": want}
        ok = ok and got == want
    detail["requests"] = {"scraped": scraped("completed"),
                          "expected": expected_requests}
    ok = ok and scraped("completed") == expected_requests
    detail["ok"] = ok
    return ok, detail


def _stage_summary(snap):
    st = snap["stage_ms"]
    return {
        "host_ms_p50": round(st["host"]["p50"], 3),
        "host_ms_p95": round(st["host"]["p95"], 3),
        "device_ms_p50": round(st["device"]["p50"], 3),
        "device_ms_p95": round(st["device"]["p95"], 3),
        "assembly_ms_p50": round(st["assembly"]["p50"], 3),
        "dispatch_ms_p50": round(st["dispatch"]["p50"], 3),
        "device_wait_ms_p50": round(st["device_wait"]["p50"], 3),
        "fetch_ms_p50": round(st["fetch"]["p50"], 3),
        "host_fraction": round(st["host_fraction"], 3),
    }


def run_default(args):
    rng = np.random.RandomState(0)
    reqs = [rng.randn(1, 64).astype("float32")
            for _ in range(args.requests)]
    with tempfile.TemporaryDirectory() as d:
        pred = build_predictor(d, hidden=args.hidden or 256)
        serial_rps, serial_s = bench_serial(pred, reqs)
        batched_rps, batched_s, snap = bench_server(
            pred, reqs, args.batch, args.wait_ms)
    out = {
        "requests": args.requests,
        "max_batch_size": args.batch,
        "serial_rps": round(serial_rps, 1),
        "serial_total_s": round(serial_s, 4),
        "batched_rps": round(batched_rps, 1),
        "batched_total_s": round(batched_s, 4),
        "speedup": round(batched_rps / serial_rps, 2),
        "batches": snap["counters"]["batches"],
        "batch_size_hist": snap["batch_size_hist"],
        "compile_cache": snap["compile_cache"],
        "latency_ms": snap["latency_ms"],
        "stage_ms": _stage_summary(snap),
    }
    scrape_ok = True
    if args.scrape:
        scrape_ok, out["scrape"] = scrape_check("bench", snap,
                                                args.requests)
    if args.json:
        print(json.dumps(out, indent=1))
    else:
        print(f"serial : {out['serial_rps']:>9.1f} req/s "
              f"({out['serial_total_s']}s for {args.requests})")
        print(f"batched: {out['batched_rps']:>9.1f} req/s "
              f"({out['batched_total_s']}s, "
              f"{out['batches']} device batches)")
        print(f"speedup: {out['speedup']}x  "
              f"(target >= 2x at batchable load)")
        print(f"compile cache: {out['compile_cache']}")
        print(f"latency ms: p50={out['latency_ms']['p50']:.2f} "
              f"p95={out['latency_ms']['p95']:.2f} "
              f"p99={out['latency_ms']['p99']:.2f}")
        print(f"host/device split: {out['stage_ms']}")
        if args.scrape:
            print(f"scrape check ({'OK' if scrape_ok else 'MISMATCH'}): "
                  f"{out['scrape']}")
    return 0 if out["speedup"] >= 2.0 and scrape_ok else 1


def run_pipeline(args):
    """Pipelined (depth N) vs synchronous batched (depth 0) executor —
    same predictor, same traffic, same warmed compile cache. Each
    executor runs ``--trials`` times and reports its MEDIAN throughput;
    trials are INTERLEAVED round-robin across the executors so a slow
    phase of the box (single-core CI jitters 20%+) taxes all three
    equally instead of whichever ran during it."""
    rng = np.random.RandomState(0)
    reqs = [rng.randn(1, 64).astype("float32")
            for _ in range(args.requests)]
    hidden = args.hidden or 256

    configs = [
        ("pr1", dict(pipeline_depth=0, name="bench_pr1",
                     cls=_PR1Server)),
        ("sync", dict(pipeline_depth=0, name="bench_sync")),
        ("pipe", dict(pipeline_depth=args.depth, name="bench_pipe")),
    ]
    runs = {key: [] for key, _ in configs}
    with tempfile.TemporaryDirectory() as d:
        pred = build_predictor(d, hidden=hidden, layers=args.layers)
        serial_rps, _ = bench_serial(pred, reqs)
        import gc
        gc.collect()
        gc.disable()      # GC pauses are run-to-run noise, not executor
        old_switch = sys.getswitchinterval()
        # the pipelined executor hands work between two CPU-bound
        # threads; the default 5 ms GIL switch interval turns each
        # hand-off into a scheduling bubble on small batches
        sys.setswitchinterval(1e-3)
        try:
            for _ in range(max(1, args.trials)):
                for key, kw in configs:
                    runs[key].append(bench_server(
                        pred, reqs, args.batch, args.wait_ms,
                        start_first=True, **kw))
                    gc.collect()   # between trials, outside the timing
        finally:
            gc.enable()
            sys.setswitchinterval(old_switch)

    def median(key):
        r = sorted(runs[key], key=lambda x: x[0])
        return r[len(r) // 2]

    pr1_rps, pr1_s, pr1_snap = median("pr1")
    sync_rps, sync_s, sync_snap = median("sync")
    pipe_rps, pipe_s, pipe_snap = median("pipe")
    out = {
        "mode": "pipeline",
        "requests": args.requests,
        "max_batch_size": args.batch,
        "hidden": hidden,
        "pipeline_depth": args.depth,
        "serial_rps": round(serial_rps, 1),
        "pr1_batched_rps": round(pr1_rps, 1),
        "pr1_batched_total_s": round(pr1_s, 4),
        "batched_sync_rps": round(sync_rps, 1),
        "batched_sync_total_s": round(sync_s, 4),
        "pipelined_rps": round(pipe_rps, 1),
        "pipelined_total_s": round(pipe_s, 4),
        "speedup_vs_serial": round(pipe_rps / serial_rps, 2),
        "speedup_vs_pr1_batched": round(pipe_rps / pr1_rps, 2),
        "speedup_vs_batched_sync": round(pipe_rps / sync_rps, 2),
        "pr1_stage_ms": _stage_summary(pr1_snap),
        "sync_stage_ms": _stage_summary(sync_snap),
        "pipelined_stage_ms": _stage_summary(pipe_snap),
        "batches": pipe_snap["counters"]["batches"],
        "compile_cache": pipe_snap["compile_cache"],
        "latency_ms": pipe_snap["latency_ms"],
    }
    if args.json:
        print(json.dumps(out, indent=1))
    else:
        print(f"serial          : {out['serial_rps']:>9.1f} req/s")
        print(f"PR1 batched     : {out['pr1_batched_rps']:>9.1f} req/s "
              f"({out['pr1_batched_total_s']}s — concat+eager-call "
              f"executor)")
        print(f"batched sync    : {out['batched_sync_rps']:>9.1f} req/s "
              f"({out['batched_sync_total_s']}s — staging+jit, "
              f"pipeline_depth=0)")
        print(f"pipelined       : {out['pipelined_rps']:>9.1f} req/s "
              f"({out['pipelined_total_s']}s, "
              f"depth={args.depth}, {out['batches']} batches)")
        print(f"speedup vs PR1 batched-serial: "
              f"{out['speedup_vs_pr1_batched']}x (target >= 1.3x); "
              f"vs sync executor: {out['speedup_vs_batched_sync']}x; "
              f"vs serial: {out['speedup_vs_serial']}x")
        print(f"pr1   stage ms: {out['pr1_stage_ms']}")
        print(f"sync  stage ms: {out['sync_stage_ms']}")
        print(f"pipe  stage ms: {out['pipelined_stage_ms']}")
    return 0 if out["speedup_vs_pr1_batched"] >= 1.3 else 1


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=256)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--wait-ms", type=float, default=5.0)
    ap.add_argument("--pipeline", action="store_true",
                    help="ISSUE 2 gauge: pipelined vs sync batched "
                         "executor with host/device stage split")
    ap.add_argument("--depth", type=int, default=2,
                    help="pipeline depth for --pipeline mode")
    ap.add_argument("--trials", type=int, default=5,
                    help="interleaved runs per executor in --pipeline "
                         "mode (median reported)")
    ap.add_argument("--hidden", type=int, default=0,
                    help="model width (0 = auto: 256)")
    ap.add_argument("--layers", type=int, default=2,
                    help="hidden Linear+Tanh blocks in the bench model")
    ap.add_argument("--scrape", action="store_true",
                    help="scrape /metrics over HTTP at end-of-run and "
                         "assert scraped serving counters match the "
                         "bench's own request accounting")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable output only")
    args = ap.parse_args()
    return run_pipeline(args) if args.pipeline else run_default(args)


if __name__ == "__main__":
    sys.exit(main())
