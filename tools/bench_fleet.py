"""Fleet bench: aggregate QPS scaling, warm scale-out, rolling swap.

Three phases, one committed BENCH_FLEET_r*.json record:

1. **scaling** — N replica worker PROCESSES behind the FleetRouter's
   HTTP front end, driven by a multi-process closed-loop load
   generator. Replicas run the ``StubBackend``: a real worker process
   speaking the real wire protocol whose "device" is ``device_ms`` of
   held-lock sleep per dispatched batch — the accelerator-bound
   production shape (device compute holds no host CPU), which is what
   makes fleet scaling measurable on a single-core CI box where four
   CPU-bound model replicas would just share one core. Headline:
   aggregate QPS and p99 at 1 vs 4 replicas (target >= 3x).

2. **scale_out** — REAL workers (Predictor + InferenceServer over a
   jit-saved MLP with a 16-point batch x seq bucket lattice): median
   spawn->ready time of a cold replica (fresh compile cache, full
   lattice warmup) vs a warm one (shared ``FLAGS_compile_cache_dir``
   + traffic-recorded warmup manifest, PR 5's machinery). Target:
   warm >= 2x faster — the fleet's elastic-scale story.

3. **rolling_swap** — 2 real replicas serving live router traffic
   while ``swap_weights`` drains/reloads them one at a time onto a
   version-stamped v2 artifact. Asserts ZERO failed requests and that
   post-swap outputs match a local v2 reference predictor.

A separately-invoked slice (``--mesh``) benches TENSOR-PARALLEL
serving instead (serving/mesh.py: one replica spanning an ``mp``
mesh): greedy decode tok/s and measured per-chip KV-pool residency
for the sharded vs the single-shard engine, with a greedy-parity
cross-check between the two. Emits a BENCH_TP_r*.json record. Honest
caveat baked into the record: on the CPU virtual-device mesh the mp
"chips" are XLA partitions sharing one host's cores — partitioning
overhead without partitioned silicon — so the committed CPU record's
perf claims are the memory split and parity, not the tok/s ratio;
the TPU rows rerun via bench.py when a TPU is reachable.

A fourth, separately-invoked phase (``--trace``) exercises the
distributed-tracing layer instead: a fully-sampled run through the
router front end whose per-stage span counts are cross-checked
against the bench's own request accounting (every counted call must
leave exactly one ``router::request``, one ``router::forward`` and
one ``worker::submit_many`` span in the flight recorder), plus a
tracing-off vs ``FLAGS_trace_sample_rate=0.05`` QPS comparison on the
stub-process fleet — the acceptance bound is < 5% regression. Emits a
TRACE_r*.json record.

Usage: JAX_PLATFORMS=cpu python tools/bench_fleet.py
       [--replicas 4] [--duration 6] [--trials 2]
       [--device-ms 12] [--out BENCH_FLEET_rNN.json]
       [--skip-scaleout] [--skip-swap]
       [--trace --out TRACE_rNN.json]
       [--mesh --mesh-mp 8 --out BENCH_TP_rNN.json]
"""
import argparse
import json
import os
import sys
import threading
import time
import urllib.error
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from tools._bench_common import emit_record  # noqa: E402

# One process owns a chip. This parent spawns workers, so it never asks
# jax for its default backend: the phases that compute in-process pin
# themselves to the CPU first (``_parent_on_cpu``), and the real workers
# run where ``_WORKER_PLATFORM`` says. Only ``--mesh`` is one process
# that computes on whatever jax finds.
_WORKER_PLATFORM = "cpu"


def _parent_on_cpu():
    import jax
    jax.config.update("jax_platforms", "cpu")


def _median(xs):
    xs = sorted(xs)
    return xs[len(xs) // 2]


def _pctl(xs, q):
    if not xs:
        return 0.0
    xs = sorted(xs)
    return xs[min(len(xs) - 1, int(round(q * (len(xs) - 1))))]


def _opener():
    return urllib.request.build_opener(
        urllib.request.ProxyHandler({}))


# ------------------------------------------------------------- loadgen
def _loadgen_main(cfg: dict) -> dict:
    """One load-generator PROCESS (spawned as ``bench_fleet.py
    --loadgen <json>``, NOT forked — forking a process with live JAX
    threads risks deadlock): ``threads`` closed-loop threads each
    POSTing k-request batches to the router front end. Counting is
    wall-clock aligned across generators (``start_at`` ..
    ``start_at + duration_s``); ramp traffic before the window is
    sent but not counted. Returns (completed, shed, errors,
    latency percentiles)."""
    from paddle_tpu.serving.fleet import codec

    opener = _opener()
    k = int(cfg["k"])
    payload = np.ones((1, 16), np.float32)
    body = codec.encode_batch([[payload]] * k)
    lock = threading.Lock()
    stats = {"completed": 0, "shed": 0, "errors": 0}
    lat = []
    t_count = float(cfg["start_at"])
    t_end = t_count + float(cfg["duration_s"])
    url = cfg["url"]

    def _one():
        req = urllib.request.Request(
            url + "/submit_many", data=body,
            headers={"Content-Type": "application/x-paddle-fleet"})
        t0 = time.perf_counter()
        resp = opener.open(req, timeout=30)
        results = codec.decode_results(resp.read())
        ms = (time.perf_counter() - t0) * 1e3
        ok = sum(1 for r in results
                 if not isinstance(r, BaseException))
        return ok, len(results) - ok, ms

    def _loop():
        while time.time() < t_end:
            counting = time.time() >= t_count
            try:
                ok, bad, ms = _one()
                if counting:
                    with lock:
                        stats["completed"] += ok
                        stats["errors"] += bad
                        lat.append(ms)
            except urllib.error.HTTPError as e:
                e.read()
                if counting:
                    with lock:
                        key = "shed" if e.code in (429, 503) \
                            else "errors"
                        stats[key] += k
                time.sleep(0.002)
            except Exception:  # noqa: BLE001 - router teardown race
                if counting:
                    with lock:
                        stats["errors"] += k
                time.sleep(0.01)

    ts = [threading.Thread(target=_loop)
          for _ in range(int(cfg["threads"]))]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    stats["lat"] = lat
    return stats


def _run_load(url, *, k, threads, procs, duration_s, ramp_s=3.0):
    """Drive ``procs`` loadgen subprocesses against ``url``; the
    counted window starts ``ramp_s`` from now (imports + first
    requests happen during the ramp) and is identical across
    generators."""
    import subprocess
    cfg = {"url": url, "k": k, "threads": threads,
           "duration_s": duration_s,
           "start_at": time.time() + ramp_s}
    workers = [subprocess.Popen(
        [sys.executable, os.path.abspath(__file__),
         "--loadgen", json.dumps(cfg)],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        text=True) for _ in range(procs)]
    agg = {"completed": 0, "shed": 0, "errors": 0, "lat": []}
    for p in workers:
        out, _ = p.communicate(timeout=ramp_s + duration_s + 120)
        s = json.loads(out.strip().splitlines()[-1])
        for key in ("completed", "shed", "errors"):
            agg[key] += s[key]
        agg["lat"].extend(s["lat"])
    agg["qps"] = agg["completed"] / duration_s
    agg["p50_ms"] = round(_pctl(agg["lat"], 0.50), 2)
    agg["p99_ms"] = round(_pctl(agg["lat"], 0.99), 2)
    agg["calls"] = len(agg["lat"])
    del agg["lat"]
    return agg


# ------------------------------------------------------------- phases
def _phase_scaling(args):
    """Aggregate QPS at 1 vs N stub replicas through the router."""
    from paddle_tpu.serving import fleet

    out = {"replica_backend":
           f"stub worker processes (device_ms={args.device_ms}, "
           f"max_batch={args.stub_batch}; accelerator-emulating: "
           f"device time is held-lock sleep, protocol/router/codec "
           f"are the production path)",
           "loadgen": {"procs": args.load_procs,
                       "threads_per_proc": args.load_threads,
                       "batch_per_call": args.load_k,
                       "duration_s": args.duration,
                       "trials": args.trials}}
    for n in (1, args.replicas):
        trials = []
        for trial in range(args.trials):
            fac = fleet.ProcessReplicaFactory(extra_args=[
                "--stub",
                "--stub-device-ms", str(args.device_ms),
                "--stub-max-batch", str(args.stub_batch),
                "--stub-capacity", str(args.stub_capacity)])
            sup = fleet.ReplicaSupervisor(fac, n).start()
            router = fleet.FleetRouter(
                supervisor=sup, name=f"bench-{n}-{trial}",
                health_interval_ms=200)
            try:
                if not router.wait_ready(n, timeout=60):
                    raise RuntimeError(
                        f"{n} stub replicas not ready in 60s: "
                        f"{router.replica_states()}")
                app = fleet.RouterApp(router,
                                      host="127.0.0.1").start()
                try:
                    trials.append(_run_load(
                        app.url(), k=args.load_k,
                        threads=args.load_threads,
                        procs=args.load_procs,
                        duration_s=args.duration))
                finally:
                    app.stop()
            finally:
                router.shutdown()
                sup.stop()
        best = sorted(trials, key=lambda s: s["qps"])[len(trials) // 2]
        best["trials_qps"] = [round(s["qps"], 1) for s in trials]
        out[f"replicas_{n}"] = best
    q1 = out["replicas_1"]["qps"]
    qn = out[f"replicas_{args.replicas}"]["qps"]
    out["speedup"] = round(qn / q1, 2) if q1 else 0.0
    return out


def _build_artifact(tmpdir, name, seed, hidden=192, layers=4):
    """A deliberately non-trivial MLP: per-signature XLA compile time
    must dominate the ~1s import floor for the cold/warm split to
    measure the cache, not Python startup (PR 5's bench sized its
    lattice the same way)."""
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    paddle.seed(seed)
    blocks = [nn.Linear(8, hidden), nn.Tanh()]
    for _ in range(layers - 1):
        blocks += [nn.Linear(hidden, hidden), nn.Tanh()]
    blocks.append(nn.Linear(hidden, 4))
    net = nn.Sequential(*blocks).eval()
    prefix = os.path.join(tmpdir, name)
    paddle.jit.save(net, prefix, input_spec=[
        paddle.static.InputSpec([None, None, 8], "float32", "x")])
    return prefix


_SEQ_BUCKETS = (8, 16, 32, 64, 128)
_ROW_BUCKETS = (1, 2, 4, 8)


def _real_factory(fleet, prefix, cache_dir, warmup, **kw):
    return fleet.ProcessReplicaFactory(
        extra_args=["--model-prefix", prefix,
                    "--warmup", warmup,
                    "--max-batch-size", "8",
                    "--seq-buckets",
                    ",".join(str(s) for s in _SEQ_BUCKETS)],
        env={"JAX_PLATFORMS": _WORKER_PLATFORM,
             "FLAGS_compile_cache_dir": cache_dir}, **kw)


def _time_to_ready(factory, rid, timeout=300.0):
    """Spawn one replica, poll /readyz, return (seconds, proc)."""
    opener = _opener()
    t0 = time.monotonic()
    proc = factory(rid)
    deadline = t0 + timeout
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            raise RuntimeError(
                f"replica exited rc={proc.poll()} during warmup")
        url = proc.url()
        if url:
            try:
                with opener.open(url + "/readyz",
                                 timeout=2.0) as resp:
                    if json.loads(resp.read()).get("ready"):
                        return time.monotonic() - t0, proc
            except Exception:  # noqa: BLE001 - keep polling
                pass
        time.sleep(0.01)
    raise RuntimeError("replica not ready within timeout")


def _drive_lattice(url):
    """Hit every (row, seq) lattice point once so the worker's
    manifest records the full traffic lattice."""
    from paddle_tpu.serving.fleet import codec
    opener = _opener()
    for rows in _ROW_BUCKETS:
        for seq in _SEQ_BUCKETS:
            body = codec.encode_batch(
                [[np.zeros((rows, seq, 8), np.float32)]])
            with opener.open(urllib.request.Request(
                    url + "/submit_many", data=body),
                    timeout=60) as resp:
                results = codec.decode_results(resp.read())
            if isinstance(results[0], BaseException):
                raise results[0]


def _phase_scaleout(args, workdir):
    """Cold (fresh cache, lattice warmup) vs warm (shared cache +
    manifest replay) spawn->ready time for a real replica."""
    _parent_on_cpu()
    from paddle_tpu.serving import fleet

    prefix = _build_artifact(workdir, "model_v1", seed=0)
    shared_cache = os.path.join(workdir, "cache")

    # seed the shared cache + manifest: one replica warms the lattice
    # (populating the cache), then real traffic over every lattice
    # point records the manifest signatures
    fac = _real_factory(fleet, prefix, shared_cache, "lattice")
    seed_s, proc = _time_to_ready(fac, 900)
    _drive_lattice(proc.url())
    proc.terminate()
    proc.wait(10)

    cold, warm = [], []
    for trial in range(args.scaleout_trials):
        cold_cache = os.path.join(workdir, f"cold-cache-{trial}")
        fac = _real_factory(fleet, prefix, cold_cache, "lattice")
        s, proc = _time_to_ready(fac, 1000 + trial)
        cold.append(s)
        proc.terminate()
        proc.wait(10)
        fac = _real_factory(fleet, prefix, shared_cache, "manifest")
        s, proc = _time_to_ready(fac, 2000 + trial)
        warm.append(s)
        proc.terminate()
        proc.wait(10)
    return {
        "lattice_points": len(_SEQ_BUCKETS) * len(_ROW_BUCKETS),
        "seed_replica_ready_s": round(seed_s, 2),
        "cold_ready_s": round(_median(cold), 2),
        "warm_ready_s": round(_median(warm), 2),
        "cold_trials_s": [round(s, 2) for s in cold],
        "warm_trials_s": [round(s, 2) for s in warm],
        "warm_speedup": round(_median(cold) / _median(warm), 2),
    }, prefix, shared_cache


def _phase_swap(args, workdir, prefix_v1, shared_cache):
    """Rolling hot swap under live traffic: zero failed requests,
    v2 outputs verified against a local reference predictor."""
    from paddle_tpu import inference
    from paddle_tpu.serving import fleet

    prefix_v2 = _build_artifact(workdir, "model_v2", seed=7)
    fac = _real_factory(fleet, prefix_v1, shared_cache, "auto")
    sup = fleet.ReplicaSupervisor(fac, 2).start()
    router = fleet.FleetRouter(supervisor=sup, name="bench-swap",
                               health_interval_ms=100)
    stats = {"completed": 0, "failed": 0, "errors": []}
    stop = threading.Event()
    rng = np.random.RandomState(0)
    probe = rng.randn(2, 16, 8).astype("float32")

    def _traffic():
        while not stop.is_set():
            futs = router.submit_many([[probe]] * 2)
            for f in futs:
                try:
                    f.result(timeout=120)
                    stats["completed"] += 1
                except Exception as e:  # noqa: BLE001 - count, and
                    stats["failed"] += 1  # keep hammering
                    if len(stats["errors"]) < 5:
                        stats["errors"].append(
                            f"{type(e).__name__}: {e}")
            time.sleep(0.005)

    try:
        if not router.wait_ready(2, timeout=300):
            raise RuntimeError(
                f"swap fleet not ready: {router.replica_states()}")
        pre = [s["version"] for s in router.replica_states()]
        threads = [threading.Thread(target=_traffic)
                   for _ in range(args.swap_threads)]
        for t in threads:
            t.start()
        time.sleep(0.5)
        t0 = time.perf_counter()
        report = router.swap_weights(prefix_v2)
        swap_s = time.perf_counter() - t0
        time.sleep(0.5)
        stop.set()
        for t in threads:
            t.join()
        post = [s["version"] for s in router.replica_states()]
        # verify the new weights are live: fleet output == local v2
        out = router.submit([probe]).result(timeout=120)[0]
        ref = inference.create_predictor(
            inference.Config(prefix_v2)).run([probe])[0]
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)
        return {
            "requests_during_swap": stats["completed"],
            "failed_requests": stats["failed"],
            "errors": stats["errors"],
            "swap_total_s": round(swap_s, 2),
            "pre_versions": pre, "post_versions": post,
            "swap_report": report,
            "output_matches_v2_reference": True,
        }
    finally:
        stop.set()
        router.shutdown()
        sup.stop()


# ------------------------------------------------------- tensor-parallel
def _mesh_decode_trial(model, mesh, *, batch, page_size, pages_per_seq,
                       prefill_len, steps):
    """Greedy decode ``steps`` tokens on ``batch`` streams through one
    CachedDecoder (single-shard when ``mesh`` is None); returns tok/s,
    the emitted greedy streams (for the parity cross-check) and the
    MEASURED per-chip pool bytes of the placed KV pools."""
    import jax

    from paddle_tpu.serving.generation.model_fns import CachedDecoder
    from paddle_tpu.serving.mesh import ServingMesh

    smesh = ServingMesh(mesh)
    dec = CachedDecoder(model, max_batch=batch, page_size=page_size,
                        pages_per_seq=pages_per_seq, donate=False,
                        use_pallas=False, mesh=smesh)
    k, v = model.init_kv_pools(1 + batch * pages_per_seq, page_size)
    k, v = smesh.place_pools(k, v)
    pool_leaves = jax.tree_util.tree_leaves((k, v))
    total_kv = sum(int(a.size) * int(a.dtype.itemsize)
                   for a in pool_leaves)
    per_chip_kv = sum(int(np.prod(a.addressable_shards[0].data.shape))
                      * int(a.dtype.itemsize) for a in pool_leaves)
    rng = np.random.RandomState(0)
    ids = rng.randint(1, 100, size=(batch, prefill_len)).astype(np.int64)
    plens = np.full((batch,), prefill_len, np.int32)
    tables = (1 + np.arange(batch * pages_per_seq, dtype=np.int32)
              .reshape(batch, pages_per_seq))
    _, last, k, v, _ = dec.prefill(ids, plens, tables, None, None, k, v)
    toks = np.asarray(last).argmax(-1).astype(np.int64)
    active = np.ones((batch,), bool)
    streams = [toks.copy()]
    # untimed warmup step compiles the decode executable
    pos = plens.astype(np.int32)
    _, lg, k, v, _ = dec.decode(toks, pos, active, pos + 1, tables,
                                None, None, k, v)
    toks = np.asarray(lg).argmax(-1).astype(np.int64)
    streams.append(toks.copy())
    t0 = time.perf_counter()
    for i in range(steps):
        pos = (plens + 1 + i).astype(np.int32)
        _, lg, k, v, _ = dec.decode(toks, pos, active, pos + 1, tables,
                                    None, None, k, v)
        toks = np.asarray(lg).argmax(-1).astype(np.int64)
        streams.append(toks.copy())
    dt = time.perf_counter() - t0
    return {
        "decode_tok_s": round(batch * steps / dt, 1),
        "kv_pool_bytes": int(total_kv),
        "per_chip_kv_bytes": int(per_chip_kv),
        "streams": np.stack(streams, 1),
    }


def _phase_mesh(args):
    """Sharded vs single-shard decode for ONE replica spanning an
    ``{'mp': N}`` mesh. The memory claim (per-chip KV = 1/mp of the
    pool) and the greedy parity are exact on any backend; the tok/s
    ratio only means something on real multi-chip silicon."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.distributed.mesh_utils import build_mesh
    from paddle_tpu.models import GPTForCausalLM
    from paddle_tpu.models.gpt import gpt_tiny

    mp = int(args.mesh_mp)
    paddle.seed(0)
    cfg = gpt_tiny(num_heads=8, hidden_size=128, num_layers=4,
                   vocab_size=256, max_seq_len=256, stacked=True,
                   use_flash_attention=False)
    model = GPTForCausalLM(cfg)
    model.eval()
    geom = dict(batch=int(args.mesh_batch), page_size=16,
                pages_per_seq=8, prefill_len=32,
                steps=int(args.mesh_steps))
    single = _mesh_decode_trial(model, None, **geom)
    sharded = _mesh_decode_trial(model, build_mesh({"mp": mp}), **geom)
    parity = bool((single.pop("streams")
                   == sharded.pop("streams")).all())
    single.pop("per_chip_kv_bytes")      # meaningless without a mesh
    sharded["per_chip_kv_fraction"] = round(
        sharded["per_chip_kv_bytes"] / sharded["kv_pool_bytes"], 6)
    return {
        "mp": mp,
        "devices": len(jax.devices()),
        "model": {"hidden": cfg.hidden_size, "layers": cfg.num_layers,
                  "heads": cfg.num_heads, "stacked": True},
        **{k: v for k, v in geom.items()},
        "single_shard": single,
        "sharded": sharded,
        "greedy_parity": parity,
        "caveats": (
            "CPU record: the mp 'chips' are XLA virtual partitions of "
            "ONE host sharing the same cores, so sharded tok/s pays "
            "partitioning overhead with no extra silicon — the "
            "committed claims are the per-chip KV split and greedy "
            "parity, not the tok/s ratio. TPU rows rerun via bench.py "
            "when a TPU backend is reachable."),
    }


def _run_mesh(args):
    import jax

    from paddle_tpu.compile_cache import place_jax_cache
    place_jax_cache()
    mp = int(args.mesh_mp)
    if len(jax.devices()) < mp:
        sys.exit(f"--mesh needs {mp} devices for mp={mp} and jax found "
                 f"{len(jax.devices())}; run under XLA_FLAGS="
                 f"--xla_force_host_platform_device_count={mp} or on "
                 f"a host with that many chips")
    mesh = _phase_mesh(args)
    record = {
        "metric": "serving_tp_decode",
        "skipped": False,
        "value": mesh["sharded"]["decode_tok_s"],
        "unit": "tok/s",
        "vs_baseline": round(
            mesh["sharded"]["decode_tok_s"]
            / max(mesh["single_shard"]["decode_tok_s"], 1e-9), 3),
        "mesh": mesh,
        "config": {
            "mesh_mp": mp,
            "backend": jax.default_backend(),
            "host_cores": os.cpu_count(),
        },
    }
    emit_record(record, out=args.out)
    ok = mesh["greedy_parity"] and \
        abs(mesh["sharded"]["per_chip_kv_fraction"] - 1.0 / mp) < 1e-6
    return 0 if ok else 1


# ------------------------------------------------------------- tracing
def _phase_trace_accounting(args):
    """Fully-sampled in-process run: every counted request must leave
    exactly one span per router stage and one worker span, so the
    flight recorder's accounting is provably complete — not 'some
    spans showed up'."""
    from paddle_tpu.framework.flags import set_flags
    from paddle_tpu.observability import tracing
    from paddle_tpu.serving import fleet
    from paddle_tpu.serving.fleet import codec
    from paddle_tpu.serving.fleet.worker import (StubBackend,
                                                 ThreadReplicaFactory)

    buf = tracing.SpanBuffer(max_spans=200_000, max_per_trace=64)
    prev = tracing.set_default_buffer(buf)
    set_flags({"FLAGS_trace_sample_rate": 1.0})
    fac = ThreadReplicaFactory(
        lambda rid: StubBackend(device_ms=1.0, max_batch=8,
                                queue_capacity=512))
    reps = {i: fac(i).url() for i in range(2)}
    router = fleet.FleetRouter(replicas=reps, name="trace-acct",
                               start=False)
    app = fleet.RouterApp(router, host="127.0.0.1").start()
    opener = _opener()
    calls, k = int(args.trace_calls), 2
    body = codec.encode_batch([[np.ones((1, 16), np.float32)]] * k)
    try:
        if not router.wait_ready(2, timeout=30):
            raise RuntimeError("trace-accounting fleet not ready")
        completed = 0
        for _ in range(calls):
            req = urllib.request.Request(
                app.url("/submit_many"), data=body,
                headers={"Content-Type":
                         "application/x-paddle-fleet"})
            with opener.open(req, timeout=60) as resp:
                results = codec.decode_results(resp.read())
            completed += sum(1 for r in results
                             if not isinstance(r, BaseException))
        time.sleep(0.3)     # let completion threads finish recording
        spans = buf.snapshot()
        by_stage = {}
        for s in spans:
            by_stage[s["stage"]] = by_stage.get(s["stage"], 0) + 1
        expected = {"router": calls, "forward": calls,
                    "worker": calls}
        mismatches = {st: (by_stage.get(st, 0), want)
                      for st, want in expected.items()
                      if by_stage.get(st, 0) != want}
        return {
            "calls": calls, "requests_per_call": k,
            "requests_completed": completed,
            "span_counts": dict(sorted(by_stage.items())),
            "expected_per_stage": expected,
            "distinct_traces": len({s["trace_id"] for s in spans}),
            "accounting_consistent": not mismatches,
            "mismatches": mismatches,
            "exemplar_buckets": len(
                tracing.exemplars("paddle_fleet_request_ms")),
        }
    finally:
        set_flags({"FLAGS_trace_sample_rate": 0.0})
        tracing.set_default_buffer(prev)
        app.stop()
        router.shutdown()


def _phase_trace_overhead(args):
    """Aggregate QPS through real stub worker processes with tracing
    off vs head-sampled at 5% — the acceptance bound is < 5%
    regression. Sampling happens at router ingress, so the flag only
    needs flipping in THIS (router) process."""
    from paddle_tpu.framework.flags import set_flags
    from paddle_tpu.serving import fleet

    out = {}
    for label, rate in (("tracing_off", 0.0),
                        ("sampled_0.05", 0.05)):
        set_flags({"FLAGS_trace_sample_rate": rate})
        trials = []
        try:
            for _ in range(args.trials):
                fac = fleet.ProcessReplicaFactory(extra_args=[
                    "--stub",
                    "--stub-device-ms", str(args.device_ms),
                    "--stub-max-batch", str(args.stub_batch),
                    "--stub-capacity", str(args.stub_capacity)])
                sup = fleet.ReplicaSupervisor(
                    fac, args.replicas).start()
                router = fleet.FleetRouter(
                    supervisor=sup, name=f"trace-ovh-{label}",
                    health_interval_ms=200)
                try:
                    if not router.wait_ready(args.replicas,
                                             timeout=60):
                        raise RuntimeError("overhead fleet not ready")
                    app = fleet.RouterApp(router,
                                          host="127.0.0.1").start()
                    try:
                        trials.append(_run_load(
                            app.url(), k=args.load_k,
                            threads=args.load_threads,
                            procs=args.load_procs,
                            duration_s=args.duration))
                    finally:
                        app.stop()
                finally:
                    router.shutdown()
                    sup.stop()
        finally:
            set_flags({"FLAGS_trace_sample_rate": 0.0})
        best = sorted(trials,
                      key=lambda s: s["qps"])[len(trials) // 2]
        best["trials_qps"] = [round(s["qps"], 1) for s in trials]
        out[label] = best
    off = out["tracing_off"]["qps"]
    on = out["sampled_0.05"]["qps"]
    out["qps_ratio"] = round(on / off, 4) if off else 0.0
    out["regression_pct"] = round((1 - out["qps_ratio"]) * 100, 2)
    return out


def _run_trace(args):
    acct = _phase_trace_accounting(args)
    record = {
        "metric": "fleet_trace_span_accounting",
        "skipped": False,
        "value": float(acct["span_counts"].get("router", 0)),
        "unit": "spans",
        "vs_baseline": 1.0 if acct["accounting_consistent"] else 0.0,
        "accounting": acct,
        "config": {
            "replicas": args.replicas,
            "device_ms": args.device_ms,
            "backend": _WORKER_PLATFORM,
            "host_cores": os.cpu_count(),
        },
    }
    if not args.skip_overhead:
        record["overhead"] = _phase_trace_overhead(args)
    emit_record(record, out=args.out)
    ok = acct["accounting_consistent"]
    if "overhead" in record:
        # soft bound on a shared CI box: report, only fail on a
        # blowout far past the 5% acceptance target
        ok = ok and record["overhead"]["qps_ratio"] >= 0.85
    return 0 if ok else 1


# ------------------------------------------------------------- main
def main():
    args = _parse_args()
    if args.loadgen:
        print(json.dumps(_loadgen_main(json.loads(args.loadgen))))
        return 0
    if args.mesh:
        return _run_mesh(args)
    if args.trace:
        return _run_trace(args)
    return _run(args)


def _parse_args():
    ap = argparse.ArgumentParser()
    ap.add_argument("--replicas", type=int, default=4)
    ap.add_argument("--duration", type=float, default=6.0,
                    help="measured seconds per scaling trial")
    ap.add_argument("--trials", type=int, default=2)
    ap.add_argument("--device-ms", type=float, default=12.0,
                    help="emulated device time per stub batch")
    ap.add_argument("--stub-batch", type=int, default=8)
    ap.add_argument("--stub-capacity", type=int, default=64)
    ap.add_argument("--load-procs", type=int, default=2)
    ap.add_argument("--load-threads", type=int, default=4)
    ap.add_argument("--load-k", type=int, default=8,
                    help="requests per loadgen submit_many call")
    ap.add_argument("--scaleout-trials", type=int, default=3)
    ap.add_argument("--swap-threads", type=int, default=3)
    ap.add_argument("--skip-scaleout", action="store_true")
    ap.add_argument("--skip-swap", action="store_true")
    ap.add_argument("--mesh", action="store_true",
                    help="run the tensor-parallel serving slice "
                         "instead: sharded vs single-shard decode + "
                         "per-chip KV residency (BENCH_TP_r*.json)")
    ap.add_argument("--mesh-mp", type=int, default=8,
                    help="--mesh: tensor-parallel degree of the one "
                         "serving replica")
    ap.add_argument("--mesh-batch", type=int, default=8)
    ap.add_argument("--mesh-steps", type=int, default=48,
                    help="--mesh: timed greedy decode steps per "
                         "variant")
    ap.add_argument("--trace", action="store_true",
                    help="run the tracing phases instead: span-count "
                         "cross-check + sampled-QPS overhead")
    ap.add_argument("--trace-calls", type=int, default=150,
                    help="HTTP calls in the span-accounting phase")
    ap.add_argument("--skip-overhead", action="store_true",
                    help="--trace: skip the QPS overhead comparison")
    ap.add_argument("--loadgen", default=None,
                    help=argparse.SUPPRESS)   # internal: loadgen child
    ap.add_argument("--out", default=None,
                    help="also write the JSON record here")
    return ap.parse_args()


def _run(args):
    scaling = _phase_scaling(args)
    record = {
        "metric": "fleet_aggregate_qps",
        "skipped": False,
        "value": round(scaling[f"replicas_{args.replicas}"]["qps"],
                       1),
        "unit": "req/s",
        "vs_baseline": scaling["speedup"],   # N replicas over 1
        "scaling": scaling,
        "config": {
            "replicas": args.replicas,
            "device_ms": args.device_ms,
            "backend": _WORKER_PLATFORM,
            "host_cores": os.cpu_count(),
        },
    }
    from paddle_tpu.compile_cache import fresh_scratch_dir

    # the phases below build their own cold and shared caches inside it
    workdir = fresh_scratch_dir("bench_fleet")
    if not args.skip_scaleout:
        record["scale_out"], prefix_v1, cache = \
            _phase_scaleout(args, workdir)
        if not args.skip_swap:
            record["rolling_swap"] = _phase_swap(
                args, workdir, prefix_v1, cache)
    emit_record(record, out=args.out)
    ok = record["vs_baseline"] >= 3.0
    if "scale_out" in record:
        ok = ok and record["scale_out"]["warm_speedup"] >= 2.0
    if "rolling_swap" in record:
        ok = ok and record["rolling_swap"]["failed_requests"] == 0
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
