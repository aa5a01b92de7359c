"""Cold-start bench: process start -> first serving response, cold vs warm.

The acceptance gauge for the persistent compile cache (ISSUE 5): spawn
a fresh Python process that loads a saved artifact, warms its serving
lattice, and answers one request — once against an EMPTY
``FLAGS_compile_cache_dir`` (cold: every signature traces + XLA-
compiles) and once against the cache the cold runs populated (warm:
every signature deserializes an AOT executable; the warmup manifest
replays exactly the lattice the cold process served). Each trial
measures wall time from just before ``Popen`` to the first resolved
response INSIDE the child, so interpreter + import + framework start
all count — this is what a restart storm or autoscaler actually pays.

Every child also scrapes its own ``/metrics`` endpoint and cross-checks
the exposed ``paddle_compile_cache_{hits,misses}_total`` against the
in-process ``compile_cache.stats()`` accounting AND against the
expected hit/miss split for its mode; ``"consistent"`` in the output
is the AND of those checks across all trials.

    python tools/bench_coldstart.py [--trials 5] [--hidden 512]
        [--layers 4] [--max-batch 16] [--json]

Target (PERF.md / acceptance): warm median >= 2x faster than cold
median on CPU (median of >= 5 trials per side).
"""
import argparse
import json
import os
import shutil
import statistics
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


# --------------------------------------------------------------- child
def _scrape_compile_cache(port):
    """Parse paddle_compile_cache_{hits,misses}_total sums from the
    live /metrics page."""
    import urllib.request
    text = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=10).read().decode()
    out = {"hits": 0, "misses": 0}
    for line in text.splitlines():
        if line.startswith("#"):
            continue
        for kind in out:
            if line.startswith(f"paddle_compile_cache_{kind}_total"):
                out[kind] += int(float(line.rsplit(None, 1)[-1]))
    return out


def run_child(args):
    # FLAGS_compile_cache_dir arrives via the environment (flags read
    # env at definition time), so the cache is live from the first
    # import — exactly the deployment shape
    import numpy as np

    import paddle_tpu as paddle  # noqa: F401  (framework start counts)
    from paddle_tpu import compile_cache, inference, serving

    seq_buckets = [int(s) for s in args.seq_buckets.split(",")] \
        if args.seq_buckets else None
    pred = inference.create_predictor(inference.Config(args.prefix))
    srv = serving.InferenceServer(
        pred, max_batch_size=args.max_batch, name="coldstart",
        seq_buckets=seq_buckets, start=False, pipeline_depth=0,
        telemetry_port=0)
    manifest = srv.warmup_manifest
    if manifest is not None and len(manifest):
        mode = "warm"
        warmed = srv.warmup_from_manifest()
    else:
        # no recorded lattice yet: a genuinely cold start warms the
        # full theoretical bucket lattice, the pre-manifest discipline
        mode = "cold"
        warmed = srv.warmup()
    srv.start()
    rng = np.random.RandomState(0)

    def one_feed():
        if seq_buckets:
            return rng.randn(1, args.seq, 64).astype("float32")
        return rng.randn(1, 64).astype("float32")

    fut = srv.submit([one_feed()])
    fut.result(timeout=300)
    first_response_s = time.time() - args.t0

    # a short burst so the manifest records the lattice real traffic
    # lands on (two signatures: the rows->1 and rows->4 buckets)
    futs = srv.submit_many([[one_feed()] for _ in range(3)])
    for f in futs:
        f.result(timeout=300)

    stats = compile_cache.stats()
    scraped = _scrape_compile_cache(srv.telemetry.port)
    expected = {
        # cold: every persistent lookup missed (nothing on disk);
        # warm: manifest replay loads every signature, nothing compiles
        "cold": stats["misses"] > 0 and stats["hits"] == 0,
        "warm": stats["hits"] > 0 and stats["misses"] == 0,
    }[mode]
    consistent = (scraped["hits"] == stats["hits"]
                  and scraped["misses"] == stats["misses"] and expected)
    print(json.dumps({
        "mode": mode, "first_response_s": round(first_response_s, 3),
        "warmed": warmed, "accounting": {"hits": stats["hits"],
                                         "misses": stats["misses"]},
        "scraped": scraped, "consistent": consistent,
    }))
    srv.shutdown()
    return 0


# -------------------------------------------------------------- parent
def _save_model(prefix, hidden, layers, with_seq):
    # the children run on the CPU (``_trial``); so does this parent —
    # a parent on the default backend would hold a chip it never uses
    import jax
    jax.config.update("jax_platforms", "cpu")

    import paddle_tpu as paddle
    import paddle_tpu.nn as nn

    paddle.seed(0)
    blocks = [nn.Linear(64, hidden), nn.Tanh()]
    for _ in range(layers - 1):
        blocks += [nn.Linear(hidden, hidden), nn.Tanh()]
    blocks.append(nn.Linear(hidden, 16))
    net = nn.Sequential(*blocks).eval()
    # a dynamic sequence axis makes the serving lattice 2-D (batch x
    # seq buckets) — the transformer-serving shape discipline, and the
    # regime where full-lattice cold warmup visibly hurts
    shape = [None, None, 64] if with_seq else [None, 64]
    paddle.jit.save(net, prefix, input_spec=[
        paddle.static.InputSpec(shape, "float32", "x")],
        pdmodel_format=False)


def _trial(prefix, cache_dir, args):
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               FLAGS_compile_cache_dir=cache_dir,
               FLAGS_serving_telemetry_port="-1")
    t0 = time.time()
    r = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child",
         "--prefix", prefix, "--t0", repr(t0),
         "--max-batch", str(args.max_batch),
         "--seq-buckets", args.seq_buckets, "--seq", str(args.seq)],
        capture_output=True, text=True, env=env, timeout=900)
    if r.returncode != 0:
        raise RuntimeError(f"child failed:\n{r.stdout}\n{r.stderr}")
    return json.loads(r.stdout.strip().splitlines()[-1])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--trials", type=int, default=5,
                    help="trials per side (median reported)")
    ap.add_argument("--hidden", type=int, default=512)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--max-batch", type=int, default=16,
                    help="serving lattice breadth: pow2 buckets up to "
                         "this (5 signatures at 16)")
    ap.add_argument("--seq-buckets", default="32,64,128",
                    help="comma-separated sequence buckets (empty = no "
                         "sequence axis): the full lattice is batch x "
                         "seq buckets, what a cold server pre-compiles")
    ap.add_argument("--seq", type=int, default=48,
                    help="request sequence length (bucketed up)")
    ap.add_argument("--json", action="store_true",
                    help="suppress progress lines, print only the "
                         "final JSON")
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--prefix", help=argparse.SUPPRESS)
    ap.add_argument("--t0", type=float, help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child:
        return run_child(args)

    from paddle_tpu.compile_cache import fresh_scratch_dir

    # (the cold side wipes its cache per trial below)
    tmp = fresh_scratch_dir("bench_coldstart")
    prefix = os.path.join(tmp, "model")
    cache_dir = os.path.join(tmp, "cache")
    try:
        if not args.json:
            print(f"# saving model (hidden={args.hidden} "
                  f"layers={args.layers}) ...", file=sys.stderr)
        _save_model(prefix, args.hidden, args.layers,
                    with_seq=bool(args.seq_buckets))

        cold, warm, consistent = [], [], True
        for i in range(max(args.trials, 5)):
            shutil.rmtree(cache_dir, ignore_errors=True)
            res = _trial(prefix, cache_dir, args)
            assert res["mode"] == "cold", res
            consistent &= res["consistent"]
            cold.append(res["first_response_s"])
            if not args.json:
                print(f"# cold[{i}]: {res['first_response_s']:.2f}s "
                      f"{res['accounting']}", file=sys.stderr)
        # the LAST cold run's cache + manifest seed the warm side — the
        # restart-after-serving scenario
        for i in range(max(args.trials, 5)):
            res = _trial(prefix, cache_dir, args)
            assert res["mode"] == "warm", res
            consistent &= res["consistent"]
            warm.append(res["first_response_s"])
            if not args.json:
                print(f"# warm[{i}]: {res['first_response_s']:.2f}s "
                      f"{res['accounting']}", file=sys.stderr)

        cold_med = statistics.median(cold)
        warm_med = statistics.median(warm)
        speedup = cold_med / warm_med if warm_med else 0.0
        print(json.dumps({
            "metric": "serving_coldstart_speedup", "skipped": False,
            "value": round(speedup, 2), "unit": "x",
            "vs_baseline": round(speedup / 2.0, 4),
            "cold_median_s": round(cold_med, 3),
            "warm_median_s": round(warm_med, 3),
            "trials": max(args.trials, 5),
            "metrics_consistent": consistent,
            "pass": bool(speedup >= 2.0 and consistent),
        }))
        return 0 if (speedup >= 2.0 and consistent) else 1
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
