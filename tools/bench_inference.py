#!/usr/bin/env python
"""Inference serving benchmark: latency p50/p99 + QPS through the
Predictor surface, f32 vs bf16 (Config.set_precision).

Reference analog: Paddle Inference's benchmark harness over
AnalysisPredictor with convert_to_mixed_precision
(/root/reference/paddle/fluid/inference/analysis/passes/
convert_to_mixed_precision.cc). Runs on whatever backend jax selects
and names it in the record (CPU with --cpu or JAX_PLATFORMS=cpu).

Usage: python tools/bench_inference.py [--iters N] [--out PERF_INFER.json]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def _bench_predictor(pred, feeds, iters):
    import jax
    # warmup (compile) — not timed
    for _ in range(3):
        out = pred.run(feeds)
    lat = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = pred.run(feeds)  # noqa: F841 — includes host<->device copies
        lat.append(time.perf_counter() - t0)
    lat = np.asarray(lat) * 1e3
    row = {"p50_ms": float(np.percentile(lat, 50)),
           "p99_ms": float(np.percentile(lat, 99)),
           "qps": float(1e3 / lat.mean())}
    # device-compute view: pipeline iters dispatches, sync once at the
    # end — leaves the per-call host round trip out
    prog = getattr(pred._artifact, "_prog", None)
    if prog is not None:
        import jax.numpy as jnp
        # device-committed feeds: measure compute, not host copies
        feed = {k: jnp.asarray(v)
                for k, v in zip(pred._artifact.feed_names, feeds)}
        prog.run(feed)
        t0 = time.perf_counter()
        outs = [prog.run(feed) for _ in range(iters)]
        jax.block_until_ready(outs[-1])
        row["device_ms"] = (time.perf_counter() - t0) * 1e3 / iters
    return row


def bench_model(name, export_fn, feeds, iters):
    from paddle_tpu import inference
    from paddle_tpu.compile_cache import fresh_scratch_dir

    d = fresh_scratch_dir(os.path.join("bench_inference", name))
    prefix = os.path.join(d, name)
    export_fn(prefix)

    rows = {}
    f32_out = None
    for prec, ptype in (("float32", inference.PrecisionType.Float32),
                        ("bfloat16", inference.PrecisionType.Bfloat16)):
        cfg = inference.Config(prefix + ".pdmodel", prefix + ".pdiparams")
        cfg.set_precision(ptype)
        pred = inference.create_predictor(cfg)
        rows[prec] = _bench_predictor(pred, feeds, iters)
        out = pred.run(feeds)[0]
        if prec == "float32":
            f32_out = out
        else:
            scale = np.abs(f32_out).max() + 1e-9
            rows[prec]["max_rel_err_vs_f32"] = float(
                np.abs(out - f32_out).max() / scale)
    if "device_ms" in rows.get("bfloat16", {}):
        rows["speedup_device"] = rows["float32"]["device_ms"] / \
            rows["bfloat16"]["device_ms"]
    rows["speedup_p50"] = rows["float32"]["p50_ms"] / \
        rows["bfloat16"]["p50_ms"]
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=50)
    ap.add_argument("--out", default=None)
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--small", action="store_true",
                    help="tiny configs for a CPU smoke run")
    args = ap.parse_args()
    import jax

    from paddle_tpu.compile_cache import place_jax_cache
    place_jax_cache()
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")

    import paddle_tpu as paddle
    from paddle_tpu.models.ernie import (ErnieForSequenceClassification,
                                         ernie_base, ernie_tiny)
    from paddle_tpu.vision.models import resnet18, resnet50

    dev = jax.devices()[0]
    results = {"device": {"platform": dev.platform,
                          "kind": dev.device_kind,
                          "count": len(jax.devices())}}

    # ---- ERNIE classifier (small output: latency is not transfer-bound) --
    paddle.seed(0)
    cfg_e = ernie_tiny() if args.small else ernie_base(
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0)
    ernie = ErnieForSequenceClassification(cfg_e, num_classes=2)
    ernie.eval()
    bs, seq = (2, 16) if args.small else (8, 128)
    ids = np.random.RandomState(0).randint(
        1, cfg_e.vocab_size, (bs, seq)).astype("int64")

    def export_ernie(prefix):
        paddle.jit.save(
            ernie, prefix,
            input_spec=[paddle.static.InputSpec([bs, seq], "int64")])

    results[f"ernie_{'tiny' if args.small else 'base'}_b{bs}_s{seq}"] = \
        bench_model("ernie", export_ernie, [ids], args.iters)

    # ---- ResNet ----
    paddle.seed(0)
    rn = resnet18() if args.small else resnet50()
    rn.eval()
    rbs, rsz = (1, 64) if args.small else (8, 224)
    img = np.random.RandomState(0).randn(rbs, 3, rsz, rsz).astype(
        "float32")

    def export_resnet(prefix):
        paddle.jit.save(
            rn, prefix,
            input_spec=[paddle.static.InputSpec([rbs, 3, rsz, rsz],
                                                "float32")])

    results[f"resnet{'18' if args.small else '50'}_b{rbs}_{rsz}"] = \
        bench_model("resnet", export_resnet, [img], args.iters)

    print(json.dumps(results, indent=2))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=2)


if __name__ == "__main__":
    main()
