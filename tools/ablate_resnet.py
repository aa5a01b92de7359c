#!/usr/bin/env python
"""ResNet-50 whole-step ablation on the real chip (round-4 verdict item 4).

Same methodology as tools/ablate_13b.py: replace one component with
identity (or flip one knob), re-time the FULL training step, attribute
the delta. An isolated microbenchmark times its own dispatch, not the
step (round-2 lesson, PERF.md).

MFU accounting: ResNet-50 forward ~4.09 GFLOP @ 224x224 (conv+fc MACs*2),
train step ~3x forward = 12.3 GFLOP/img; v5e bf16 peak 197 TFLOP/s.

Usage: python tools/ablate_resnet.py [--variants base,b256,...] [--steps N]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

FWD_GFLOP = 4.09
TRAIN_GFLOP = 3.0 * FWD_GFLOP
PEAK_TFLOPS = 197.0


def _sync(out):
    import jax
    if hasattr(out, "numpy"):
        np.asarray(out.numpy())
    else:
        jax.block_until_ready(out)


def time_step(step_fn, feeds, steps, windows=3):
    """Best-of-windows images/s for a run_steps-style callable."""
    out = step_fn(steps, *feeds)
    _sync(out)
    best = None
    for _ in range(windows):
        t0 = time.perf_counter()
        out = step_fn(steps, *feeds)
        _sync(out)
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    return best / steps


def build_step(paddle, batch, amp, bn_identity=False, fwd_only=False,
               avgpool=False, stem_s4=False, nhwc=False):
    import paddle_tpu.nn as nn
    import paddle_tpu.nn.functional as F
    from paddle_tpu.jit.train_step import TrainStep
    from paddle_tpu.vision.models import resnet50

    paddle.seed(0)
    model = resnet50(num_classes=1000,
                     data_format="NHWC" if nhwc else "NCHW")
    if avgpool:
        # max-pool backward is select-and-scatter (TPU-slow); measure its
        # share by swapping in avg-pool (same shapes, cheap broadcast grad)
        model.maxpool = nn.AvgPool2D(kernel_size=3, stride=2, padding=1)
    if stem_s4:
        # fold the stem (7x7 s2 conv + 3x3 s2 maxpool) into one 7x7 s4
        # conv: same downstream shapes, no pool at all
        model.conv1 = nn.Conv2D(3, 64, 7, stride=4, padding=3,
                                bias_attr=False)
        model.maxpool = nn.Identity()
    if bn_identity:
        class _Id(nn.Layer):
            def forward(self, x):
                return x

        # walk _sub_layers (Layer.__setattr__ stores sublayers there, NOT
        # in __dict__) and replace every BatchNorm2D
        def walk(layer):
            subs = getattr(layer, "_sub_layers", {})
            for name, sub in list(subs.items()):
                if isinstance(sub, nn.BatchNorm2D):
                    subs[name] = _Id()
                elif isinstance(sub, nn.Layer):
                    walk(sub)
        walk(model)
        n_bn = sum(isinstance(m, nn.BatchNorm2D)
                   for m in model.sublayers())
        assert n_bn == 0, f"{n_bn} BatchNorm2D layers survived the swap"

    rng = np.random.RandomState(0)
    shape = (batch, 224, 224, 3) if nhwc else (batch, 3, 224, 224)
    x = paddle.to_tensor(rng.randn(*shape).astype("float32"))
    y = paddle.to_tensor(rng.randint(0, 1000, (batch,)).astype("int64"))

    if fwd_only:
        import jax
        from paddle_tpu.jit.functional import functional_call, state_arrays
        params, buffers = state_arrays(model)

        def fwd(params, buffers, xx):
            import jax as _jax
            from paddle_tpu.amp.auto_cast import auto_cast
            from paddle_tpu.core import autograd as ag

            def unwrap(o):
                return o._data if hasattr(o, "_data") else o
            with ag.no_grad():
                if amp:
                    with auto_cast(True, level=amp):
                        return unwrap(functional_call(
                            model, params, buffers, xx, training=False))
                return unwrap(functional_call(model, params, buffers, xx,
                                              training=False))

        jf = jax.jit(fwd)

        def run(steps, xx, yy):
            out = None
            for _ in range(steps):
                out = jf(params, buffers, xx._data)
            return out
        return run, (x, y)

    opt = paddle.optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                    parameters=model.parameters())
    step = TrainStep(model, lambda o, yy: F.cross_entropy(o, yy), opt,
                     amp_level=amp)

    def run(steps, xx, yy):
        return step.run_steps(steps, xx, yy)
    return run, (x, y)


def nhwc_conv_stack_ab(paddle, batch=64):
    """Whole-program NCHW vs NHWC A/B over a conv+bn+relu stack shaped
    like ResNet stage bodies (layout hypothesis check)."""
    import jax
    import jax.numpy as jnp

    rng = np.random.RandomState(0)
    chans = [(64, 64, 3, 1), (64, 128, 3, 2), (128, 128, 3, 1),
             (128, 256, 3, 2), (256, 256, 3, 1), (256, 512, 3, 2),
             (512, 512, 3, 1)]
    ws = [jnp.asarray((rng.randn(co, ci, k, k) * 0.05).astype(np.float32))
          for ci, co, k, _ in chans]

    def stack(fmt):
        dn = (("NCHW", "OIHW", "NCHW") if fmt == "NCHW"
              else ("NHWC", "OIHW", "NHWC"))

        def f(x, ws):
            h = x
            for w, (ci, co, k, s) in zip(ws, chans):
                h = jax.lax.conv_general_dilated(
                    h, w.astype(jnp.bfloat16), (s, s),
                    [(1, 1), (1, 1)], dimension_numbers=dn)
                h = jax.nn.relu(h)
            return jnp.sum(h.astype(jnp.float32))
        return jax.jit(f)

    res = {}
    for fmt in ("NCHW", "NHWC"):
        shape = (batch, 64, 56, 56) if fmt == "NCHW" else (batch, 56, 56, 64)
        x = jnp.asarray(rng.randn(*shape).astype(np.float32)).astype(
            jnp.bfloat16)
        f = stack(fmt)
        out = f(x, ws)
        jax.block_until_ready(out)
        best = None
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(10):
                out = f(x, ws)
            jax.block_until_ready(out)
            dt = (time.perf_counter() - t0) / 10
            best = dt if best is None else min(best, dt)
        res[fmt] = best * 1e3
    return res


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--variants", default="base,b256,f32,bn_id,fwd,"
                                          "avgpool,stem_s4")
    ap.add_argument("--layout-ab", action="store_true")
    args = ap.parse_args()

    import paddle_tpu as paddle

    results = {}
    variants = args.variants.split(",") if args.variants else []
    for v in variants:
        batch, amp, kw = 128, "O2", {}
        if v == "b256":
            batch = 256
        elif v == "b64":
            batch = 64
        elif v == "f32":
            amp = None
        elif v == "bn_id":
            kw = {"bn_identity": True}
        elif v == "fwd":
            kw = {"fwd_only": True}
        elif v == "avgpool":
            kw = {"avgpool": True}
        elif v == "stem_s4":
            kw = {"stem_s4": True}
        elif v == "nhwc":
            kw = {"nhwc": True}
        elif v == "nhwc_fwd":
            kw = {"nhwc": True, "fwd_only": True}
        step_fn, feeds = build_step(paddle, batch, amp, **kw)
        sec = time_step(step_fn, feeds, args.steps)
        gflop = FWD_GFLOP if v == "fwd" else TRAIN_GFLOP
        imgs = batch / sec
        mfu = imgs * gflop / 1e3 / PEAK_TFLOPS
        results[v] = {"batch": batch, "step_ms": round(sec * 1e3, 2),
                      "images_per_sec": round(imgs, 1),
                      "mfu_pct": round(100 * mfu, 1)}
        print(v, json.dumps(results[v]), flush=True)

    if args.layout_ab:
        results["conv_stack_layout_ms"] = nhwc_conv_stack_ab(paddle)
        print("layout_ab", json.dumps(results["conv_stack_layout_ms"]),
              flush=True)

    print(json.dumps(results, indent=2))


if __name__ == "__main__":
    main()
