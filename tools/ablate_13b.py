"""Step-time ablation for the 1.3B north-star config (PERF.md evidence).

Variants knock one component out of the compiled train step and re-time
the whole window, attributing step time end-to-end (an isolated
microbenchmark times its own dispatch, not the step — PERF.md).

Usage: python tools/ablate_13b.py [variant ...]
  base        unmodified step (flash attention, full remat)
  noattn      attention replaced by identity on q (removes both s^2
              matmuls + kernel overhead, keeps qkv/proj matmuls)
  dense       XLA softmax attention instead of the Pallas kernel
              (may OOM at s=2048; prints OOM if so)
  nodrop      recompute="none" (may OOM; quantifies the remat tax)
  dots        recompute="dots"
  b1          batch=1 (halves compute; checks batch scaling)
"""
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run(variant, steps=20, windows=2, batch=2, seq=2048):
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models import (GPTForCausalLM, GPTPretrainingCriterion,
                                   gpt3_1p3b)
    from paddle_tpu.ops import flash_attention as fa

    paddle.seed(0)
    recompute = "full"
    if variant == "nodrop":
        recompute = "none"
    elif variant == "dots":
        recompute = "dots"
    if variant == "b1":
        batch = 1
    cfg = gpt3_1p3b(stacked=True, recompute=recompute)
    if variant == "noattn":
        orig = fa.attention_bshd
        fa.attention_bshd = lambda q, k, v, causal=False, scale=None, \
            use_flash=True: q
    elif variant == "dense":
        orig = fa.preferred
        fa.preferred = lambda *a, **k: False

    model = GPTForCausalLM(cfg)
    crit = GPTPretrainingCriterion()
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters(),
                                 moment_dtype="bfloat16")
    step = TrainStep(model, lambda out, y: crit(out, y), opt, amp_level="O2")
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (batch, seq)).astype("int64"))
    try:
        loss = step.run_steps(steps, ids, ids)
        float(loss.numpy())
        best = None
        for _ in range(windows):
            t0 = time.perf_counter()
            loss = step.run_steps(steps, ids, ids)
            float(loss.numpy())
            dt = (time.perf_counter() - t0) / steps
            best = dt if best is None else min(best, dt)
        toks = batch * seq / best
        print(f"{variant:8s} step={best*1e3:8.1f} ms  {toks:9.0f} tok/s")
    except Exception as e:  # noqa: BLE001
        print(f"{variant:8s} FAILED: {type(e).__name__}: {str(e)[:200]}")
    finally:
        if variant == "noattn":
            fa.attention_bshd = orig
        elif variant == "dense":
            fa.preferred = orig


if __name__ == "__main__":
    variants = sys.argv[1:] or ["base", "noattn", "dots"]
    if len(variants) == 1:
        run(variants[0])
    else:
        # one subprocess per variant: a dead variant's buffers must not
        # poison the next one (the chip holds ~16 GB total)
        import subprocess
        for v in variants:
            subprocess.run([sys.executable, os.path.abspath(__file__), v])
