"""Benchmark: GPT causal-LM training throughput on one TPU chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline",
"platform", "device_kind", "device_count"}. The reference publishes no
in-repo numbers (SURVEY §6/BASELINE.md); the headline target is
MFU-based (>=45% on the GPT config), so vs_baseline is measured_MFU /
0.45, against the peak that ``xstats.chip_peaks`` lists for the
device's ``device_kind`` (an unlisted kind is an error). See PERF.md.

It measures on a TPU and nowhere else: with no chip it exits non-zero
with the backend's own error, or with the platform jax did find.
``--smoke`` is the CPU rehearsal of the same path at gpt-tiny size.

Methodology: K training steps run inside ONE compiled program
(TrainStep.run_steps — lax.scan over the step), the only host sync is the
final loss fetch, and the best of several windows is reported.

Usage: python bench.py [--smoke]
       [--config small|medium|large|1.3b|bert|resnet50]
       [--batch N] [--moment-dtype float32|bfloat16] [--amp O1|O2]
       [--recompute full|dots|none] [--steps K] [--windows W] [--no-amp]
"""
import argparse
import json
import sys
import time

import numpy as np


def _device_record():
    """The device every record names, as jax reports it."""
    import jax
    dev = jax.devices()[0]
    return {"platform": dev.platform, "device_kind": dev.device_kind,
            "device_count": len(jax.devices())}


def _peak_flops():
    import jax
    from paddle_tpu.observability import xstats
    return xstats.chip_peaks(jax.devices()[0].device_kind)["flops"]


def _bench_resnet(args, paddle, TrainStep):
    """BASELINE config 2: ResNet-50 training images/s (vs_baseline is
    images/s / 2000 — a round v5e single-chip waypoint, no published
    reference number exists). Default layout is NHWC, the MXU-native
    fast path (round-4 measured +11% over NCHW; the input pipeline
    produces channels-last directly — a real TPU training setup decodes
    HWC images anyway). ``--layout nchw`` re-measures the reference's
    layout. The extra "mfu" key uses 3x the 4.089 GFLOP/img fwd cost
    (fwd + 2x bwd, conv-dominated)."""
    import paddle_tpu.nn.functional as F
    from paddle_tpu.vision.models import resnet50

    layout = (args.layout or "nhwc").upper()
    model = resnet50(num_classes=1000, data_format=layout)
    opt = paddle.optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                    parameters=model.parameters())
    amp = None if args.no_amp else (args.amp or "O2")
    step = TrainStep(model, lambda o, y: F.cross_entropy(o, y), opt,
                     amp_level=amp)
    batch = args.batch or 128
    rng = np.random.RandomState(0)
    shape = (batch, 3, 224, 224) if layout == "NCHW" \
        else (batch, 224, 224, 3)
    x = paddle.to_tensor(rng.randn(*shape).astype("float32"))
    y = paddle.to_tensor(rng.randint(0, 1000, (batch,)).astype("int64"))
    K = max(args.steps, 1)
    loss = step.run_steps(K, x, y)
    assert np.isfinite(float(loss.numpy()))
    best = 0.0
    for _ in range(max(args.windows, 1)):
        t0 = time.perf_counter()
        loss = step.run_steps(K, x, y)
        float(loss.numpy())
        best = max(best, K * batch / (time.perf_counter() - t0))
    mfu = best * 3 * 4.089e9 / _peak_flops()
    print(json.dumps({"metric": "resnet50_train_images_per_sec",
                      "value": round(best, 1), "unit": "images/s",
                      "vs_baseline": round(best / 2000.0, 4),
                      "mfu": round(mfu, 4), "layout": layout,
                      **_device_record()}))


def _bench_bert(args, paddle, TrainStep):
    """BASELINE config 3: BERT-base MLM+NSP pretraining tokens/s
    (measured ~124,000 / 45.2% MFU at b=32 s=512 AMP O2, 40-step
    windows; MFU-based vs_baseline like the GPT configs)."""
    from paddle_tpu.models import (BertConfig, BertForPretraining,
                                   BertPretrainingCriterion)

    cfg = BertConfig(hidden_dropout_prob=0.0,
                     attention_probs_dropout_prob=0.0)
    model = BertForPretraining(cfg)
    crit = BertPretrainingCriterion(ignore_index=-1000)  # bench labels
    # are dense random ids, none ignored

    def loss_fn(out, labels, nsp_labels):
        return crit(out, labels, nsp_labels)

    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters(),
                                 moment_dtype=args.moment_dtype
                                 or "float32")
    amp = None if args.no_amp else (args.amp or "O2")
    step = TrainStep(model, loss_fn, opt, amp_level=amp)
    batch, seq = (args.batch or 32), 512
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (batch, seq)).astype("int64"))
    nsp = paddle.to_tensor(rng.randint(0, 2, (batch,)).astype("int64"))
    K = max(args.steps, 1)
    loss = step.run_steps(K, ids, ids, nsp, n_inputs=1)
    assert np.isfinite(float(loss.numpy()))
    best = 0.0
    for _ in range(max(args.windows, 1)):
        t0 = time.perf_counter()
        loss = step.run_steps(K, ids, ids, nsp, n_inputs=1)
        float(loss.numpy())
        best = max(best, K * batch * seq / (time.perf_counter() - t0))
    n = model.num_params()
    fpt = 6 * n + 12 * cfg.num_layers * cfg.hidden_size * seq
    print(json.dumps({"metric": "bert_base_pretrain_tokens_per_sec",
                      "value": round(best, 1), "unit": "tokens/s",
                      "vs_baseline": round(
                          best * fpt / _peak_flops() / 0.45, 4),
                      **_device_record()}))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny config on CPU for CI/verify")
    ap.add_argument("--config", default="1.3b",
                    choices=["small", "medium", "large", "1.3b",
                             "resnet50", "bert"],
                    help="default is the BASELINE north-star (GPT-3 1.3B "
                         "b=2 s=2048 single chip, measured 49.9%% MFU); "
                         "medium is the short-seq headline (51.8%%)")
    ap.add_argument("--batch", type=int, default=0,
                    help="override batch size (0 = config default)")
    ap.add_argument("--seq", type=int, default=0,
                    help="override sequence length (gpt configs; 0 = "
                         "config default). Long-context rows: "
                         "--config medium --seq 4096 --batch 2")
    ap.add_argument("--moment-dtype", default=None,
                    choices=["float32", "bfloat16"])
    ap.add_argument("--layout", default=None, choices=["nhwc", "nchw"],
                    help="resnet50 activation layout (default nhwc, the "
                         "MXU-native fast path)")
    ap.add_argument("--recompute", default=None,
                    choices=["full", "dots", "attn", "none"],
                    help="stacked-decoder recompute policy (large and "
                         "1.3b configs; their default 'full' is the only "
                         "policy that fits HBM)")
    ap.add_argument("--steps", type=int, default=40,
                    help="steps per compiled window (one dispatch and "
                         "one host sync per window)")
    ap.add_argument("--windows", type=int, default=3)
    ap.add_argument("--input-pipeline", action="store_true",
                    help="feed every step from io.DataLoader (shm_ring "
                         "workers) instead of one resident synthetic "
                         "batch — measures the real ingestion path "
                         "(PERF.md 'with input pipeline' row)")
    ap.add_argument("--workers", type=int, default=2,
                    help="DataLoader workers for --input-pipeline")
    ap.add_argument("--amp", default="O2", choices=["O1", "O2"],
                    help="autocast level (default O2 pure-bf16 with f32 "
                         "master params: measured 43.0%% vs O1's 40.8%% "
                         "MFU at gpt2-medium, identical loss trajectory)")
    ap.add_argument("--no-amp", action="store_true",
                    help="disable bf16 autocast entirely")
    args = ap.parse_args()

    import jax

    from paddle_tpu.compile_cache import place_jax_cache
    place_jax_cache()
    if args.smoke:
        jax.config.update("jax_platforms", "cpu")
    elif jax.devices()[0].platform != "tpu":
        # no chip is a failure, never a CPU number under a device
        # metric's name; a TPU that fails to start raised above
        sys.exit(f"bench.py measures on a TPU and jax found "
                 f"{jax.devices()[0].platform!r}; --smoke is the CPU "
                 f"rehearsal")
    return _run(args)


# hand-vs-cost-model agreement bound: divergence beyond this from BOTH
# analytic candidates (plain 6N, full-remat ~8N) fails the record
_COST_AGREE_TOL = 0.15


def _train_cost_model_check(batch, seq, n_params, attn_flops):
    """XLA cost-model FLOPs of the train executable that actually ran
    (xstats registry) vs the hand formula. Returns the record section;
    ``available`` is False when no analysis could be read (the bench
    then reports the hand number alone instead of failing)."""
    out = {"available": False}
    try:
        from paddle_tpu.observability import xstats
        reg = xstats.default_exec_registry()
        ents = [e for e in reg.entries()
                if e.site == "train_step" and e.dispatches]
        if not ents:
            return out
        ent = max(ents, key=lambda e: e.last_dispatch_unix_ms or 0)
        ana = reg.ensure_analysis(ent)
        if not ana or not ana.get("flops"):
            out["error"] = ent.analysis_error
            return out
        # a run_steps window executable wraps K steps in a lax.scan;
        # XLA's HLO cost analysis counts the while BODY once (it does
        # not multiply by trip count), so the per-token normalization
        # tries both readings and keeps the closer one — either way a
        # real model-shape drift moves the FLOPs far beyond the bound
        tag = ent.signature[0][1] if ent.signature else "tag:single"
        steps = int(tag.rsplit(":", 1)[1]) if "multi" in tag else 1
        per_token = {"body_once": ana["flops"] / (batch * seq),
                     "times_steps":
                     ana["flops"] / (steps * batch * seq)}
        hand = 6 * n_params + attn_flops
        # full remat re-runs the forward inside the backward: ~one
        # extra model forward (2N) and a second attention pass
        hand_remat = 8 * n_params + 2 * attn_flops
        ratios = {f"{k}_vs_{h}": cm / hv
                  for k, cm in per_token.items()
                  for h, hv in (("plain", hand), ("remat", hand_remat))}
        best_key = min(ratios, key=lambda k: abs(ratios[k] - 1.0))
        out.update({
            "available": True,
            "flops_per_token": round(
                per_token["body_once" if "body_once" in best_key
                          else "times_steps"], 1),
            "hand_flops_per_token": float(hand),
            "hand_remat_flops_per_token": float(hand_remat),
            "ratios": {k: round(v, 4) for k, v in ratios.items()},
            "best": best_key,
            "agrees": abs(ratios[best_key] - 1.0) <= _COST_AGREE_TOL,
            "exec_flops": ana["flops"],
            "window_steps": steps,
        })
    except Exception as e:  # noqa: BLE001 - the cross-check must not
        out["error"] = f"{type(e).__name__}: {e}"  # sink a bench run
    return out


def _run(args):
    import paddle_tpu as paddle
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models import (GPTForCausalLM, GPTPretrainingCriterion,
                                   gpt_tiny, gpt2_large, gpt2_medium,
                                   gpt2_small, gpt3_1p3b)

    paddle.seed(0)
    if args.config in ("resnet50", "bert"):
        if args.smoke:
            raise SystemExit(
                f"--smoke runs the gpt-tiny CPU config only; run "
                f"--config {args.config} without --smoke (real chip)")
        if args.config == "resnet50":
            return _bench_resnet(args, paddle, TrainStep)
        return _bench_bert(args, paddle, TrainStep)
    if args.smoke:
        cfg = gpt_tiny(use_flash_attention=False)
        batch, seq = 2, 64
        metric = "gpt_tiny_smoke_tokens_per_sec"
    elif args.config == "small":
        cfg = gpt2_small(max_seq_len=512)
        batch, seq = 8, 512
        metric = "gpt2s_train_tokens_per_sec"
    elif args.config == "large":
        # 774M: stacked scan decoder; at b=8 s=1024 only full recompute +
        # bf16 optimizer moments fit the 15.75 GB chip ("dots" saves ~7.5GB
        # of matmul outputs across 36 layers and OOMs). Measured 25.5% MFU
        # vs medium's 30.6% — the +33% recompute FLOPs outweigh the better
        # H=1280 matmul shapes, which is why medium stays the default.
        cfg = gpt2_large(stacked=True,
                         recompute=args.recompute or "full")
        batch, seq = 8, 1024
        metric = "gpt2l_train_tokens_per_sec"
        if args.moment_dtype is None:
            args.moment_dtype = "bfloat16"
    elif args.config == "1.3b":
        # BASELINE north-star model on ONE chip: stacked scan + full
        # remat + bf16 moments + flash attention (s>=2048) fit 1.3B in
        # 15.75 GB; measured 7,313 tok/s (33.8% MFU) b=2 s=2048
        cfg = gpt3_1p3b(stacked=True, recompute=args.recompute or "full")
        batch, seq = 2, 2048
        metric = "gpt3_1p3b_train_tokens_per_sec"
        if args.moment_dtype is None:
            args.moment_dtype = "bfloat16"
    else:
        cfg = gpt2_medium(max_seq_len=512)
        batch, seq = 16, 512
        metric = "gpt2m_train_tokens_per_sec"
    if args.batch:
        batch = args.batch
    if args.seq and not args.smoke:
        seq = args.seq
        # rebuild the config with a matching context window (and stacked
        # full-remat for the long-context rows, which need O(S) memory)
        base = {"small": gpt2_small, "medium": gpt2_medium,
                "large": gpt2_large, "1.3b": gpt3_1p3b}.get(args.config)
        if base is not None:
            kw = dict(max_seq_len=seq)
            if seq >= 4096 or args.config in ("large", "1.3b"):
                kw.update(stacked=True, recompute=args.recompute or "full")
                if args.moment_dtype is None:
                    args.moment_dtype = "bfloat16"
            cfg = base(**kw)
            metric = f"{metric[:metric.index('_train')]}_s{seq}" \
                     "_train_tokens_per_sec"

    from paddle_tpu.framework.flags import flag_value
    if not args.smoke and getattr(cfg, "use_flash_attention", True) and \
            seq >= int(flag_value("FLAGS_flash_min_seqlen")):
        # flash kicks in at FLAGS_flash_min_seqlen (2048): autotune the
        # block sizes for THIS attention shape eagerly (fwd+bwd timing,
        # persisted) — the traced TrainStep picks the winner up through
        # the "mha_step" cache instead of the static 512x1024 default
        from paddle_tpu.ops import flash_attention
        # key the tuning on the dtype attention will actually run in
        # (bf16 under AMP autocast, f32 under --no-amp) or the cache
        # entry can never be hit by the traced dispatch
        tune_dtype = "float32" if args.no_amp else "bfloat16"
        picked = flash_attention.pretune(
            batch, cfg.num_heads, seq, cfg.hidden_size // cfg.num_heads,
            dtype=tune_dtype)
        if picked:
            print(f"# flash pretune s={seq}: block_q={picked[0]} "
                  f"block_k={picked[1]}", file=sys.stderr)

    model = GPTForCausalLM(cfg)
    crit = GPTPretrainingCriterion()
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters(),
                                 moment_dtype=args.moment_dtype or "float32")
    amp_level = None if (args.smoke or args.no_amp) else args.amp
    step = TrainStep(model, lambda out, y: crit(out, y), opt,
                     amp_level=amp_level)

    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (batch, seq)).astype("int64"))

    K = max(args.steps, 1)
    if args.input_pipeline:
        # real ingestion: every step's batch comes through io.DataLoader
        # (multiprocess workers + shm_ring transport). Steps dispatch
        # asynchronously; the loss fetch at window end is the only sync,
        # so host-side loading overlaps device compute.
        import paddle_tpu.io as io

        class TokenDataset(io.Dataset):
            def __init__(self, n):
                self.n = n

            def __len__(self):
                return self.n

            def __getitem__(self, i):
                r = np.random.RandomState(i)
                return r.randint(0, cfg.vocab_size, (seq,)).astype("int64")

        n_batches = K * (args.windows + 1) + 2
        loader = io.DataLoader(TokenDataset(n_batches * batch),
                               batch_size=batch, shuffle=False,
                               num_workers=args.workers, drop_last=True)
        it = iter(loader)

        def one_window():
            loss = None
            for _ in range(K):
                b = next(it)
                if isinstance(b, (list, tuple)):
                    b = b[0]
                loss = step(b, b)
            return float(loss.numpy())     # single sync per window

        final = one_window()               # compile + warm
        best = 0.0
        for _ in range(max(args.windows, 1)):
            t0 = time.perf_counter()
            final = one_window()
            dt = time.perf_counter() - t0
            best = max(best, K * batch * seq / dt)
        metric += "_pipelined"
    else:
        loss = step.run_steps(K, ids, ids)     # compile + warm window
        final = float(loss.numpy())

        best = 0.0
        for _ in range(max(args.windows, 1)):
            t0 = time.perf_counter()
            loss = step.run_steps(K, ids, ids)
            final = float(loss.numpy())        # the only sync point
            dt = time.perf_counter() - t0
            best = max(best, K * batch * seq / dt)

    n_params = model.num_params()
    # 6*N FLOPs/token (fwd+bwd) + attention term 12*L*H*S per token
    attn_flops = 12 * cfg.num_layers * cfg.hidden_size * seq
    flops_per_token = 6 * n_params + attn_flops
    assert np.isfinite(final), "loss diverged"
    # the smoke has no MFU: a CPU has no entry in the peaks table
    vs_baseline = 1.0 if args.smoke else round(
        best * flops_per_token / _peak_flops() / 0.45, 4)

    # cost-model cross-check: the XLA-counted FLOPs of the executable
    # that actually ran (xstats registry) against the hand formula the
    # MFU headline is derived from — silent model-shape drift in the
    # hand 6ND would show up here as divergence. Full-remat configs
    # legitimately execute ~an extra forward (8N-ish), so agreement is
    # judged against the closer of the two analytic candidates.
    cost_model = _train_cost_model_check(batch, seq, n_params,
                                         attn_flops)

    print(json.dumps({
        "metric": metric,
        "value": round(best, 1),
        "unit": "tokens/s",
        "vs_baseline": vs_baseline,
        "cost_model": cost_model,
        **_device_record(),
    }))
    if cost_model.get("available") and not cost_model["agrees"]:
        print(f"# FAIL: cost-model FLOPs/token "
              f"{cost_model['flops_per_token']:.3e} diverges "
              f">{int(_COST_AGREE_TOL * 100)}% from the hand formula "
              f"({cost_model['hand_flops_per_token']:.3e} plain / "
              f"{cost_model['hand_remat_flops_per_token']:.3e} remat)",
              file=sys.stderr)
        return 1


if __name__ == "__main__":
    sys.exit(main())
