"""Decode-serving benchmark: paged-KV continuous batching vs the
full-window generate() baseline.

Measures, on the same model/prompts/token budget:

- **baseline**: ``HybridParallelInferenceHelper._full_window_generate``
  — the pre-PR-7 path that re-runs the whole O(T^2 L) padded-window
  forward for every emitted token (one compiled shape, greedy);
- **engine**: ``GenerationServer`` — prefill once per prompt, then
  fixed-shape ``[max_batch, 1]`` cached decode steps with continuous
  batching, tokens streamed per request.

Reports aggregate decode tokens/s for both, the speedup ratio, p99
inter-token latency (engine: measured between streamed tokens;
baseline: window time / tokens, the lockstep equivalent), and a
cached-vs-uncached logits equivalence probe. One JSON line to stdout;
``--out`` also writes the committed BENCH_DECODE_r*.json record.

Two further modes share ``_bench_common`` plumbing and emit ONE
combined ``decode_prefix_spec`` record (BENCH_PREFIX_r*.json):

- ``--prefix``: hot-vs-cold time-to-first-token with a shared
  256-token preamble. Cold = empty prefix cache, full-prompt prefill;
  hot = radix hit, chunked suffix-only prefill. Paired per trial on
  one warmed engine (``clear_prefix_cache`` between pairs).
- ``--spec``: speculative decoding single-stream throughput. The
  draft is a small GPT; the TARGET is the draft plus zero-residual
  tail layers (bit-identical logits, ~layers-ratio more compute), so
  the mode measures the draft/verify machinery at its acceptance
  ceiling with the rate reported honestly alongside; greedy parity
  vs the non-speculative engine is asserted, not assumed.

A third mode, ``--kernels``, runs PAIRED serving trials over the
fused-kernel / quantized-KV matrix (``GenerationServer(use_pallas=)``
x ``FLAGS_decode_kv_dtype``) on ONE model: decode tok/s, TTFT and p99
inter-token latency per variant, the int8 page-capacity ratio vs f32
(the pool-sizing claim: same byte budget, ~2x resident sequences),
greedy-parity across every variant's streams, and a clean page-leak
check. Emits one ``decode_kernels`` record (BENCH_KERNELS_r*.json);
on a CPU host the Pallas variants run in interpret mode, so their
timings gate parity/capacity invariants, not kernel speed.

Usage: JAX_PLATFORMS=cpu python tools/bench_decode.py
       [--batch 8] [--prompt-len 12] [--max-new 48] [--trials 3]
       [--requests N] [--prefix] [--spec] [--spec-k 4] [--kernels]
       [--out BENCH_DECODE_rNN.json | BENCH_PREFIX_rNN.json |
        BENCH_KERNELS_rNN.json]
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from tools._bench_common import emit_record  # noqa: E402


def _median(xs):
    xs = sorted(xs)
    return xs[len(xs) // 2]


def main():
    return _run(_parse_args())


def _parse_args():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=8,
                    help="concurrent prompts (= engine max_batch)")
    ap.add_argument("--requests", type=int, default=0,
                    help="total requests per engine trial (0 = 2x "
                         "batch, exercising join/evict churn)")
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=48)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--trials", type=int, default=3)
    ap.add_argument("--prefix", action="store_true",
                    help="hot-vs-cold TTFT with a shared preamble")
    ap.add_argument("--spec", action="store_true",
                    help="speculative decoding single-stream tok/s")
    ap.add_argument("--spec-k", type=int, default=6,
                    help="draft tokens proposed per verify step")
    ap.add_argument("--kernels", action="store_true",
                    help="fused-kernel/quantized-KV variant matrix")
    ap.add_argument("--preamble", type=int, default=256,
                    help="shared-prefix preamble length (--prefix)")
    ap.add_argument("--out", default=None,
                    help="also write the JSON record here")
    return ap.parse_args()


def _ttft(srv, prompt, max_new):
    """Submit one request; wall-clock to the FIRST streamed token."""
    t0 = time.perf_counter()
    fut = srv.submit_generate(prompt, max_new_tokens=max_new)
    for _ in fut:
        break
    ttft = (time.perf_counter() - t0) * 1e3
    fut.result(timeout=600)
    return ttft


def _bench_prefix(args):
    """Hot-vs-cold TTFT with a shared preamble: page-granular radix
    hits turn the preamble prefill into block-table rows, leaving only
    the unique suffix's chunked prefill on the critical path."""
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForCausalLM, gpt_tiny
    from paddle_tpu.serving.generation import GenerationServer

    paddle.seed(0)
    pre_len, suf_len, max_new = args.preamble, 8, 4
    cfg = gpt_tiny(use_flash_attention=False, hidden_size=128,
                   num_layers=4, num_heads=4,
                   max_seq_len=2 * args.preamble)
    model = GPTForCausalLM(cfg)
    model.eval()
    rng = np.random.RandomState(0)
    preamble = list(rng.randint(0, cfg.vocab_size, pre_len))
    srv = GenerationServer(model, max_batch=2, page_size=args.page_size,
                           name="bench-prefix", start=False)
    # warm every signature BOTH paths dispatch, so TTFT measures
    # prefill compute, not compilation
    full_bucket = srv.policy.bucket_seq(pre_len + suf_len)
    suffix_bucket = srv.policy.bucket_seq(suf_len)
    srv.warmup(seq_buckets=sorted({full_bucket, suffix_bucket}),
               batch_buckets=[1])
    srv.start()
    cold_ms, hot_ms, reused = [], [], 0
    for trial in range(args.trials):
        srv.clear_prefix_cache()
        suffix = list(rng.randint(0, cfg.vocab_size, suf_len))
        cold_ms.append(_ttft(srv, preamble + suffix, max_new))
        suffix = list(rng.randint(0, cfg.vocab_size, suf_len))
        hot_ms.append(_ttft(srv, preamble + suffix, max_new))
    snap = srv.metrics_snapshot()
    reused = snap["prefix"]["tokens_reused"]
    assert snap["prefix"]["hits"] == args.trials, snap["prefix"]
    assert snap["kv_leak_check"]["ok"]
    srv.shutdown()
    cold, hot = _median(cold_ms), _median(hot_ms)
    return {
        "cold_ttft_ms": round(cold, 3),
        "hot_ttft_ms": round(hot, 3),
        "ttft_speedup": round(cold / hot, 3) if hot else 0.0,
        "preamble_tokens": pre_len,
        "suffix_tokens": suf_len,
        "tokens_reused_total": int(reused),
        "trials": args.trials,
        "model": {"hidden": cfg.hidden_size,
                  "layers": cfg.num_layers,
                  "max_seq_len": cfg.max_seq_len},
    }


def _spec_model_pair(layers_draft=2, layers_extra=10):
    """(draft, target) with BIT-IDENTICAL logits: the target is the
    draft plus ``layers_extra`` residual blocks whose output
    projections are zeroed (each contributes exactly 0 through the
    residual stream) — honest target-sized compute at the acceptance
    ceiling."""
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForCausalLM, gpt_tiny

    paddle.seed(0)
    dcfg = gpt_tiny(use_flash_attention=False, num_layers=layers_draft)
    draft = GPTForCausalLM(dcfg)
    draft.eval()
    paddle.seed(1)
    tcfg = gpt_tiny(use_flash_attention=False,
                    num_layers=layers_draft + layers_extra)
    target = GPTForCausalLM(tcfg)
    target.eval()
    shared = dict(draft.named_parameters())
    for name, p in target.named_parameters():
        src = shared.get(name)
        if src is not None and tuple(src.shape) == tuple(p.shape):
            p.set_value(np.asarray(src.numpy()))
    for layer in list(target.gpt.layers)[layers_draft:]:
        for par in (layer.attn.out_proj.weight,
                    layer.attn.out_proj.bias,
                    layer.mlp.fc_out.weight, layer.mlp.fc_out.bias):
            par.set_value(np.zeros(par.shape, dtype=par.numpy().dtype))
    return draft, target, tcfg


def _bench_spec(args):
    """Single-stream tok/s, speculative vs plain, same target model.
    Greedy parity is ASSERTED (the accept rule guarantees it); the
    acceptance rate rides the record."""
    from paddle_tpu.serving.generation import GenerationServer

    draft, target, cfg = _spec_model_pair()
    rng = np.random.RandomState(0)
    prompt = list(rng.randint(0, cfg.vocab_size, args.prompt_len))
    max_new = args.max_new

    def run(srv):
        srv.warmup(seq_buckets=[srv.policy.bucket_seq(len(prompt))],
                   batch_buckets=[1])
        srv.start()
        streams, tps = [], []
        for _ in range(args.trials):
            srv.clear_prefix_cache()
            t0 = time.perf_counter()
            streams.append(srv.generate(prompt, max_new_tokens=max_new))
            tps.append(max_new / (time.perf_counter() - t0))
        snap = srv.metrics_snapshot()
        srv.shutdown()
        return streams, _median(tps), snap

    base_srv = GenerationServer(target, max_batch=2,
                                page_size=args.page_size,
                                name="bench-spec-base", start=False)
    base_streams, base_tps, _ = run(base_srv)
    spec_srv = GenerationServer(target, max_batch=2,
                                page_size=args.page_size,
                                draft_model=draft, spec_k=args.spec_k,
                                name="bench-spec", start=False)
    spec_streams, spec_tps, snap = run(spec_srv)
    parity = all(s == b for s, b in zip(spec_streams, base_streams))
    spec = snap["spec"]
    steps = snap["step_ms"]["decode"]["count"]
    return {
        "base_tok_s": round(base_tps, 1),
        "spec_tok_s": round(spec_tps, 1),
        "speedup": round(spec_tps / base_tps, 3) if base_tps else 0.0,
        "greedy_parity": bool(parity),
        "acceptance_rate": round(spec["acceptance_rate"], 4),
        "accepted_tokens_per_step": round(
            spec["accepted"] / max(1, steps), 3),
        "spec_k": args.spec_k,
        "max_new_tokens": max_new,
        "trials": args.trials,
        "model": {"draft_layers": 2,
                  "target_layers": cfg.num_layers,
                  "hidden": cfg.hidden_size},
    }


# fused-kernel / quantized-KV variant matrix: name -> (kv_dtype,
# pallas routing). f32+reference is the parity baseline; int8_pallas
# is the serving configuration the capacity claim is about.
_KERNEL_VARIANTS = [
    ("f32", "", False),
    ("f32_pallas", "", True),
    ("int8", "int8", False),
    ("int8_pallas", "int8", True),
]


def _bench_kernels(args):
    """Paired trials across the kernel/quantization matrix on one
    model and one prompt set. Greedy streams must be IDENTICAL across
    all four variants (int8 is greedy-stable on this model; the 0.05
    logits envelope is tested in tests/test_pallas_paged.py) and the
    int8 pool must hold ~2x the pages of the f32 pool under the same
    byte budget — those are the gated invariants; the per-variant
    timings ride along as diagnostics (interpret-mode Pallas on CPU
    is not a speed measurement)."""
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.framework import flags as F
    from paddle_tpu.models import GPTForCausalLM, gpt_tiny
    from paddle_tpu.serving.generation import GenerationServer

    paddle.seed(0)
    cfg = gpt_tiny(use_flash_attention=False)
    model = GPTForCausalLM(cfg)
    model.eval()
    b, plen, new = args.batch, args.prompt_len, args.max_new
    rng = np.random.RandomState(0)
    prompts = [list(rng.randint(0, cfg.vocab_size, plen))
               for _ in range(b)]

    variants, streams = {}, {}
    saved = F.get_flags(["FLAGS_decode_kv_dtype"])
    try:
        for name, kd, up in _KERNEL_VARIANTS:
            F.set_flags({"FLAGS_decode_kv_dtype": kd})
            srv = GenerationServer(model, max_batch=b,
                                   page_size=args.page_size,
                                   name=f"bench-kern-{name}",
                                   use_pallas=up, start=False)
            srv.warmup(seq_buckets=[srv.policy.bucket_seq(plen)])
            srv.start()
            ttfts = [_ttft(srv, prompts[0], new)
                     for _ in range(args.trials)]
            tps, runs = [], []
            for _ in range(args.trials):
                t0 = time.perf_counter()
                futs = [srv.submit_generate(p, max_new_tokens=new)
                        for p in prompts]
                done = [list(f.result(timeout=600)) for f in futs]
                tps.append(sum(len(d) for d in done)
                           / (time.perf_counter() - t0))
                runs.append(done)
            snap = srv.metrics_snapshot()
            chk = srv.kv.leak_check()
            streams[name] = runs
            variants[name] = {
                "kv_dtype": kd or "float32",
                "use_pallas": up,
                "decode_tok_s": round(_median(tps), 1),
                "ttft_ms": round(_median(ttfts), 3),
                "p99_inter_token_ms": round(
                    snap["inter_token_ms"].get("p99", 0.0), 3),
                "capacity_pages": srv.kv.capacity,
                "capacity_factor": srv.kv_capacity_factor,
                "pool_bytes": srv.kv.pool_bytes(),
                "leak_ok": bool(chk["ok"]) and chk["leaked"] == 0,
            }
            srv.shutdown()
    finally:
        F.set_flags(saved)

    base = variants["f32"]
    parity = all(streams[n] == streams["f32"] for n in streams)
    ref, quant = base, variants["int8_pallas"]
    return {
        "metric": "decode_kernels",
        "skipped": False,
        "value": quant["decode_tok_s"],
        "unit": "tokens/s",
        "vs_baseline": round(
            quant["decode_tok_s"] / ref["decode_tok_s"], 3)
            if ref["decode_tok_s"] else 0.0,
        "greedy_parity": bool(parity),
        "leaks_clean": all(v["leak_ok"] for v in variants.values()),
        "capacity_ratio": round(
            quant["capacity_pages"] / ref["capacity_pages"], 3),
        "pool_bytes_saved_pct": round(
            100.0 * (1 - quant["pool_bytes"] / ref["pool_bytes"]), 1),
        "variants": variants,
        "config": {"model": "gpt_tiny", "batch": b,
                   "prompt_len": plen, "max_new_tokens": new,
                   "page_size": args.page_size, "trials": args.trials,
                   "backend": jax.default_backend(),
                   "pallas_interpret":
                       jax.default_backend() == "cpu"},
    }


_COST_AGREE_TOL = 0.15


def _decode_cost_model_check(model, cfg, batch):
    """XLA cost-model FLOPs of the fixed-shape decode executable that
    ran (xstats registry, site generate_decode) against the hand
    forward-only estimate: ``batch x (2N + 4·L·H·T)`` — every lane of
    the fixed-shape step computes, and decode attention gathers the
    full T-slot window through the block table. Divergence beyond
    ±15% flags silent model-shape drift in the hand formula."""
    out = {"available": False}
    try:
        from paddle_tpu.observability import xstats
        reg = xstats.default_exec_registry()
        ents = [e for e in reg.entries()
                if e.site == "generate_decode" and e.dispatches]
        if not ents:
            return out
        ent = max(ents, key=lambda e: e.last_dispatch_unix_ms or 0)
        ana = reg.ensure_analysis(ent)
        if not ana or not ana.get("flops"):
            out["error"] = ent.analysis_error
            return out
        n_params = model.num_params()
        t_slots = cfg.max_seq_len
        hand = batch * (2 * n_params
                        + 4 * cfg.num_layers * cfg.hidden_size
                        * t_slots)
        ratio = ana["flops"] / hand
        out.update({
            "available": True,
            "exec_flops_per_step": ana["flops"],
            "hand_flops_per_step": float(hand),
            "ratio": round(ratio, 4),
            "agrees": abs(ratio - 1.0) <= _COST_AGREE_TOL,
        })
    except Exception as e:  # noqa: BLE001 - the cross-check must not
        out["error"] = f"{type(e).__name__}: {e}"  # sink a bench run
    return out


def _run(args):
    import jax

    if jax.default_backend() == "cpu":
        jax.config.update("jax_platforms", "cpu")

    if args.kernels:
        record = _bench_kernels(args)
        emit_record(record, out=args.out)
        if not (record["greedy_parity"] and record["leaks_clean"]):
            print("# FAIL: kernel-variant parity/leak invariant broke "
                  f"(greedy_parity={record['greedy_parity']}, "
                  f"leaks_clean={record['leaks_clean']})",
                  file=sys.stderr)
            return 1
        return 0

    if args.prefix or args.spec:
        record = {"metric": "decode_prefix_spec", "skipped": False,
                  "unit": "x", "vs_baseline": 0.0}
        if args.prefix:
            record["prefix"] = _bench_prefix(args)
            record["value"] = record["prefix"]["ttft_speedup"]
        if args.spec:
            record["spec"] = _bench_spec(args)
            record.setdefault("value", record["spec"]["speedup"])
        record["vs_baseline"] = record["value"]
        record["config"] = {"backend": jax.default_backend(),
                            "page_size": args.page_size}
        emit_record(record, out=args.out)
        return 0

    import paddle_tpu as paddle
    from paddle_tpu.distributed.fleet.utils import (
        HybridParallelInferenceHelper)
    from paddle_tpu.models import GPTForCausalLM, gpt_tiny
    from paddle_tpu.serving.generation import GenerationServer

    paddle.seed(0)
    cfg = gpt_tiny(use_flash_attention=False)
    model = GPTForCausalLM(cfg)
    model.eval()

    b, plen, new = args.batch, args.prompt_len, args.max_new
    total = plen + new
    assert total <= cfg.max_seq_len
    rng = np.random.RandomState(0)
    prompts = rng.randint(0, cfg.vocab_size, (b, plen)).astype("int64")
    n_requests = args.requests or 2 * b

    # ---- equivalence probe: cached decode logits vs full forward ----
    from paddle_tpu.serving.generation.model_fns import CachedDecoder
    pages_per_seq = -(-cfg.max_seq_len // args.page_size)
    dec = CachedDecoder(model, max_batch=b, page_size=args.page_size,
                        pages_per_seq=pages_per_seq)
    k, v = model.init_kv_pools(1 + b * pages_per_seq, args.page_size)
    tables = (1 + np.arange(b * pages_per_seq, dtype=np.int32)
              .reshape(b, pages_per_seq))
    lens = np.full(b, plen, np.int32)
    _, last, k, v, _ = dec.prefill(prompts, lens, tables, None, None, k, v)
    cur = np.asarray(last).argmax(-1)
    ref_ids = np.concatenate([prompts, cur[:, None]], 1)
    _, logits, k, v, _ = dec.decode(
        cur, np.full(b, plen, np.int32), np.ones(b, bool),
        np.full(b, plen + 1, np.int32), tables, None, None, k, v)
    ref = model(paddle.to_tensor(ref_ids)).numpy()[:, -1]
    equiv = float(np.abs(np.asarray(logits) - ref).max())

    # ---- baseline: full-window generate ----
    helper = HybridParallelInferenceHelper(model, max_length=total)
    helper._full_window_generate(prompts, total, 0.0, 0)  # compile+warm
    base_tps, base_tok_ms = [], []
    for _ in range(args.trials):
        t0 = time.perf_counter()
        out = helper._full_window_generate(prompts, total, 0.0, 0)
        dt = time.perf_counter() - t0
        assert out.shape == (b, total)
        base_tps.append(b * new / dt)
        base_tok_ms.append(dt / new * 1e3)
    baseline = _median(base_tps)

    # ---- engine: continuous-batching cached decode ----
    eng_tps, eng_p99 = [], []
    occupancy = None
    for trial in range(args.trials):
        srv = GenerationServer(
            model, max_batch=b, page_size=args.page_size,
            name=f"bench{trial}", start=False)
        srv.warmup(seq_buckets=[srv.policy.bucket_seq(plen)])
        srv.start()
        t0 = time.perf_counter()
        futs = [srv.submit_generate(prompts[i % b], max_new_tokens=new)
                for i in range(n_requests)]
        done = [f.result(timeout=600) for f in futs]
        dt = time.perf_counter() - t0
        n_tokens = sum(len(d) for d in done)
        snap = srv.metrics_snapshot()
        srv.shutdown()
        eng_tps.append(n_tokens / dt)
        eng_p99.append(snap["inter_token_ms"].get("p99", 0.0))
        occupancy = snap["batch_occupancy"]
    engine = _median(eng_tps)

    record = {
        "metric": "decode_tokens_per_sec",
        "skipped": False,
        "value": round(engine, 1),
        "unit": "tokens/s",
        "vs_baseline": round(engine / baseline, 3) if baseline else 0.0,
        "baseline_full_window_tokens_per_sec": round(baseline, 1),
        "baseline_per_token_ms": round(_median(base_tok_ms), 3),
        "engine_p99_inter_token_ms": round(_median(eng_p99), 3),
        "batch_occupancy": occupancy,
        "cached_vs_uncached_max_abs_diff": equiv,
        "cost_model": _decode_cost_model_check(model, cfg, b),
        "config": {"model": "gpt_tiny", "batch": b,
                   "requests_per_trial": n_requests,
                   "prompt_len": plen, "max_new_tokens": new,
                   "page_size": args.page_size,
                   "trials": args.trials,
                   "backend": jax.default_backend()},
    }
    emit_record(record, out=args.out)
    if record["cost_model"].get("available") and \
            not record["cost_model"]["agrees"]:
        print("# FAIL: decode cost-model FLOPs diverge >15% from the "
              "hand 2N estimate "
              f"({record['cost_model']})", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
