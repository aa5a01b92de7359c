#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the two hot paths once each through the entry points a user
calls, at the full width and depth of gpt3_1p3b (hidden 2048, 24
layers, 16 heads, vocab 50304) with random weights made from a seed:

- *train*: ``jit.TrainStep`` over ``GPTForCausalLM(gpt3_1p3b(stacked,
  full remat))`` with AdamW (bf16 moments) and AMP O2, b=2 s=2048 —
  bench.py's ``--config 1.3b`` — a few steps on one fixed batch; the
  loss must be finite and fall, and the step must hold the flash kernel;
- *serve*: a ``GenerationServer`` on the same architecture (the module
  stack, whose pools update in place) with a KV pool sized from the
  compiler's memory analysis to fill what the weights leave; a few
  requests of different lengths, one pair sharing a prefix, streamed
  to the end and held to the plain full forward of the same weights;
  once over the model's dtype, once over an int8 pool, both times on
  the path a server built with defaults takes (on a TPU the fused
  Pallas paged kernels; the log says which);
- *restart*: the AOT compile cache is on for all of it; after the
  first serve pass a fresh server over the same weights replays the
  warm-up manifest from the cache (hits, no misses) and serves from
  the loaded executables.

``--phase smallthinker`` runs instead the one comparison the
benchmark's token parity cannot give (PERF.md section 7): the 8-layer
cut of ``smallthinker_21ba3b`` in bfloat16 at its published widths
prefills a 6,000-token prompt and decodes 64 tokens through
``CachedDecoder`` and both kinds of KV pool, and each step's **logits**
are held to the plain float32 reference's full forward
(``benchmarks/reference/smallthinker.py``), with the reference's own
float8 control beside them, which has to fail.

``--phase kexaone`` is the same comparison for the share of
``k_exaone_236b_a23b`` the benchmark serves (5 layers, experts 0-15 of
128, 19,200 vocabulary rows): a 1,500-token prompt through
``prefill[1,2048]``, 64 decode steps, against
``benchmarks/reference/kexaone.py``.

``--phase nemotron`` is the same for the share of
``nemotron_3_super_120b_a12b`` the benchmark serves (the published
layers 0-10, experts 0-127 of 512, 32,768 vocabulary rows): the KV pool
of its one attention layer and the state pool of its five state-space
layers, against ``benchmarks/reference/nemotron_h.py``; and one more
reading, the program itself with the SSM state kept in bfloat16
(``ssm_state_dtype``), which this statistic cannot tell from the
program (PERF.md section 6, PR 35) and which is reported, not judged.

``--chips 4`` runs instead the paths that exist only across chips, and
what they are compared with: one TrainStep over a dp x mp mesh against
the single-device step, and an mp=4 ``ServingMesh`` server against the
one-chip engine (depth cut, width published).

One process; it fails at once where jax finds no TPU; each phase's
failure is the script's. The last line of stdout is
``{"ok": true, "device": {"platform", "kind", "count"}}``. Numbers it
prints on earlier lines are existence proofs, not benchmark results.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time

import numpy as np

GIB = 1024 ** 3
# the share of the device kept free beside weights, pool and the largest
# program's temporaries: the allocator's fragmentation and the parity
# forward (half a GiB of a v5e's 15.75)
POOL_MARGIN = 1 / 32
SERVE_SITES = ("generate_prefill", "generate_chunked", "generate_decode")


def log(msg: str):
    print(msg, flush=True)


def fmt(nbytes) -> str:
    return f"{nbytes / GIB:.2f} GiB" if nbytes >= GIB // 10 \
        else f"{nbytes / 2**20:.2f} MiB"


# ------------------------------------------------------------ counters
class CompileCounter:
    """What jax compiled and what its persistent cache served, counted
    from jax.monitoring — so a warm run can show it compiled nothing."""

    def __init__(self):
        import jax
        self.hits = self.misses = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(
            self._duration)

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def snapshot(self):
        return (self.hits, self.misses, self.compile_s)

    def since(self, snap) -> str:
        # jax times compile_or_get_cached: a hit's load is in it too
        return (f"jax cache hits {self.hits - snap[0]} misses "
                f"{self.misses - snap[1]}, backend compile or cache "
                f"load {self.compile_s - snap[2]:.1f} s")


def device_bytes() -> dict:
    """Bytes held on each local device: the allocator's own count where
    the backend reports it, else the shards of every live array."""
    import jax
    devices = jax.local_devices()
    stats = [d.memory_stats() for d in devices]
    if all(s is not None for s in stats):
        return {d: int(s["bytes_in_use"]) for d, s in zip(devices, stats)}
    out = dict.fromkeys(devices, 0)
    for a in jax.live_arrays():
        for shard in a.addressable_shards:
            out[shard.device] += int(shard.data.nbytes)
    return out


def drop_programs():
    """Forget every compiled program and what its closures pin (a
    decoder's thunks in the xstats registry hold its model)."""
    import jax

    from paddle_tpu.observability import xstats
    xstats.default_exec_registry().clear()   # its thunks pin the step
    jax.clear_caches()
    gc.collect()


def release(what: str, limit_bytes: int) -> int:
    """Everything the finished phase held is dropped and the device is
    looked at before the next phase allocates: a 1.3B train step's plan
    leaves no room beside it, and the compiler counts one program at a
    time, not what the process still holds."""
    drop_programs()
    in_use = max(device_bytes().values())
    log(f"[release] after {what}: bytes_in_use {in_use} "
        f"({fmt(in_use)})")
    if in_use > limit_bytes // 64:
        raise RuntimeError(
            f"{what} left {in_use} bytes on the device; the next phase "
            f"needs it empty")
    return in_use


def exec_table(sites) -> list:
    """One row per executable registered at ``sites``: its memory
    analysis and whether its program holds a Pallas kernel."""
    from paddle_tpu.observability import xstats
    reg = xstats.default_exec_registry()
    rows = []
    for ent in reg.entries():
        if ent.site not in sites:
            continue
        text = ent.program_text()
        ana = reg.ensure_analysis(ent)
        if ana is None or "temp_bytes" not in ana:
            raise RuntimeError(
                f"no memory analysis for {ent.site} {ent.signature}: "
                f"{ent.analysis_error or ana}")
        rows.append({
            "site": ent.site,
            "shape": tuple(ent.signature[0][0]) if ent.signature else (),
            "kernel": text is not None and "tpu_custom_call" in text,
            "cache": ent.provenance.get("cache"),
            "arg": ana["arg_bytes"], "temp": ana["temp_bytes"],
            "out": ana["out_bytes"], "alias": ana["alias_bytes"]})
    return rows


def log_exec_table(tag: str, rows: list):
    for r in rows:
        log(f"[{tag}] {r['site']}{list(r['shape'])}: attention "
            f"{'pallas kernel (tpu_custom_call)' if r['kernel'] else 'xla (no kernel)'}"
            f", cache {r['cache']}, args {fmt(r['arg'])} temp "
            f"{fmt(r['temp'])} out {fmt(r['out'])} alias "
            f"{fmt(r['alias'])}")


# --------------------------------------------------------------- train
def _train_losses(cfg, *, batch, seq, steps, amp_level, moment_dtype,
                  mesh=None):
    """Seeded model + AdamW + TrainStep, ``steps`` calls on one fixed
    batch. Returns (losses, seconds, model)."""
    import paddle_tpu as paddle
    from paddle_tpu.distributed import shard
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models import GPTForCausalLM, GPTPretrainingCriterion

    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    if mesh is not None:
        shard.apply_sharding(model, mesh=mesh)
    crit = GPTPretrainingCriterion()
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters(),
                                 moment_dtype=moment_dtype)
    step = TrainStep(model, lambda out, y: crit(out, y), opt,
                     amp_level=amp_level)
    ids = paddle.to_tensor(np.random.RandomState(0).randint(
        0, cfg.vocab_size, (batch, seq)).astype("int64"))
    losses, secs = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        losses.append(float(step(ids, ids).numpy()))
        secs.append(time.perf_counter() - t0)
    return losses, secs, model


def phase_train(cfg, *, batch, seq, steps, expect_kernel,
                amp_level="O2", moment_dtype="bfloat16") -> dict:
    """The training hot path through ``TrainStep.__call__`` (the
    ``run_steps`` scan window is a second whole-model compile and is
    not run)."""
    losses, secs, model = _train_losses(
        cfg, batch=batch, seq=seq, steps=steps, amp_level=amp_level,
        moment_dtype=moment_dtype)
    log(f"[train] {model.num_params() / 1e9:.3f}B params hidden "
        f"{cfg.hidden_size} layers {cfg.num_layers} heads "
        f"{cfg.num_heads} vocab {cfg.vocab_size}; b={batch} s={seq} "
        f"amp={amp_level} moments={moment_dtype}; TrainStep.__call__ "
        f"x{steps} (run_steps not run)")
    log(f"[train] losses {[round(x, 4) for x in losses]}; first call "
        f"{secs[0]:.1f} s (compile included), later calls "
        f"{[round(s, 3) for s in secs[1:]]} s")
    if not all(np.isfinite(losses)):
        raise RuntimeError(f"train loss not finite: {losses}")
    if not losses[-1] < losses[0]:
        raise RuntimeError(f"train loss did not fall: {losses}")
    rows = exec_table(("train_step",))
    log_exec_table("train", rows)
    kernel = any(r["kernel"] for r in rows)
    if expect_kernel and not kernel:
        raise RuntimeError(
            "the compiled train step holds no tpu_custom_call: "
            "attention took the dense path, not the flash kernel")
    return {"losses": losses, "seconds": secs, "kernel": kernel}


# --------------------------------------------------------------- serve
def make_serve_model(cfg):
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForCausalLM
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    model.eval()
    return model


def make_traffic(cfg, page_size: int, seq_buckets, seed: int = 0) -> dict:
    """Prompts of different lengths: one short (the parity request),
    one past the small bucket, and a pair sharing a two-page prefix."""
    rng = np.random.RandomState(seed)

    def toks(n):
        return rng.randint(1, cfg.vocab_size, (n,)).astype(np.int64)

    small, big = seq_buckets
    prefix = toks(2 * page_size)
    return {
        "short": (toks(small * 3 // 8), 16),
        "long": (toks(small + (big - small) * 2 // 5), 12),
        "pair_a": (np.concatenate([prefix, toks(8)]), 12),
        "pair_b": (np.concatenate([prefix, toks(12)]), 12),
    }


def _server_kwargs(seq_buckets, name):
    return dict(seq_buckets=list(seq_buckets), name=name, start=False)


def plan_kv_pool(model, *, limit_bytes, seq_buckets, kv_dtype) -> int:
    """Pages of a KV pool that fills what the weights leave. Nothing is
    guessed: a probe server with half the free memory for a pool gives
    the device bytes of one page as the allocator counts them, and its
    executables — the lattice that bounds the smoke's traffic — give,
    from the compiler's memory analysis, what the largest program needs
    beside its arguments. Those temporaries can grow with the pool
    (an int8 pool's scale planes are copied whole, padded to 128
    lanes), so they are charged to the page: the bound is theirs at the
    probe's size, in proportion."""
    from paddle_tpu.observability import xstats
    from paddle_tpu.serving.generation import GenerationServer

    base = max(device_bytes().values())
    margin = int(limit_bytes * POOL_MARGIN)
    free = limit_bytes - base - margin
    nominal = 16 * int(model.kv_cache_spec(kv_dtype)["kv_bytes_per_token"])
    probe_pages = int(free // 2 // nominal)
    xstats.default_exec_registry().clear()
    probe = GenerationServer(model, num_pages=probe_pages,
                             **_server_kwargs(seq_buckets, "smoke-probe"))
    page_bytes = (max(device_bytes().values()) - base) / probe_pages
    t0 = time.perf_counter()
    probe.warmup(seq_buckets=list(seq_buckets), batch_buckets=[2])
    rows = exec_table(SERVE_SITES)
    log_exec_table("probe", rows)
    extra = max(r["temp"] + r["out"] - r["alias"] for r in rows)
    probe.shutdown()
    del probe
    xstats.default_exec_registry().clear()
    gc.collect()
    pages = int(free // (page_bytes + extra / probe_pages))
    log(f"[plan] limit {fmt(limit_bytes)} - weights resident "
        f"{fmt(base)} - margin {fmt(margin)} = {fmt(free)} for pool and "
        f"temporaries; a probe pool of {probe_pages} pages costs "
        f"{fmt(page_bytes)} a page on the device and its largest "
        f"program {fmt(extra)} beside its arguments (memory analysis "
        f"of {len(rows)} executables, {time.perf_counter() - t0:.0f} "
        f"s): {pages} pages = pool {fmt(pages * page_bytes)} + up to "
        f"{fmt(pages * extra / probe_pages)} of temporaries")
    if pages * 16 < 2 * int(model.kv_cache_spec()["max_seq_len"]):
        raise RuntimeError(f"no room for a KV pool: {pages} pages")
    return pages


def reference_logits(model, prompt, new_tokens):
    """The plain full forward of the same weights over prompt +
    generated tokens, one window: ``[len, vocab]`` float32."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.jit.functional import functional_call, state_arrays
    params, buffers = state_arrays(model)
    ids = np.concatenate([prompt, np.asarray(new_tokens, np.int64)])[None]
    logits = jax.jit(lambda p, b, x: functional_call(
        model, p, b, x, training=False))(params, buffers,
                                         jnp.asarray(ids))
    return np.asarray(logits, np.float32)[0]


def reference_tokens(model, prompt, new_tokens):
    """Per generated position: the reference's own greedy token, and
    how far the served token's logit is below the reference's best."""
    logits = reference_logits(model, prompt, new_tokens)
    # position t's logits predict token t+1
    pred = logits[len(prompt) - 1:len(logits) - 1]
    served = np.asarray(new_tokens)
    gap = pred.max(-1) - pred[np.arange(len(served)), served]
    return pred.argmax(-1), gap, float(pred.std())


def logit_parity(srv, model, prompt, new_tokens, seq_bucket) -> tuple:
    """After the drain, the server's own decoder and pools once more,
    by hand: the prefill's logits for the short prompt and one decode
    step's, whole vocabulary rows against the full forward. A random
    model mostly echoes its input, so equal greedy tokens say little
    about attention; the rows say whether what was written to the pages
    and read back through the block table is what the forward sees.
    Returns the two largest absolute differences and the logit std."""
    n, width, lanes = len(prompt), srv.pages_per_seq, srv.max_batch
    ref = reference_logits(model, prompt, new_tokens)
    pages = srv.kv.alloc(srv.kv.pages_for(n + 1))
    tables = np.zeros((lanes, width), np.int32)
    tables[0, :len(pages)] = pages
    ids = np.zeros((2, seq_bucket), np.int64)
    ids[0, :n] = prompt
    _, last, srv.kv.k, srv.kv.v, _ = srv.decoder.prefill(
        ids, np.array([n, 0], np.int32), tables[:2], None, None,
        srv.kv.k, srv.kv.v)
    lane0 = np.zeros(lanes, bool)
    lane0[0] = True
    _, step, srv.kv.k, srv.kv.v, _ = srv.decoder.decode(
        np.where(lane0, new_tokens[0], 0).astype(np.int64),
        np.where(lane0, n, 0).astype(np.int32), lane0,
        np.where(lane0, n + 1, 0).astype(np.int32), tables,
        None, None, srv.kv.k, srv.kv.v)
    srv.kv.release(pages)
    return (float(np.abs(np.asarray(last, np.float32)[0]
                         - ref[n - 1]).max()),
            float(np.abs(np.asarray(step, np.float32)[0] - ref[n]).max()),
            float(ref[n - 1:n + 1].std()))


# how far the logits of prefill + decode through the cache may lie from
# the float32 reference's full forward, as the root mean square of the
# difference over the reference logits' standard deviation, for a
# configuration that states bfloat16 weights, activations and pool.
# Set between two readings on the chip (my chip run, PR 29; PERF.md
# section 6): the program's own, 0.0141 (its worst single row 0.056;
# 0.0167 while its logits left the device as bfloat16), and the
# reference's control with every activation rounded to float8
# e4m3, 1.07, which has to lie above it: 6 x room below, 10 x above.
LOGIT_RMS_TOL = 0.1
# the same for --phase kexaone (5 layers, 16 of 128 experts held, 1/8 of
# the vocabulary; my chip runs, PR 33): the program 0.0229 (its worst
# single row 0.090), the float8 control 0.310 (weights, activations and
# the residual stream in float8; activations alone read 0.162: an eighth
# of the routed experts is in the sum, so a token sent elsewhere by a
# rounded router moves one term in eight, beside a shared expert and a
# dense layer that do not route). 0.06: 2.6 x room below, 5.2 x above,
# and under the control of activations alone too.
KEXAONE_LOGIT_RMS_TOL = 0.06
# the same for --phase nemotron (11 layers, 128 of 512 experts held, 1/4
# of the vocabulary; my chip runs, PR 35: this smoke as committed, from
# a git archive of the final tree, rc 0; an earlier tree had read the
# same to the last digit): the program 0.00996 (its worst single row
# 0.0201), the float8 control 0.2268 (weights, activations and the
# residual stream in float8). 0.04: 4.0 x room below, 5.7 x above. The
# program with its SSM state in bfloat16 reads 0.01029, among the
# program's own: a state rounded once a step adds about what one more
# bfloat16 rounding of the layer's output adds (0.30% against 0.17% of
# y in a simulation of 2,048 steps), so no limit lies between the two
# and that reading is reported, not judged. The state's precision is
# held elsewhere: PagedKVCache refuses state pools whose bytes a slot
# are not kv_cache_spec()'s (serving/generation/kv_cache.py).
NEMOTRON_LOGIT_RMS_TOL = 0.04


# --phase -> the preset in paddle_tpu.models, the cut it is built with and
# what phase_cached_logits is told
CACHED_LOGITS_PHASES = {
    "nemotron": ("nemotron_3_super_120b_a12b",
                 dict(num_layers=11, moe_num_experts=128, vocab_size=32768,
                      dtype="bfloat16"),
                 dict(prompt_len=1500, new_tokens=64, seq_bucket=2048,
                      reference="nemotron_h", tol=NEMOTRON_LOGIT_RMS_TOL,
                      model_class="NemotronHForCausalLM",
                      also_read={"state_in_bfloat16":
                                  {"ssm_state_dtype": "bfloat16"}})),
    "smallthinker": ("smallthinker_21ba3b",
                     dict(num_layers=8, dtype="bfloat16"),
                     dict(prompt_len=6000, new_tokens=64, seq_bucket=8192)),
    "kexaone": ("k_exaone_236b_a23b",
                dict(num_layers=5, moe_num_experts=16, vocab_size=19200,
                     dtype="bfloat16"),
                dict(prompt_len=1500, new_tokens=64, seq_bucket=2048,
                     reference="kexaone", tol=KEXAONE_LOGIT_RMS_TOL)),
}


def phase_cached_logits(cfg, *, prompt_len, new_tokens, seq_bucket,
                        page_size=16, seed=0, tol=LOGIT_RMS_TOL,
                        reference="smallthinker",
                        model_class="GPTForCausalLM",
                        also_read=None) -> dict:
    """Prefill then decode through ``CachedDecoder`` and the cache
    manager's own pools and table row, one lane, greedy; every step's
    logits against the full forward of the configuration's plain
    reference over prompt + served tokens, and the reference's control
    (one precision step down) against the same. ``also_read`` names
    further readings, each the program itself rebuilt from the same
    seed with some fields of ``cfg`` replaced and fed the tokens the
    program served: reported beside the two, not judged. Raises unless
    the program lies inside ``tol`` and the control outside it."""
    import dataclasses
    import os

    import paddle_tpu as paddle
    from benchmarks import common
    from paddle_tpu import models
    from paddle_tpu.jit.functional import state_arrays
    from paddle_tpu.serving.generation.kv_cache import PagedKVCache
    from paddle_tpu.serving.generation.model_fns import CachedDecoder

    reference = common.load_module(os.path.join(
        common.HERE, "reference", reference + ".py"), reference + "_ref")
    total = prompt_len + new_tokens
    rng = np.random.default_rng(seed)
    prompt = rng.integers(0, cfg.vocab_size, prompt_len)

    def through_the_cache(cfg, forced=None):
        """``(model, logits [new_tokens + 1, vocab], tokens)``: greedy,
        or fed ``forced`` whatever the logits say."""
        paddle.seed(seed)
        model = getattr(models, model_class)(cfg)
        model.eval()
        kv = PagedKVCache(model, num_pages=2 + -(-total // page_size),
                          page_size=page_size, max_batch=1)
        width = kv.table_width(total)
        dec = CachedDecoder(model, max_batch=1, page_size=page_size,
                            pages_per_seq=width, max_positions=total)
        log(f"[cached-logits] {model.num_params() / 1e9:.3f} B parameters, "
            f"{'the fused paged kernels' if dec.use_pallas else 'the pure-JAX body'}"
            f"; pools {fmt(kv.pool_bytes())}; tables {width} wide "
            f"(ring {kv.ring_pages}, state slot {kv.state_columns})")
        tables = np.zeros((1, width), np.int32)
        kv.fill_row(tables[0], kv.alloc(kv.pages_for(total)),
                    kv.alloc_window(total), kv.alloc_state())
        ids = np.zeros((1, seq_bucket), np.int64)
        ids[0, :prompt_len] = prompt
        _, last, kv.k, kv.v, _ = dec.prefill(
            ids, np.array([prompt_len], np.int32), tables, None, None,
            kv.k, kv.v)
        rows = [np.asarray(last, np.float32)[0]]
        tokens = []
        for step in range(new_tokens):
            tokens.append(int(rows[-1].argmax()) if forced is None
                          else forced[step])
            ctx = prompt_len + step
            _, out, kv.k, kv.v, _ = dec.decode(
                np.array([tokens[-1]], np.int64), np.array([ctx], np.int32),
                np.array([True]), np.array([ctx + 1], np.int32), tables,
                None, None, kv.k, kv.v)
            rows.append(np.asarray(out, np.float32)[0])
        return model, np.stack(rows), tokens    # positions n-1 .. n+new-1

    model, got, served = through_the_cache(cfg)
    # the full forward over prompt + served tokens, padded to whole
    # query blocks (a causal model's earlier positions do not see it)
    padded = -(-total // reference.QUERY_BLOCK) * reference.QUERY_BLOCK
    full = np.zeros((1, padded), np.int64)
    full[0, :prompt_len] = prompt
    full[0, prompt_len:total] = served
    at = np.arange(prompt_len - 1, total)
    params = state_arrays(model)[0]
    want = np.asarray(reference.logits(params, full, cfg, positions=at))[0]
    low = np.asarray(reference.control_logits(params, full, cfg,
                                              positions=at))[0]

    def rms_over_std(x):
        return float(np.sqrt(np.mean(np.square(x - want))) / want.std())

    others = {}
    log(f"[cached-logits] program {rms_over_std(got):.5f}, control "
        f"{rms_over_std(low):.5f} of the reference logits' std")
    del model, params               # one model's weights at a time
    for name, fields in (also_read or {}).items():
        drop_programs()
        _, rows, _ = through_the_cache(
            dataclasses.replace(cfg, **fields), forced=served)
        others[name] = rms_over_std(rows)
    out = {"program": rms_over_std(got), "control": rms_over_std(low),
           **others,
           "program_worst_row": float(max(
               np.sqrt(np.mean(np.square(g - w))) for g, w in
               zip(got, want)) / want.std()),
           "same_greedy_token": int(np.sum(
               got.argmax(-1) == want.argmax(-1))), "rows": len(got),
           "tol": tol}
    log(f"[cached-logits] {out}")
    if not out["program"] <= tol < out["control"]:
        raise AssertionError(
            f"logits of prefill + decode through the cache against the "
            f"reference's full forward: {out}")
    return out


def drive(srv, traffic, vocab_size) -> dict:
    """The smoke's traffic through ``submit_generate`` on a server that
    has not started: every stream read to its end, checked against what
    was asked. The same calls in the same order give the same prefill
    groups, hence the same executables."""
    futs = {}
    # queued before the loop starts, so the first admission groups them
    for key in ("short", "long", "pair_a"):
        prompt, new = traffic[key]
        futs[key] = srv.submit_generate(prompt, max_new_tokens=new)
    srv.start()
    streams = {}
    # pair_a's first token means its prompt pages are published: its
    # partner then takes the shared pages and prefills only its suffix
    it_a = iter(futs["pair_a"])
    first_a = next(it_a)
    prompt, new = traffic["pair_b"]
    futs["pair_b"] = srv.submit_generate(prompt, max_new_tokens=new)
    streams["pair_a"] = [first_a] + list(it_a)
    for key in ("short", "long", "pair_b"):
        streams[key] = list(futs[key])         # the stream, to its end
    for key, fut in futs.items():
        want = traffic[key][1]
        got = fut.result(timeout=60)
        if got != streams[key] or len(got) != want:
            raise RuntimeError(
                f"{key}: streamed {len(streams[key])} tokens, result "
                f"{len(got)}, asked {want}")
        if not all(0 <= t < vocab_size for t in got):
            raise RuntimeError(f"{key}: token out of range {got}")
    return streams


def serve_once(model, *, num_pages, seq_buckets, traffic, name,
               parity_tol, use_pallas=None) -> dict:
    """One server's life: start, the smoke's traffic through
    ``submit_generate``, streams read to the end, parity of the short
    request, drain, page accounting."""
    from paddle_tpu.serving.generation import GenerationServer

    srv = GenerationServer(model, num_pages=num_pages,
                           use_pallas=use_pallas,
                           **_server_kwargs(seq_buckets, name))
    log(f"[{name}] GenerationServer max_batch {srv.max_batch} page_size "
        f"{srv.page_size} tables {srv.pages_per_seq} pages/seq, pool "
        f"{srv.kv.num_pages} pages {fmt(srv.kv.pool_bytes())} "
        f"({srv.kv_dtype or 'model dtype'}); decode attention: "
        f"{'the fused paged kernel' if srv.use_pallas else 'the pure-JAX gather'}"
        f" ({'by default' if use_pallas is None else 'asked for'})")
    t0 = time.perf_counter()
    streams = drive(srv, traffic, model.config.vocab_size)
    secs = time.perf_counter() - t0
    snap = srv.metrics_snapshot()
    if snap["prefix"]["hits"] < 1 or \
            snap["prefix"]["tokens_reused"] < 2 * srv.page_size:
        raise RuntimeError(f"{name}: the pair shared no prefix pages: "
                           f"{snap['prefix']}")
    prompt = traffic["short"][0]
    ref, gap, spread = reference_tokens(model, prompt, streams["short"])
    agree = int((ref == np.asarray(streams["short"])).sum())
    log(f"[{name}] {sum(len(s) for s in streams.values())} tokens over "
        f"{len(streams)} streams in {secs:.1f} s (compiles included); "
        f"prefix hits {snap['prefix']['hits']} tokens reused "
        f"{snap['prefix']['tokens_reused']}; greedy vs full forward: "
        f"{agree}/{len(ref)} tokens equal, largest logit gap "
        f"{gap.max():.4f} (tolerance {parity_tol * spread:.4f} = "
        f"{parity_tol} x logit std {spread:.3f})")
    if gap.max() > parity_tol * spread:
        raise RuntimeError(
            f"{name}: served tokens disagree with the full forward of "
            f"the same weights: gaps {gap}")
    rows = exec_table(SERVE_SITES)
    log_exec_table(name, rows)
    srv.shutdown(drain=True)
    srv.kv.assert_no_leaks()
    srv.clear_prefix_cache()
    if srv.kv.used_pages:
        raise RuntimeError(f"{name}: {srv.kv.used_pages} pages still "
                           f"held after drain")
    d_prefill, d_decode, spread = logit_parity(
        srv, model, prompt, streams["short"], seq_buckets[0])
    log(f"[{name}] logits vs full forward, largest difference over the "
        f"vocabulary: prefill {d_prefill:.5f}, decode step "
        f"{d_decode:.5f} (tolerance {parity_tol * spread:.5f})")
    if max(d_prefill, d_decode) > parity_tol * spread:
        raise RuntimeError(
            f"{name}: the decoder's logits are not the full forward's")
    srv.kv.assert_no_leaks()
    return {"streams": streams, "rows": rows, "seconds": secs,
            "pool_bytes": srv.kv.pool_bytes()}


def phase_serve(model, *, limit_bytes, seq_buckets, name,
                use_pallas=None, kv_dtype="", parity_tol=0.0625,
                restart=False) -> dict:
    """A serve pass over one pool dtype; ``use_pallas`` None is the
    path a server built with defaults takes (on a TPU the fused paged
    kernel, and the decode executable must then hold it). ``restart``
    adds the warm restart from the AOT cache."""
    import paddle_tpu as paddle
    from paddle_tpu.framework import place

    paddle.set_flags({"FLAGS_decode_kv_dtype": kv_dtype})
    try:
        pages = plan_kv_pool(model, limit_bytes=limit_bytes,
                             seq_buckets=seq_buckets, kv_dtype=kv_dtype)
        traffic = make_traffic(model.config, 16, seq_buckets)
        out = serve_once(model, num_pages=pages, seq_buckets=seq_buckets,
                         traffic=traffic, name=name,
                         parity_tol=parity_tol, use_pallas=use_pallas)
        if use_pallas is not False and place.on_tpu() and not any(
                r["kernel"] for r in out["rows"]
                if r["site"] == "generate_decode"):
            raise RuntimeError(
                "on a TPU the decode step attends through the fused "
                "paged kernel, and the decode executable holds no "
                "tpu_custom_call")
        gc.collect()              # the first server's pool, before the next
        if restart:
            phase_restart(model, num_pages=pages,
                          seq_buckets=seq_buckets, name=name,
                          traffic=traffic, expect=out["streams"],
                          use_pallas=use_pallas)
        return out
    finally:
        paddle.set_flags({"FLAGS_decode_kv_dtype": ""})


def phase_restart(model, *, num_pages, seq_buckets, name, traffic,
                  expect, use_pallas=None):
    """What tests/test_compile_cache.py::TestServingSite does to stand
    for a restart: the in-process cache handle is dropped, a fresh
    server over the same weights replays the manifest its predecessor's
    traffic recorded, and every executable must come off the disk —
    ``deserialize_and_load`` on a real device — and then serve."""
    from paddle_tpu import compile_cache as cc
    from paddle_tpu.observability import xstats
    from paddle_tpu.serving.generation import GenerationServer

    xstats.default_exec_registry().clear()
    cc.reset_default_cache()
    srv = GenerationServer(model, num_pages=num_pages,
                           use_pallas=use_pallas,
                           **_server_kwargs(seq_buckets, name))
    before = cc.stats()
    t0 = time.perf_counter()
    srv.warmup_from_manifest()
    after = cc.stats()
    d = {k: after[k] - before[k] for k in ("hits", "misses", "errors")}
    replayed = len(srv.warmup_manifest.specs())
    log(f"[restart] manifest of {replayed} signatures replayed in "
        f"{time.perf_counter() - t0:.1f} s: compile cache {d}")
    if d["hits"] < replayed or d["misses"] or d["errors"] or \
            replayed < 3:
        raise RuntimeError(f"warm restart did not load from the AOT "
                           f"cache: {d} for {replayed} signatures")
    got = drive(srv, traffic, model.config.vocab_size)
    log_exec_table("restart", exec_table(SERVE_SITES))
    srv.shutdown(drain=True)
    srv.kv.assert_no_leaks()
    served = cc.stats()
    if served["misses"] != after["misses"]:
        raise RuntimeError("serving after the restart compiled "
                           "something the manifest should have loaded")
    if got != expect:
        raise RuntimeError(
            f"the loaded executables served {got}, the compiled ones "
            f"served {expect}")
    log(f"[restart] the same traffic served from the loaded "
        f"executables, {sum(map(len, got.values()))} tokens identical "
        f"to the first server's")


# ---------------------------------------------------------- four chips
def phase_train_mesh(cfg, *, axes, batch, seq, steps, rtol) -> dict:
    """One TrainStep over a dp x mp mesh, sharded through
    ``distributed.shard.apply_sharding``, against the single-device
    step on the same seed: losses and every parameter after ``steps``
    updates agree, and every device holds its share. f32 without
    autocast — the comparison has to tell a mis-sharded weight from
    rounding, and at random init every weight gives the same loss."""
    import jax

    from paddle_tpu.distributed.mesh_utils import (build_mesh,
                                                   set_global_mesh)
    from paddle_tpu.observability import xstats

    kw = dict(batch=batch, seq=seq, steps=steps, amp_level=None,
              moment_dtype="float32")
    losses_1, _, model = _train_losses(cfg, **kw)
    params_1 = {n: np.asarray(p._data)
                for n, p in model.named_parameters()}
    del model
    xstats.default_exec_registry().clear()   # its thunks pin the step
    gc.collect()
    base = device_bytes()
    mesh = build_mesh(axes)
    set_global_mesh(mesh)
    try:
        losses_n, secs, model = _train_losses(cfg, mesh=mesh, **kw)
    finally:
        set_global_mesh(None)
    log(f"[train x{mesh.size}] mesh {dict(mesh.shape)} hidden "
        f"{cfg.hidden_size} heads {cfg.num_heads} vocab "
        f"{cfg.vocab_size}, depth cut to {cfg.num_layers} layers; "
        f"b={batch} s={seq} f32; losses {losses_n} vs single device "
        f"{losses_1}; first call {secs[0]:.1f} s")
    np.testing.assert_allclose(losses_n, losses_1, rtol=rtol,
                               err_msg="sharded loss != single device")
    # one Adam step moves a weight by at most the learning rate, and
    # where the gradient is all rounding its direction is too; a weight
    # in the wrong place is off by its initial spread, 200 times that
    for n, p in model.named_parameters():
        np.testing.assert_allclose(
            np.asarray(p._data), params_1[n], rtol=rtol, atol=1e-4,
            err_msg=f"param {n} diverged after {steps} sharded steps")
    now = device_bytes()
    held = {d: now[d] - base[d] for d in mesh.devices.flat}
    total = sum(int(np.prod(p.shape)) * p._data.dtype.itemsize
                for p in model.parameters()) * 3     # + two moments
    log(f"[train x{mesh.size}] held per device "
        f"{[fmt(b) for b in held.values()]} of {fmt(total)} of "
        f"parameters and moments")
    assert_spread(held, total, ways=int(mesh.shape["mp"]))
    sharded = [n for n, p in model.named_parameters()
               if len(p._data.sharding.device_set) == mesh.size
               and p._data.addressable_shards[0].data.nbytes
               < p._data.nbytes]
    if not sharded:
        raise RuntimeError("no parameter is sharded over the mesh")
    del model
    gc.collect()
    jax.clear_caches()
    return {"losses": losses_n, "single": losses_1}


def assert_spread(held: dict, total: int, ways: int):
    """Every device holds its share and none holds the whole: each
    within a tenth of total/ways (replicated norms and biases are the
    slack)."""
    share = total / ways
    for d, b in held.items():
        if not 0.9 * share <= b <= 1.1 * share + 64 * 2**20:
            raise RuntimeError(
                f"device {d} holds {b} bytes; its share of {total} "
                f"over {ways} ways is {share:.0f}: {held}")


def phase_serve_mesh(cfg, *, mp, seq_buckets, num_pages) -> dict:
    """An mp-way ``ServingMesh`` server against the one-chip engine on
    the same weights: greedy streams identical, each chip holding 1/mp
    of every KV pool and its share of the weights."""
    import jax

    from paddle_tpu.distributed.mesh_utils import build_mesh
    from paddle_tpu.serving.generation import GenerationServer

    model = make_serve_model(cfg)
    traffic = make_traffic(cfg, 16, seq_buckets)
    streams = {}
    for label, mesh in (("1 chip", None),
                        (f"mp={mp}", build_mesh({"mp": mp}))):
        base = device_bytes()
        srv = GenerationServer(
            model, num_pages=num_pages, mesh=mesh,
            **_server_kwargs(seq_buckets, f"smoke-{label[0]}"))
        now = device_bytes()
        held = {d: now[d] - base[d] for d in
                (jax.devices()[:1] if mesh is None
                 else mesh.devices.flat)}
        futs = {k: srv.submit_generate(p, max_new_tokens=n)
                for k, (p, n) in traffic.items()}
        t0 = time.perf_counter()
        srv.start()
        streams[label] = {k: list(f) for k, f in futs.items()}
        log(f"[serve {label}] hidden {cfg.hidden_size} heads "
            f"{cfg.num_heads} vocab {cfg.vocab_size}, depth cut to "
            f"{cfg.num_layers} layers; "
            f"{sum(map(len, streams[label].values()))} tokens in "
            f"{time.perf_counter() - t0:.1f} s; the engine holds "
            f"{[fmt(b) for b in held.values()]} per device")
        if mesh is not None:
            leaves = jax.tree_util.tree_leaves((srv.kv.k, srv.kv.v))
            for a in leaves:
                shards = a.addressable_shards
                if len(shards) != mp or any(
                        s.data.nbytes * mp != a.nbytes for s in shards):
                    raise RuntimeError(
                        f"a KV pool leaf {a.shape} is not split "
                        f"1/{mp} per chip: "
                        f"{[s.data.shape for s in shards]}")
            weights = sum(int(np.prod(p.shape)) * 4
                          for p in model.parameters())
            assert_spread(held, weights + srv.kv.pool_bytes(), mp)
            log(f"[serve {label}] every KV pool leaf holds 1/{mp} "
                f"on each chip ({fmt(srv.kv.pool_bytes() / mp)} of "
                f"{fmt(srv.kv.pool_bytes())})")
        srv.shutdown(drain=True)
        srv.kv.assert_no_leaks()
        del srv
        gc.collect()
    one, many = streams["1 chip"], streams[f"mp={mp}"]
    if one != many:
        raise RuntimeError(f"mp={mp} greedy streams differ from one "
                           f"chip's: {many} vs {one}")
    log(f"[serve mp={mp}] greedy streams identical to one chip's")
    return streams


# ---------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the cross-chip phase and what it "
                         "is compared with")
    ap.add_argument("--phase", default="gpt",
                    choices=("gpt", *CACHED_LOGITS_PHASES),
                    help="smallthinker, kexaone, nemotron: the cached-logits "
                         "comparison of that expert configuration's cut")
    args = ap.parse_args(argv)

    from paddle_tpu.compile_cache import aot_cache_dir, place_jax_cache
    jax_cache = place_jax_cache()
    import jax
    dev = jax.devices()[0]           # a TPU that cannot start raises here
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke.py needs a TPU and jax found "
                 f"{dev.platform!r} ({dev.device_kind})")
    if len(jax.devices()) != args.chips:
        sys.exit(f"--chips {args.chips} and jax found "
                 f"{len(jax.devices())} devices")
    counter = CompileCounter()

    import paddle_tpu as paddle
    from paddle_tpu import native
    from paddle_tpu.models import gpt3_1p3b
    paddle.set_flags({"FLAGS_compile_cache_dir": aot_cache_dir()})
    limit = int(dev.memory_stats()["bytes_limit"])
    t_start = time.perf_counter()
    log(f"[start] {len(jax.devices())} x {dev.device_kind} "
        f"({dev.platform}), {limit / GIB:.2f} GiB each; jax "
        f"{jax.__version__}; jax cache {jax_cache}; AOT cache "
        f"{aot_cache_dir()}")
    native.lib()
    log(f"[start] native library: {native.status()}")

    def timed(label, fn, **kw):
        snap, t0 = counter.snapshot(), time.perf_counter()
        out = fn(**kw)
        log(f"[{label}] phase {time.perf_counter() - t0:.1f} s; "
            f"{counter.since(snap)}")
        return out

    if args.phase in CACHED_LOGITS_PHASES:
        from paddle_tpu import models
        preset, cut, told = CACHED_LOGITS_PHASES[args.phase]
        timed("cached-logits", phase_cached_logits,
              cfg=getattr(models, preset)(**cut), **told)
    elif args.chips == 1:
        timed("train", phase_train,
              cfg=gpt3_1p3b(stacked=True, recompute="full"),
              batch=2, seq=2048, steps=4, expect_kernel=True)
        release("train", limit)
        model = make_serve_model(gpt3_1p3b())
        timed("serve", phase_serve, model=model, limit_bytes=limit,
              seq_buckets=(64, 256), name="smoke", restart=True)
        timed("serve-int8", phase_serve, model=model,
              limit_bytes=limit, seq_buckets=(64, 256),
              name="smoke-int8", kv_dtype="int8", parity_tol=0.25)
    else:
        # both comparisons are of one program against its sharded self:
        # a mesh changes the order of the sums, and neither a greedy
        # stream of a random model nor a 2e-4 tolerance may hang on the
        # bf16 passes of a default-precision f32 matmul
        jax.config.update("jax_default_matmul_precision", "highest")
        log("[start] matmul precision: highest")
        cut = gpt3_1p3b(num_layers=4)
        # s=1024: under a GSPMD mesh the module stack cannot hold the
        # flash kernel (s >= 2048) — "Mosaic kernels cannot be
        # automatically partitioned"; one chip's train phase covers it
        timed("train x4", phase_train_mesh, cfg=cut,
              axes={"dp": 2, "mp": 2}, batch=4, seq=1024, steps=2,
              rtol=2e-4)
        release("train x4", limit)
        timed("serve x4", phase_serve_mesh, cfg=cut, mp=4,
              seq_buckets=(64, 256), num_pages=1025)
    log(f"[done] {time.perf_counter() - t_start:.1f} s; "
        f"{counter.since((0, 0, 0.0))}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
