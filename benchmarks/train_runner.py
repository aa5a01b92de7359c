"""The training hot path, measured: ``jit.TrainStep.__call__`` on a
fresh seeded batch every step, the loss fetched every step.

Mostly ``chip_smoke.py``'s train phase (PR 21) with a window in place
of four steps: the same model construction (stacked decoder, full
recomputation), AdamW with bf16 moments, AMP O2, flash attention at its
static default blocks (no ``pretune``: a kernel variant picked by timing
would differ between runs).

Set-up: model from the seed; the plain float32 reference's loss on the
first batch, from the model's own arrays, before the optimizer state
exists beside them; ``warm_steps`` steps (the first loads or compiles
the step). Then the window: steps until ``--seconds`` have passed.

Nothing here names an architecture: the configuration file says which
class to build (``model.class`` over ``model.preset``), which loss
drives it (``train.criterion``) and which plain reference judges its
first loss (``reference``, handed in by ``run.py``).

Token ids are drawn from a Zipf-like distribution over the vocabulary,
so that there is something to learn in a few dozen steps: uniform ids
start at their entropy and a falling loss would be noise.
"""
from __future__ import annotations

import time

import numpy as np

from . import common, xplane


def zipf_cdf(vocab_size: int, exponent: float) -> np.ndarray:
    """Cumulative P(rank <= r) with P(rank r) ~ 1 / r**exponent."""
    p = 1.0 / np.arange(1, vocab_size + 1) ** exponent
    return np.cumsum(p / p.sum())


def zipf_ranks(rng, cdf: np.ndarray, shape) -> np.ndarray:
    """Ranks (0 = most frequent) drawn from ``cdf``."""
    return np.searchsorted(cdf, rng.random(shape)).clip(
        0, len(cdf) - 1).astype(np.int64)


def run(cell: dict, config: dict, traffic: dict, *, seed: int,
        seconds: float, trace: bool, t_start: float, rehearse: bool,
        reference) -> dict:
    caches = common.place_caches()
    import jax
    counter = common.CompileCounter()

    import paddle_tpu as paddle
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.jit.functional import state_arrays

    train = config["train"]
    criterion = common.resolve(train["criterion"], "train.criterion")
    batch, seq = int(traffic["batch"]), int(traffic["seq"])
    paddle.seed(int(seed) % (2 ** 31 - 1))
    cfg, model = common.build_model(config, "train")
    t_model = time.perf_counter()

    rng = np.random.default_rng([int(seed), 0x7A1])
    # the ranks are scattered over the vocabulary by a permutation of
    # the seed's, so that no seed's frequent ids are the low ones
    perm = rng.permutation(cfg.vocab_size)
    cdf = zipf_cdf(cfg.vocab_size, float(traffic["zipf_exponent"]))

    def next_batch():
        return perm[zipf_ranks(rng, cdf, (batch, seq))]

    first = next_batch()
    ref_loss = float(reference.causal_lm_loss(
        state_arrays(model)[0], first, first, cfg))
    t_ref = time.perf_counter()

    crit = criterion()
    opt = paddle.optimizer.AdamW(
        learning_rate=float(train["learning_rate"]),
        parameters=model.parameters(), moment_dtype=train["moment_dtype"])
    step = TrainStep(model, lambda out, y: crit(out, y), opt,
                     amp_level=train["amp_level"])

    def one_step(ids):
        with jax.profiler.TraceAnnotation("bench.batch"):
            x = paddle.to_tensor(ids)
        with jax.profiler.TraceAnnotation("bench.step"):
            loss = step(x, x)
        with jax.profiler.TraceAnnotation("bench.loss_fetch"):
            return float(loss.numpy())

    losses = [one_step(first)]
    for _ in range(int(traffic["warm_steps"]) - 1):
        losses.append(one_step(next_batch()))
    warm_compiles = counter.snapshot()

    # ------------------------------------------------------ the window
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    compiles0 = counter.snapshot()
    step_s, trace_dir, tracing, profiler_s = [], None, False, 0.0
    trace_steps = int(traffic["trace_steps"])
    lead = max(0.0, seconds / 2 - 1.0)
    window_cm = None
    while True:
        now = time.perf_counter()
        if now - t0 >= seconds:
            break
        if trace and trace_dir is None and now - t0 >= lead:
            trace_dir = common.start_trace(cell["name"])
            window_cm = jax.profiler.TraceAnnotation(
                xplane.WINDOW_ANNOTATION)
            window_cm.__enter__()
            tracing, traced = True, 0
            profiler_s += time.perf_counter() - now
        ids = next_batch()
        ta = time.perf_counter()
        losses.append(one_step(ids))
        step_s.append(time.perf_counter() - ta)
        if tracing:
            traced += 1
            if traced >= trace_steps:
                tb = time.perf_counter()
                window_cm.__exit__(None, None, None)
                jax.profiler.stop_trace()
                tracing = False
                profiler_s += time.perf_counter() - tb
    t1 = time.perf_counter()
    compiles = counter.since(compiles0)

    # -------------------------------------------- after it, outside it
    from paddle_tpu.observability import xstats
    text = [e.program_text() for e in xstats.default_exec_registry().entries()
            if e.site == "train_step"]
    kernel = any(t is not None and "tpu_custom_call" in t for t in text)
    tol = float(train["reference_loss_rtol"])
    window_losses = losses[int(traffic["warm_steps"]):]
    checks = {
        "losses_finite": bool(np.all(np.isfinite(losses))),
        "loss_falls": len(losses) >= 10
        and float(np.mean(losses[-5:])) < float(np.mean(losses[:5])),
        "first_loss_is_reference": abs(losses[0] - ref_loss)
        <= tol * abs(ref_loss),
        "no_compile_in_window": compiles["backend_compiles"] == 0,
        "flash_kernel_in_step": kernel or not train["expect_flash_kernel"],
    }
    return {
        "kind": traffic["kind"], "cell": cell, "config": config,
        "traffic": traffic, "model_cfg": cfg, "seconds": t1 - t0,
        "t0": t0, "t1": t1, "setup_s": setup_s, "step_s": step_s,
        "tokens_per_step": batch * seq, "n_params": model.num_params(),
        "losses": losses, "trace_dir": trace_dir,
        "trace_steps": trace_steps, "profiler_s": profiler_s,
        "gap_default": "unattributed",
        "attempted": len(step_s),
        "failed": int(sum(not np.isfinite(x) for x in window_losses)),
        "correct": all(checks.values()), "checks": checks,
        "memory_peak_bytes": common.device_record()["memory_peak_bytes"],
        "compared": {
            "first_loss_rel_gap": [abs(losses[0] - ref_loss)
                                   / abs(ref_loss), tol],
            "window_compiles": [compiles["backend_compiles"], 0],
            "losses_not_finite": [
                int(sum(not np.isfinite(x) for x in losses)), 0]},
        "notes": {
            "caches": caches, "model_s": t_model - t_start,
            "reference_s": t_ref - t_model, "reference_loss": ref_loss,
            "first_loss": losses[0], "last_losses": losses[-5:],
            "warm_compiles": warm_compiles, "window_compiles": compiles,
            "rehearse": rehearse},
    }
