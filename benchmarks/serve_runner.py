"""The serving hot path, measured: ``GenerationServer.submit_generate``
under an open or a closed loop.

Mostly what ``chip_smoke.py`` proved on the chip in PR 21 (seeded
model, a pool of a planned size, ``CompileCounter``, parity against the
full forward), with the load generator in place of its four requests
and a window in place of its one pass.

Set-up, in order: model from the seed; server with the pool size the
configuration file fixes; the executables this cell's traffic can
dispatch, replayed from a manifest the runner writes from the file's
``warm`` lists (``GenerationServer.warmup_from_manifest``, the
program's own restart path: cold prefills of each row and sequence
bucket and the decode step, and nothing else); the load's warm-up
(``warmup_s`` of arrivals, or the first ``open_after_completions``).
Then the window. Then, outside it: the end of the load, the page
accounting, and the parity of a seeded sample of completed requests
against the configuration's plain reference.

Nothing here names an architecture: the configuration file says which
class to build (``model.class`` over ``model.preset``), in which dtype
to serve it (``serve.weights_dtype``) and which reference judges it
(``reference``, handed in by ``run.py``).
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

from . import common, loadgen, xplane

PARITY_TOL = 0.0625   # x the reference logits' std: bf16 passes of
#                       default-precision f32 matmuls (chip_smoke, PR 21)


def parity_tol(serve: dict) -> float:
    """``PARITY_TOL``, unless the configuration states
    ``serve.parity_tol`` and, in ``parity_tol_why``, the readings it
    was set from."""
    if "parity_tol" not in serve:
        return PARITY_TOL
    if not serve.get("parity_tol_why"):
        raise ValueError("serve.parity_tol needs a serve.parity_tol_why")
    return float(serve["parity_tol"])


def write_manifest(path: str, serve: dict, pages_per_seq: int):
    """The signatures ``serve['warm']`` names, in the format
    ``compile_cache.WarmupManifest`` reads."""
    mb = int(serve["max_batch"])
    entries = [{"site": "generate_decode", "feeds": [
        [[mb], "int64"], [[mb], "int32"], [[mb], "bool"], [[mb], "int32"],
        [[mb, pages_per_seq], "int32"]]}]
    for seq in serve["warm"]["prefill_seq"]:
        for rows in serve["warm"]["prefill_rows"]:
            entries.append({"site": "generate_prefill", "feeds": [
                [[rows, seq], "int64"], [[rows], "int32"],
                [[rows, pages_per_seq], "int32"]]})
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump({"version": 1, "entries": entries}, f)
    return len(entries)


def parity(reference, model, cfg, reqs, pad_to: int, n_pos: int, *,
           control: bool, seed: int) -> dict:
    """Every served token of ``reqs`` against the plain reference's
    logits at its position: how far below the reference's best the
    served token's logit lies, over the logits' std. Sequences are
    padded to one length (a causal model's earlier positions do not see
    the padding), so the reference compiles once. With ``control`` the
    same gap is also read for two stand-ins for the served token at
    each of those positions: the token that the reference's
    ``control_logits`` (one precision step down) puts first, and
    another token id drawn from ``seed`` (a token altered where it is
    produced; the least gap any of them reads)."""
    from paddle_tpu.jit.functional import state_arrays
    params, _ = state_arrays(model)
    gaps, control_gaps, altered_gaps = [], [], []   # over the logits' std
    rng = np.random.default_rng([int(seed), 0xA17E])
    for req in reqs:
        n, served = len(req.prompt), np.asarray(req.tokens, np.int64)
        ids = np.zeros((1, pad_to), np.int64)
        ids[0, :n] = req.prompt
        ids[0, n:n + len(served) - 1] = served[:-1]
        # position t's logits predict token t + 1
        positions = np.arange(n - 1, n - 1 + len(served))
        # one fixed count of positions too, for the same reason
        pos_pad = np.resize(positions, max(n_pos, len(positions)))
        lg = np.asarray(reference.logits(
            params, ids, cfg, positions=pos_pad))[0][:len(served)]
        rows = np.arange(len(served))
        gaps.extend((lg.max(-1) - lg[rows, served]) / lg.std())
        if control:
            low = np.asarray(reference.control_logits(
                params, ids, cfg, positions=pos_pad))[0][:len(served)]
            control_gaps.extend(
                (lg.max(-1) - lg[rows, low.argmax(-1)]) / lg.std())
            other = (served + rng.integers(1, lg.shape[-1], len(served))
                     ) % lg.shape[-1]           # never the served token
            altered_gaps.extend((lg.max(-1) - lg[rows, other]) / lg.std())
    out = {"worst_gap_over_std": float(max(gaps, default=0.0)),
           "tokens_checked": len(gaps), "requests_checked": len(reqs)}
    if control:
        out["control_gap_over_std"] = float(max(control_gaps, default=0.0))
        out["altered_token_gap_over_std"] = float(
            min(altered_gaps, default=0.0))
    return out


def run(cell: dict, config: dict, traffic: dict, *, seed: int,
        seconds: float, trace: bool, t_start: float, rehearse: bool,
        reference, control: bool = False) -> dict:
    caches = common.place_caches()
    import jax
    counter = common.CompileCounter()

    import paddle_tpu as paddle
    from paddle_tpu import compile_cache as cc
    from paddle_tpu.serving.generation import GenerationServer

    serve = config["serve"]
    tol = parity_tol(serve)
    paddle.seed(int(seed) % (2 ** 31 - 1))
    cfg, model = common.build_model(config, "serve")
    # the program's own cast; the pool follows the model's dtype
    model.to(dtype=serve["weights_dtype"])
    model.eval()
    t_model = time.perf_counter()

    srv = GenerationServer(
        model, max_batch=serve["max_batch"], page_size=serve["page_size"],
        num_pages=serve["num_pages"], max_seq_len=serve["max_seq_len"],
        seq_buckets=serve["seq_buckets"],
        queue_capacity=serve["queue_capacity"],
        prefix_cache=serve["prefix_cache"],
        name="bench-" + cell["name"], start=False)
    manifest = os.path.join(common.scratch_dir(cell["name"]), "warmup.json")
    n_warm = write_manifest(manifest, serve, srv.pages_per_seq)
    fresh = srv.warmup_from_manifest(manifest)
    t_warm = time.perf_counter()
    warm_compiles = counter.snapshot()
    srv.start()

    schedule = loadgen.make_schedule(traffic, seed=seed, seconds=seconds,
                                     vocab_size=cfg.vocab_size)
    gen = loadgen.LoadGenerator(
        lambda prompt, max_new: srv.submit_generate(
            prompt, max_new_tokens=max_new), schedule, traffic)
    gen.start(time.perf_counter(), seconds)
    if traffic["kind"] == "closed":
        if not gen.wait_completed(int(traffic["open_after_completions"]),
                                  timeout=300):
            raise RuntimeError("the closed loop's warm-up never completed")
        gen.t0 = time.perf_counter()
    else:
        time.sleep(max(0.0, gen.t0 - time.perf_counter()))

    # ------------------------------------------------------ the window
    t0 = gen.t0
    setup_s = t0 - t_start
    snap0, compiles0 = srv.metrics_snapshot(), counter.snapshot()
    trace_dir = None
    if trace:
        lead = max(0.0, (seconds - float(traffic["trace_s"])) / 2)
        time.sleep(max(0.0, t0 + lead - time.perf_counter()))
        trace_dir = common.start_trace(cell["name"])
        with jax.profiler.TraceAnnotation(xplane.WINDOW_ANNOTATION):
            time.sleep(float(traffic["trace_s"]))
        jax.profiler.stop_trace()
    time.sleep(max(0.0, t0 + seconds / 2 - time.perf_counter()))
    queue_mid = int(srv.queue_depth)
    time.sleep(max(0.0, t0 + seconds - time.perf_counter()))
    t1 = time.perf_counter()
    snap1, compiles = srv.metrics_snapshot(), counter.since(compiles0)
    queue_end = int(srv.queue_depth)

    # -------------------------------------------- after it, outside it
    in_window = [r for r in schedule if r.due is not None
                 and 0.0 <= r.due < seconds]
    if traffic["kind"] == "open":
        gen.wait_first_tokens(in_window, float(traffic["drain_s"]))
    gen.stop()          # what the server aborts from here on is ours
    srv.shutdown(drain=False, timeout=60)
    still_running = gen.join(timeout=30)
    srv.kv.assert_no_leaks()
    srv.clear_prefix_cache()
    pages_held = int(srv.kv.used_pages)
    memory_peak = common.device_record()["memory_peak_bytes"]

    done = [r for r in gen.sent if r.finish == "complete"
            and len(r.tokens) == r.max_new]
    rng = np.random.default_rng([int(seed), 0x9A91])
    picks = [done[i] for i in sorted(rng.choice(
        len(done), size=min(int(traffic["parity_sample"]), len(done)),
        replace=False))] if done else []
    par = parity(reference, model, cfg, picks,
                 int(traffic["parity_pad_to"]),
                 int(traffic["output_len"]["max"]), control=control,
                 seed=seed)

    if traffic["kind"] == "open":
        attempted = len(in_window)
        failed = sum(1 for r in in_window if not r.token_times)
    else:
        attempted = len(gen.sent)
        failed = sum(1 for r in gen.sent
                     if r.finish in ("failed", "refused"))
    short = [r for r in gen.sent if r.finish == "complete"
             and len(r.tokens) != r.max_new]
    checks = {
        "no_compile_in_window": compiles["backend_compiles"] == 0
        and snap1["compile_cache"]["misses"] == snap0["compile_cache"]["misses"],
        "no_page_leaked": pages_held == 0,
        "parity": bool(picks) and par["worst_gap_over_std"] <= tol,
        "streams_whole": not short,
        "clients_ended": still_running == 0,
    }
    return {
        "kind": traffic["kind"], "cell": cell, "config": config,
        "traffic": traffic, "model_cfg": cfg, "seconds": t1 - t0,
        "t0": t0, "t1": t1, "setup_s": setup_s,
        "requests": list(gen.sent), "in_window": in_window,
        "snap0": snap0, "snap1": snap1, "max_batch": srv.max_batch,
        "trace_dir": trace_dir,
        "gap_default": "engine thread, unattributed",
        "attempted": attempted, "failed": failed,
        "correct": all(checks.values()), "checks": checks,
        "parity": par, "memory_peak_bytes": memory_peak,
        "compared": {
            "parity_gap_over_std": [par["worst_gap_over_std"], tol],
            "window_compiles": [compiles["backend_compiles"], 0],
            "pages_held": [pages_held, 0],
            "streams_short": [len(short), 0],
            "clients_running": [still_running, 0]}
        | ({"control_gap_over_std": [par["control_gap_over_std"], tol],
            "altered_token_gap_over_std": [
                par["altered_token_gap_over_std"], tol]}
           if control else {}),
        "notes": {
            "caches": caches, "model_s": t_model - t_start,
            "warm_s": t_warm - t_model, "warm_signatures": n_warm,
            "warm_fresh": int(fresh), "warm_compiles": warm_compiles,
            "aot_cache": cc.stats(),
            "window_compiles": compiles, "pool_pages": srv.kv.num_pages,
            "pool_bytes": int(srv.kv.pool_bytes()),
            "queue_mid": queue_mid, "queue_end": queue_end,
            "offered_per_s": len(in_window) / seconds,
            "completed_per_s": sum(
                1 for r in gen.sent if r.finish == "complete"
                and t0 <= r.token_times[-1] < t1) / seconds,
            "rate_per_s": float(os.environ.get(loadgen.RATE_ENV)
                                or traffic.get("rate_per_s", 0)),
            "submit_max_ms": 1e3 * max(
                (r.submit_s or 0.0 for r in gen.sent), default=0.0),
            "late_max_ms": 1e3 * max(
                (r.sent - (t0 + r.due) for r in in_window
                 if r.sent is not None), default=0.0),
            "rehearse": rehearse},
    }
