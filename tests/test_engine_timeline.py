"""The engine thread's timeline (PR 25): ``engine::<phase>`` spans on
the profiler's clock, cumulative phase sums and dispatch counters in
``metrics_snapshot()["engine"]``, tail histograms read from the
difference of two snapshots, and the named scopes round paged and
flash attention."""
import bisect
import gc
import glob
import os
import re
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import GPTForCausalLM, gpt_tiny
from paddle_tpu.serving.generation import GenerationServer
from paddle_tpu.serving.generation.engine import PHASES, DecodeMetrics

PROMPT_LENS = (3, 5, 9, 12)
MAX_NEW = 5
QUEUED_S = 0.05     # what lockstep_run's requests wait before the loop


def make_model():
    paddle.seed(0)
    m = GPTForCausalLM(gpt_tiny(use_flash_attention=False))
    m.eval()
    return m


def make_server(model, **kw):
    kw.setdefault("max_batch", 4)
    return GenerationServer(model, page_size=4, max_seq_len=64,
                            seq_buckets=[8, 16, 32, 64],
                            prefix_cache=False, start=False, **kw)


def prompts():
    return [np.arange(1, n + 1) for n in PROMPT_LENS]


def reference_greedy(model, prompt, n_new):
    """Greedy decoding by the full forward, no cache."""
    ids = [int(t) for t in prompt]
    for _ in range(n_new):
        logits = model(paddle.to_tensor(np.asarray([ids], np.int64)))
        ids.append(int(np.asarray(logits.numpy())[0, -1].argmax()))
    return ids[len(prompt):]


# --------------------------------------------- counters, hand-counted
@pytest.fixture(scope="module")
def lockstep_run():
    """Four prompts queued BEFORE the loop starts, so the first
    iteration admits all of them: two prefill groups (buckets 8 and
    16, two rows each), then four decode iterations in lockstep. The
    spans as the in-process tracer keeps them, with their arguments;
    all of them Python-side (the native recorder would keep those
    without arguments apart, under the kernel's thread ids)."""
    from paddle_tpu import profiler
    model = make_model()
    srv = make_server(model)
    srv.warmup()
    futs = [srv.submit_generate(p, max_new_tokens=MAX_NEW)
            for p in prompts()]
    time.sleep(QUEUED_S)
    snap0 = srv.metrics_snapshot()
    tracer, kept = profiler._HostTracer(), profiler._tracer
    tracer._native = False
    profiler._tracer = tracer
    try:
        with profiler.Profiler(timer_only=True):
            srv.start()
            tokens = [f.result(120) for f in futs]
            snap1 = srv.metrics_snapshot()
            srv.shutdown()
    finally:
        profiler._tracer = kept
    # (a span that enqueues nothing carries no arguments)
    calls = [e["args"] for e in tracer.events
             if e["name"] == "engine::decode_call" and "args" in e]
    spans = [(e["name"], e["ts"], e["ts"] + e["dur"], e["tid"])
             for e in tracer.events]
    return {"model": model, "tokens": tokens, "snap0": snap0,
            "snap1": snap1, "decode_calls": calls, "spans": spans}


# every prompt's first token comes from its prefill, the other four
# from decode steps whose attention reads the prompt plus what was
# generated so far, the position just written among them
HAND_COUNTED = {
    # decode iterations are counted where they were: beside the
    # occupancy, which engine_loop_ms and engine_host_ms divide by
    "batch_occupancy.steps": MAX_NEW - 1,
    "engine.prefill.prompt_tokens": sum(PROMPT_LENS),
    "engine.prefill.padded_tokens": 2 * 8 + 2 * 16,
    "engine.prefill.by_shape": {"2x8": 1, "2x16": 1},
    # a histogram's last cumulative count (+Inf) is its count
    "engine.queue_wait_ms.counts.-1": len(PROMPT_LENS),
    # the first iteration began with no live stream: it stalls nobody
    "engine.stream_stall_ms.counts.-1": MAX_NEW - 2,
    # every program chose its rows' tokens: an int32 a row is all that
    # came to the host (two rows a prefill, four lanes a step; no
    # expert counts)
    "engine.fetch_bytes": 4 * (2 * 2 + (MAX_NEW - 1) * 4),
    # the two prefill groups and the decode steps, each enqueued and
    # harvested once; the warm-up's programs are not counted
    "engine.dispatch.prefill.enqueued": 2,
    "engine.dispatch.prefill.harvested": 2,
    "engine.dispatch.decode.enqueued": MAX_NEW - 1,
    "engine.dispatch.decode.harvested": MAX_NEW - 1,
}


@pytest.mark.parametrize("key", sorted(HAND_COUNTED))
def test_engine_counters_equal_hand_counted(lockstep_run, key):
    got = lockstep_run["snap1"]
    before = lockstep_run["snap0"]
    for part in key.split("."):
        part = int(part) if part.lstrip("-").isdigit() else part
        got = got[part]
        # (a kind of program the server has not run has no entry yet)
        if isinstance(before, dict):
            before = before.get(part, 0)
        elif before != 0:
            before = before[part]
    assert got == HAND_COUNTED[key]
    # cumulative since the server started
    assert before in (0, {})


def test_decode_call_span_carries_the_enqueued_steps_arguments():
    """The loop runs a step ahead (PR 36): ``engine::decode_call``
    opens, the NEXT step is enqueued, the step in flight is harvested,
    the span closes. Its arguments are those of the step it enqueues,
    also where that step has a lane more than the one it harvests; the
    span that only harvests says nothing; ``step_ms["decode"]`` and the
    step count advance once a harvested step."""
    srv = make_server(make_model(), max_batch=2)
    srv.warmup()
    log = []
    runner = srv._runners[0]
    enter, phase = srv._enter_decode_call, srv._enter_phase
    enqueue, harvest = runner.enqueue, runner.harvest

    def spy_enter(active, ctx_after, stall_t0):
        log.append(("span", len(active), int(ctx_after.sum())))
        return enter(active, ctx_after, stall_t0)

    def spy_phase(name, **args):
        if name == "decode_call" and not args and log[-1][0] != "span":
            log.append(("span", None, None))
        return phase(name, **args)

    def spy_enqueue(kind, feeds):
        if kind == "decode":
            log.append(("enqueue", int(feeds[2].sum()), int(feeds[3].sum())))
        return enqueue(kind, feeds)

    def spy_harvest(step, host_logits=False):
        if step.signature[0][0] == (srv.max_batch,):
            log.append(("harvest",))
        return harvest(step, host_logits)

    srv._enter_decode_call, srv._enter_phase = spy_enter, spy_phase
    runner.enqueue, runner.harvest = spy_enqueue, spy_harvest
    srv.start()
    # (long enough that the second surely joins it, however slow the
    # test's own thread is)
    first = srv.submit_generate(np.arange(1, 4), max_new_tokens=48)
    while len(first.tokens()) < 3:
        time.sleep(0.002)
    second = srv.submit_generate(np.arange(1, 6), max_new_tokens=3)
    assert len(first.result(60)) == 48 and len(second.result(60)) == 3
    srv.shutdown()
    snap = srv.metrics_snapshot()
    spans = [i for i, e in enumerate(log) if e[0] == "span"]
    for at, end in zip(spans, spans[1:] + [len(log)]):
        _, active, context = log[at]
        inside = log[at + 1:end]
        if active is None:      # nothing left to enqueue: the harvest
            assert inside == [("harvest",)]
            continue
        # the step it enqueues, by its own feeds, before any harvest
        assert inside[0] == ("enqueue", active, context)
        assert inside[1:] in ([], [("harvest",)])
    enqueued = [e for e in log if e[0] == "enqueue"]
    assert {e[1] for e in enqueued} == {1, 2}    # the second lane joined
    # the first step with two lanes was enqueued with one in flight
    joined = log.index(next(e for e in enqueued if e[1] == 2))
    assert log[joined + 1] == ("harvest",) and log[joined - 1][1] == 2
    steps = len(enqueued)
    assert steps == len([e for e in log if e == ("harvest",)])
    assert snap["batch_occupancy"]["steps"] == steps
    assert snap["step_ms"]["decode"]["count"] == steps
    assert snap["engine"]["run_ahead"]["ahead"] \
        + snap["engine"]["run_ahead"]["drained"] == steps


@pytest.mark.parametrize("step", range(MAX_NEW - 1))
def test_decode_call_span_says_what_its_attention_reads(lockstep_run, step):
    """``active`` live lanes and ``context_tokens`` cached positions:
    each prompt plus what was generated so far, the position the step
    writes among them."""
    assert len(lockstep_run["decode_calls"]) == MAX_NEW - 1
    assert lockstep_run["decode_calls"][step] == {
        "active": len(PROMPT_LENS),
        "context_tokens": sum(n + step + 1 for n in PROMPT_LENS)}


def test_queue_wait_counts_from_submit_to_slot(lockstep_run):
    """Every request waited for the loop to start, and none for
    longer than the run took."""
    hist = lockstep_run["snap1"]["engine"]["queue_wait_ms"]
    first = next(le for le, n in zip(hist["le"], hist["counts"]) if n)
    last = next(le for le, n in zip(hist["le"], hist["counts"])
                if n == hist["counts"][-1])
    assert first >= QUEUED_S * 1e3
    assert last < 120e3


def test_only_read_counters_are_kept(lockstep_run):
    eng = lockstep_run["snap1"]["engine"]
    # "moe" joins them for a model with expert layers (PR 29)
    # "run_ahead" (PR 36): decode_run_ahead_pct.backlog reads it
    # "loop_cpu_s" and "dispatch" (PR 37): engine_host_cpu_pct and the
    # decode_enqueue/launch/harvest_wait readers read them
    assert set(eng) == {"loop_s", "loop_cpu_s", "prefill", "kv",
                        "stream_stall_ms", "queue_wait_ms", "fetch_bytes",
                        "dispatch", "run_ahead"}
    assert set(eng["dispatch"]) == {"prefill", "decode"}
    assert all(set(d) == {"enqueued", "enqueue_s", "launch_s",
                          "harvested", "harvest_s"}
               for d in eng["dispatch"].values())
    assert set(eng["run_ahead"]) == {"ahead", "drained", "late_lanes"}
    assert set(eng["prefill"]) == {"prompt_tokens", "padded_tokens",
                                   "split_groups", "by_shape",
                                   "call_s_by_shape"}
    # "pool_bytes" (by kind, PR 35): state_mib_per_slot.hybrid reads it
    assert set(eng["kv"]) == {"pages_in_use", "capacity", "pool_bytes",
                              "window_pages_recycled"}
    assert set(eng["kv"]["capacity"]) == {"full"}
    assert set(eng["queue_wait_ms"]) == {"le", "counts"}


def test_prefill_call_time_is_kept_by_shape(lockstep_run):
    eng = lockstep_run["snap1"]["engine"]
    by_shape = eng["prefill"]["call_s_by_shape"]
    assert by_shape.keys() == HAND_COUNTED[
        "engine.prefill.by_shape"].keys()
    assert all(s > 0 for s in by_shape.values())
    # a group's decoder call lies inside its engine::prefill phase
    assert sum(by_shape.values()) <= eng["loop_s"]["prefill"]
    assert lockstep_run["snap0"]["engine"]["prefill"][
        "call_s_by_shape"] == {}


# ------------------------- inside the decode call (PR 37): the halves
def test_dispatch_counts_what_the_loop_enqueued_and_harvested(lockstep_run):
    snap = lockstep_run["snap1"]
    decode, ahead = snap["engine"]["dispatch"]["decode"], \
        snap["engine"]["run_ahead"]
    assert decode["enqueued"] == ahead["ahead"] + ahead["drained"]
    assert decode["harvested"] == snap["batch_occupancy"]["steps"]


@pytest.mark.parametrize("kind", ["prefill", "decode"])
def test_the_launch_lies_inside_the_enqueue(lockstep_run, kind):
    d = lockstep_run["snap1"]["engine"]["dispatch"][kind]
    assert 0 <= d["launch_s"] <= d["enqueue_s"]
    assert d["harvest_s"] >= 0


@pytest.mark.parametrize("kind,phase", [("prefill", "prefill"),
                                        ("decode", "decode_call")])
def test_the_halves_lie_inside_their_phase(lockstep_run, kind, phase):
    eng = lockstep_run["snap1"]["engine"]
    d = eng["dispatch"][kind]
    assert d["enqueue_s"] + d["harvest_s"] <= eng["loop_s"][phase]


def test_warmup_adds_nothing(lockstep_run):
    """The warm-up ran every program the traffic runs, and none of
    them is counted."""
    eng = lockstep_run["snap0"]["engine"]
    assert eng["dispatch"] == {} and eng["fetch_bytes"] == 0


@pytest.mark.parametrize("phase", PHASES)
def test_loop_cpu_time_is_at_most_its_wall_time(lockstep_run, phase):
    """Each phase's CPU time, the open one's too (snap1 is taken while
    the loop runs, on another thread), is no larger than its wall
    time, give or take the two clocks' readings."""
    for snap in (lockstep_run["snap0"], lockstep_run["snap1"]):
        eng = snap["engine"]
        assert set(eng["loop_cpu_s"]) == set(eng["loop_s"])
        assert 0 <= eng["loop_cpu_s"][phase] <= eng["loop_s"][phase] + 1e-3


def test_runner_spans_nest_in_the_engine_phases(lockstep_run):
    """Every ``runner::enqueue`` starts inside an ``engine::decode_call``
    (a decode step) or an ``engine::prefill`` span and holds one
    ``decoder::launch``; every ``runner::harvest`` starts inside one of
    the two too. All on the loop's thread."""
    spans = lockstep_run["spans"]
    dispatch = lockstep_run["snap1"]["engine"]["dispatch"]

    def named(name):
        return [s for s in spans if s[0] == name]

    def held(events, phase):
        holders = named("engine::" + phase)
        return [e for e in events
                if any(h[1] <= e[1] < h[2] for h in holders)]
    enqueues, harvests = named("runner::enqueue"), named("runner::harvest")
    for kind, phase in (("decode", "decode_call"), ("prefill", "prefill")):
        assert len(held(enqueues, phase)) == dispatch[kind]["enqueued"]
        assert len(held(harvests, phase)) == dispatch[kind]["harvested"]
    assert len(enqueues) == len(harvests) == 2 + MAX_NEW - 1
    launches = named("decoder::launch")
    for e in enqueues:
        assert sum(1 for ln in launches
                   if e[1] <= ln[1] and ln[2] <= e[2]) == 1
    assert len(launches) == len(enqueues)
    assert len({s[3] for s in enqueues + harvests
                + named("engine::decode_call")}) == 1


@pytest.mark.parametrize("i", range(len(PROMPT_LENS)))
def test_greedy_streams_equal_the_uncached_forward(lockstep_run, i):
    """The spans and scopes changed no token: each stream is what
    greedy decoding by the full forward gives."""
    want = reference_greedy(lockstep_run["model"], prompts()[i], MAX_NEW)
    assert lockstep_run["tokens"][i] == want


# ------------------------------------- the phases cover the wall time
def test_phases_sum_to_the_loop_threads_wall_time():
    srv = make_server(make_model())
    srv.warmup()
    srv.start()
    try:
        time.sleep(0.05)                      # the loop is up and idle
        snap0, t0 = srv.metrics_snapshot(), time.perf_counter()
        stop = time.perf_counter() + 1.5

        def client(k):
            rng = np.random.default_rng(k)
            while time.perf_counter() < stop:
                n = int(rng.integers(3, 30))
                srv.generate(rng.integers(1, 100, n),
                             max_new_tokens=int(rng.integers(2, 12)))
        threads = [threading.Thread(target=client, args=(k,))
                   for k in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        assert not any(t.is_alive() for t in threads)
        snap1, t1 = srv.metrics_snapshot(), time.perf_counter()
    finally:
        srv.shutdown()
    loop0, loop1 = snap0["engine"]["loop_s"], snap1["engine"]["loop_s"]
    assert set(loop1) == set(PHASES)
    by_phase = {p: loop1[p] - loop0[p] for p in PHASES}
    assert all(v >= 0 for v in by_phase.values())
    assert sum(by_phase.values()) == pytest.approx(t1 - t0, rel=0.01)
    # a closed load keeps the engine busy: every working phase ran
    for phase in PHASES:
        if phase != "wait":
            assert by_phase[phase] > 0, phase
    assert snap1["batch_occupancy"]["steps"] \
        > snap0["batch_occupancy"]["steps"]


def test_a_stopped_loop_adds_no_time():
    srv = make_server(make_model())
    srv.start()
    srv.generate(np.arange(1, 6), max_new_tokens=3)
    srv.shutdown()
    a = srv.metrics_snapshot()["engine"]["loop_s"]
    time.sleep(0.05)
    b = srv.metrics_snapshot()["engine"]["loop_s"]
    assert a == b


# ------------------------------- tails from two cumulative snapshots
def window_quantile(snap0: dict, snap1: dict, q: float) -> float:
    """Upper bound of the bucket in which the q-th percentile of the
    observations between the two snapshots lies."""
    counts = [b - a for a, b in zip(snap0["counts"], snap1["counts"])]
    rank = int(np.ceil(q / 100.0 * counts[-1]))
    i = bisect.bisect_left(counts, max(rank, 1))
    return snap1["le"][i] if i < len(snap1["le"]) else float("inf")


@pytest.fixture(scope="module")
def histogram_window():
    from paddle_tpu.observability.registry import MetricRegistry
    dm = DecodeMetrics("hist-test", 4, 16, registry=MetricRegistry())
    rng = np.random.default_rng(7)
    for v in rng.lognormal(1.0, 1.0, 500):    # before the window
        dm.observe_stream_stall(v)
    dm.observe_queue_wait(list(rng.lognormal(-3.0, 1.0, 300)))
    snap0 = dm.snapshot()["engine"]
    stalls = rng.lognormal(3.0, 1.2, 2000)               # milliseconds
    waits = rng.lognormal(-2.0, 1.5, 1000)               # seconds
    for v in stalls:
        dm.observe_stream_stall(v)
    dm.observe_queue_wait(list(waits))
    return {"snap0": snap0, "snap1": dm.snapshot()["engine"],
            "stream_stall_ms": stalls, "queue_wait_ms": waits * 1e3}


@pytest.mark.parametrize("q", (50, 90, 99))
@pytest.mark.parametrize("name", ("stream_stall_ms", "queue_wait_ms"))
def test_window_quantile_matches_exact_within_a_bucket(histogram_window,
                                                       name, q):
    h = histogram_window
    got = window_quantile(h["snap0"][name], h["snap1"][name], q)
    xs = np.sort(h[name])
    exact = xs[int(np.ceil(q / 100.0 * len(xs))) - 1]    # nearest rank
    assert exact <= got <= exact * 1.05 * (1 + 1e-9)


def test_tail_buckets_are_fine_and_cover_the_range(histogram_window):
    le = histogram_window["snap1"]["stream_stall_ms"]["le"]
    assert le[0] <= 0.05 and le[-1] >= 60e3
    assert max(b / a for a, b in zip(le, le[1:])) <= 1.05 + 1e-9
    counts = histogram_window["snap1"]["stream_stall_ms"]["counts"]
    assert len(counts) == len(le) + 1                    # +Inf last
    assert counts == sorted(counts)                      # cumulative


# --------------------------------- spans on the profiler's own clock
@pytest.fixture(scope="module")
def profiled_run(tmp_path_factory):
    """A one-second jax.profiler session over a serving engine and a
    RecordEvent of the test's own; host events by thread line."""
    import jax
    from jax.profiler import ProfileData

    from paddle_tpu.profiler import RecordEvent
    model = make_model()
    srv = make_server(model)
    srv.warmup()
    srv.start()
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with RecordEvent("test::outer", args={"rows": 3}) as ev:
            ev.set_arg("late", 7)
            tokens = [srv.generate(p, max_new_tokens=MAX_NEW)
                      for p in prompts()]
            gc.collect()                      # a python::gc span here
        time.sleep(0.3)                       # some engine::wait
    finally:
        jax.profiler.stop_trace()
        srv.shutdown()
    path = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    lines = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            evs = [(e.name, dict(e.stats)) for e in line.events
                   if "::" in e.name]
            if evs:
                lines[(plane.name, i, line.name)] = evs
    return {"lines": lines, "tokens": tokens, "model": model}


@pytest.mark.parametrize("phase", PHASES)
def test_profile_holds_every_engine_phase_on_one_line(profiled_run, phase):
    holders = [key for key, evs in profiled_run["lines"].items()
               if any(n.startswith("engine::") for n, _ in evs)]
    assert len(holders) == 1, holders          # the engine's thread
    names = {n for n, _ in profiled_run["lines"][holders[0]]}
    assert "engine::" + phase in names


def test_annotation_carries_args_and_late_args(profiled_run):
    evs = [(n, s) for line in profiled_run["lines"].values()
           for n, s in line if n == "test::outer"]
    assert len(evs) == 1
    stats = {k: str(v) for k, v in evs[0][1].items()}
    assert stats["rows"] == "3" and stats["late"] == "7"
    # the engine's spans run on their own thread's line
    engine_line = next(k for k, v in profiled_run["lines"].items()
                       if any(n.startswith("engine::") for n, _ in v))
    outer_line = next(k for k, v in profiled_run["lines"].items()
                      if any(n == "test::outer" for n, _ in v))
    assert engine_line != outer_line


@pytest.mark.parametrize("name", ["runner::enqueue", "decoder::launch",
                                  "runner::harvest"])
def test_profile_holds_the_runner_spans_on_the_engines_line(profiled_run,
                                                            name):
    line = next(v for v in profiled_run["lines"].values()
                if any(n.startswith("engine::") for n, _ in v))
    assert name in {n for n, _ in line}


def test_a_full_collection_is_a_span_on_the_collecting_thread(
        profiled_run):
    """``gc.collect()`` inside ``test::outer``: its ``python::gc`` span
    lies on that span's line."""
    line = next(v for v in profiled_run["lines"].values()
                if any(n == "test::outer" for n, _ in v))
    assert "python::gc" in {n for n, _ in line}


def test_younger_generations_open_no_gc_span():
    from paddle_tpu import profiler
    profiler._on_gc("start", {"generation": 0})
    profiler._on_gc("start", {"generation": 1})
    assert profiler._on_gc._annotation is None
    profiler.trace_full_collections()           # as every server asks
    profiler.trace_full_collections()
    assert gc.callbacks.count(profiler._on_gc) == 1


def test_decode_call_args_reach_the_profile(profiled_run):
    """One stream at a time: every step has one live lane, and the
    contexts of the session's steps are the hand-counted ones. A span
    carries the arguments of the step it enqueues; a stream's last
    span enqueues nothing (it harvests the last step) and says
    nothing."""
    spans = [s for line in profiled_run["lines"].values()
             for n, s in line if n == "engine::decode_call"]
    assert len(spans) == len(PROMPT_LENS) * MAX_NEW
    spans = [s for s in spans if "active" in s]
    assert len(spans) == len(PROMPT_LENS) * (MAX_NEW - 1)
    assert {int(s["active"]) for s in spans} == {1}
    assert sorted(int(s["context_tokens"]) for s in spans) == sorted(
        n + i for n in PROMPT_LENS for i in range(1, MAX_NEW))


def test_streams_under_a_profiler_session_are_unchanged(profiled_run):
    for got, p in zip(profiled_run["tokens"], prompts()):
        assert got == reference_greedy(profiled_run["model"], p, MAX_NEW)


def test_record_event_enters_no_named_scope(monkeypatch):
    import jax

    from paddle_tpu.profiler import RecordEvent

    def boom(*a, **k):
        raise AssertionError("RecordEvent entered a named scope")
    monkeypatch.setattr(jax, "named_scope", boom)
    seen = []

    class Spy(jax.profiler.TraceAnnotation):
        def __init__(self, name, **kw):
            seen.append((name, kw))
            super().__init__(name, **kw)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Spy)
    with RecordEvent("test::span", args={"k": 1}):
        pass
    assert seen == [("test::span", {"k": 1})]


def test_train_step_records_a_span():
    from paddle_tpu import profiler
    from paddle_tpu.jit import TrainStep
    paddle.seed(0)
    net = paddle.nn.Linear(4, 2)
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=net.parameters())
    step = TrainStep(net, lambda out, y: ((out - y) ** 2).mean(), opt)
    x = paddle.to_tensor(np.ones((3, 4), np.float32))
    y = paddle.to_tensor(np.zeros((3, 2), np.float32))
    seen, sink = [], profiler._span_sink
    profiler.set_span_sink(lambda name, ms: seen.append((name, ms)))
    try:
        step(x, y)
        step(x, y)
    finally:
        profiler.set_span_sink(sink)
    steps = [ms for name, ms in seen if name == "train::step"]
    assert len(steps) == 2 and all(ms > 0 for ms in steps)


# ------------------------------------------- stepprof envelope fields
@pytest.mark.parametrize("kind", ("prefill", "decode"))
def test_engine_envelopes_call_host_time_wall_time(kind):
    """An engine envelope's time is the host's time of the decoder call
    and its fetch, said once, as ``wall_ms``: nothing there knows the
    device's own time, and no second field repeats the first."""
    from paddle_tpu.observability.stepprof import default_profiler
    srv = make_server(make_model())
    srv.start()
    srv.generate(np.arange(1, 6), max_new_tokens=3)
    srv.shutdown()
    envs = default_profiler().envelopes(kind=kind, limit=2)
    assert envs
    for env in envs:
        assert "device_ms" not in env and "host_ms" not in env
        assert env["wall_ms"] > 0 and env["occupancy"] == 1


# ------------------------------------------------ scopes in the HLO
@pytest.fixture(scope="module")
def lowered_programs():
    """Optimized HLO text of the decode and prefill programs."""
    srv = make_server(make_model(), max_batch=2)
    dec = srv.decoder
    b, p = srv.max_batch, srv.pages_per_seq
    decode_args = (dec._params, dec._buffers, np.zeros(b, np.int64),
                   np.zeros(b, np.int32), np.zeros(b, bool),
                   np.zeros(b, np.int32), np.zeros((b, p), np.int32),
                   np.zeros(b, np.float32), np.zeros(b, np.float32),
                   srv.kv.k, srv.kv.v)
    prefill_args = (dec._params, dec._buffers, np.zeros((b, 8), np.int64),
                    np.zeros(b, np.int32), np.zeros((b, p), np.int32),
                    np.zeros(b, np.float32), np.zeros(b, np.float32),
                    srv.kv.k, srv.kv.v)
    return {"decode": dec._decode_jit.lower(*decode_args).compile()
            .as_text(),
            "prefill": dec._prefill_jit.lower(*prefill_args).compile()
            .as_text()}


@pytest.mark.parametrize("program,scope", [
    ("decode", "paged_attention/kv_write"),
    ("decode", "paged_attention/kv_gather"),
    ("decode", "paged_attention/attend"),
    ("prefill", "paged_attention/kv_write"),
    ("prefill", "paged_attention/attend"),
])
def test_paged_attention_scopes_reach_the_hlo(lowered_programs, program,
                                              scope):
    text = lowered_programs[program]
    names = [ln for ln in text.splitlines() if "op_name=" in ln]
    assert any(scope in ln for ln in names)


def test_prefill_attention_gathers_nothing(lowered_programs):
    assert "paged_attention/kv_gather" not in lowered_programs["prefill"]


@pytest.mark.parametrize("direction", ("forward", "backward"))
def test_flash_attention_scope_reaches_the_hlo(monkeypatch, direction):
    """The Pallas kernel in interpret mode (the CPU's way to run it):
    forward and backward operations carry the scope."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import flash_attention as fa
    monkeypatch.setattr(fa, "preferred", lambda *a, **k: True)
    q = jnp.ones((1, 128, 2, 64), jnp.float32)

    def fwd(q, k, v):
        return fa.attention_bshd(q, k, v, causal=True).sum()
    fn = fwd if direction == "forward" else jax.grad(fwd, argnums=(0, 1, 2))
    text = jax.jit(fn).lower(q, q, q).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    # the backward is traced on its own: under the forward's scope as
    # the transforms rewrote it, then under the one it enters itself
    want = "/flash_attention/" if direction == "forward" \
        else "transpose(jvp(flash_attention))/flash_attention/"
    assert any(want in n for n in names)
