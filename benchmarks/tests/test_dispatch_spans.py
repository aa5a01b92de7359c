"""``dispatch_spans.py`` (PR 37): idle time under the runner's spans
inside the decode call and under ``python::gc`` on hand-made span and
operation lists, the counters' readers on hand-made snapshots, the
readers on a run that has nothing to read, and the loader against a
real (CPU) profile."""
import os

import pytest

from benchmarks import common, dispatch_spans as ds, program_spans as ps
from benchmarks import run, xplane

MANIFEST = common.load_json(os.path.join(common.ROOT, "BENCHMARK.json"))
NEW = [m for m in MANIFEST["per_layer"] if m["name"] in (
    "decode_enqueue_ms.backlog", "decode_launch_ms.backlog",
    "decode_harvest_wait_ms.backlog", "engine_host_cpu_pct.backlog",
    "idle_enqueue_ms_per_step.backlog")]
BACKLOG = ["serve-medium-backlog", "serve-smallthinker-longctx",
           "serve-kexaone-reasoning", "serve-nemotron3-reasoning"]


def hand_made():
    """A window 0..100. The loop (line ``engine#1``) runs two decode
    calls, 10..40 and 50..80; in each the next step's enqueue
    (launch inside it), then the harvest of the one in flight; a
    prefill 85..95 enqueues and harvests a program of its own. The chip
    runs 20..45 and 62..90; a full collection on a client's line
    91..99."""
    loop, no = "engine#1", {}
    spans = [
        ("engine::decode_feeds", 5, 5, loop, no),
        ("engine::decode_call", 10, 30, loop, no),        # 10..40
        ("runner::enqueue", 10, 14, loop, no),            # 10..24
        ("decoder::launch", 16, 6, loop, no),             # 16..22
        ("runner::harvest", 24, 15, loop, no),            # 24..39
        ("engine::sample_emit", 40, 10, loop, no),
        ("engine::decode_call", 50, 30, loop, no),        # 50..80
        ("runner::enqueue", 50, 8, loop, no),             # 50..58
        ("decoder::launch", 52, 4, loop, no),             # 52..56
        ("runner::harvest", 58, 20, loop, no),            # 58..78
        ("engine::prefill", 85, 10, loop, no),            # 85..95
        ("runner::enqueue", 85, 3, loop, no),
        ("decoder::launch", 86, 1, loop, no),
        ("runner::harvest", 88, 6, loop, no),
        # another thread's enqueue inside the call's time: not the loop's
        ("runner::enqueue", 30, 2, "client#7", no),
        ("python::gc", 91, 8, "client#7", no),            # 91..99
    ]
    ops = [(20.0, 45.0), (62.0, 90.0)]
    return {"spans": spans, "window": (0.0, 100.0), "ops": ops}


def test_idle_time_under_the_decode_steps_halves():
    s = ds.summarize(hand_made())
    assert s["decode_calls"] == 2
    assert {k: v["count"] for k, v in s["decode"].items()} == {
        ds.ENQUEUE: 2, ds.LAUNCH: 2, ds.HARVEST: 2}
    assert s["decode"][ds.ENQUEUE]["seconds"] == pytest.approx(22e-9)
    idle = {k: round(v * 1e9) for k, v in s["decode_idle_s"].items()}
    # idle 0..20, 45..62, 90..100: the first enqueue 10..20, the second
    # 50..58; the launches 16..20 and 52..56; the harvests 45..62
    # inside 24..39 and 58..78: none before 45, 58..62 in the second
    assert idle == {ds.ENQUEUE: 10 + 8, ds.LAUNCH: 4 + 4, ds.HARVEST: 4}
    assert s["idle_s"] == pytest.approx(47e-9)
    assert s["window_s"] == pytest.approx(100e-9)


def test_a_full_collection_is_counted_on_any_line():
    s = ds.summarize(hand_made())
    assert s["gc"]["count"] == 1
    assert s["gc"]["longest_ms"] == pytest.approx(8e-6)
    assert s["gc"]["idle_s"] == pytest.approx(8e-9)        # 91..99


def test_the_longest_gaps_say_what_lay_over_them():
    s = ds.summarize(hand_made())
    gaps = s["longest_gaps"]
    assert [round(g["ms"] * 1e6) for g in gaps] == [20, 17, 10]
    first = gaps[0]["under_ms"]                            # 0..20
    assert round(first["engine::decode_call"] * 1e6) == 10
    assert round(first[ds.ENQUEUE] * 1e6) == 10
    last = gaps[2]["under_ms"]                             # 90..100
    assert round(last["python::gc"] * 1e6) == 8
    assert round(last["engine::prefill"] * 1e6) == 5


def test_the_enqueues_idle_is_part_of_the_host_phases_idle():
    """With ``program_spans``' own summary of the same window the
    enqueue's idle time per program is at most the host phases'."""
    trace = hand_made()
    spans_trace = {"spans": trace["spans"], "window": trace["window"],
                   "devices": {"/device:TPU:0": {
                       "ops": [("%f = f32[1] fusion()", s, e - s, "")
                               for s, e in trace["ops"]],
                       "modules": [("jit__decode(1)", 20.0, 25.0),
                                   ("jit__decode(1)", 62.0, 18.0)]}}}
    host = ps.summarize(spans_trace, "decode")
    mine = ds.summarize(trace)
    assert host["decode_programs"] == 2
    run_ = {"_program_spans": host,
            "_dispatch_spans": dict(mine, decode_programs=2)}
    assert ds.idle_enqueue_ms_per_step(run_) == pytest.approx(9e-6)
    assert ds.idle_enqueue_ms_per_step(run_) \
        <= ps.idle_host_ms_per_step(run_)


def snapshot(enqueued, enqueue_s, launch_s, harvested, harvest_s, wall,
             cpu):
    phases = ("admit", "prefill", "decode_feeds", "decode_call",
              "sample_emit", "bookkeeping", "wait")
    return {"engine": {
        "dispatch": {"decode": {
            "enqueued": enqueued, "enqueue_s": enqueue_s,
            "launch_s": launch_s, "harvested": harvested,
            "harvest_s": harvest_s}},
        "loop_s": dict.fromkeys(phases, wall),
        "loop_cpu_s": dict.fromkeys(phases, cpu),
        "stream_stall_ms": {"le": [1.0], "counts": [0, 0]},
        "queue_wait_ms": {"le": [1.0], "counts": [0, 0]}}}


def test_the_counters_readers_take_the_windows_difference():
    run_ = {"snap0": snapshot(10, 0.03, 0.02, 9, 0.01, 1.0, 0.5),
            "snap1": snapshot(110, 0.33, 0.22, 109, 0.11, 2.0, 1.25)}
    assert ds.decode_ms(run_, "enqueue_s", "enqueued") == \
        pytest.approx(3.0)
    assert ds.decode_ms(run_, "launch_s", "enqueued") == \
        pytest.approx(2.0)
    assert ds.decode_ms(run_, "harvest_s", "harvested") == \
        pytest.approx(1.0)
    assert ds.host_cpu_pct(run_) == pytest.approx(75.0)
    share = ds.counters(run_)["decode_halves_share_of_call"]
    assert share == pytest.approx(0.4 / 1.0)


def test_a_window_without_decode_steps_reads_nothing():
    same = snapshot(10, 0.03, 0.02, 9, 0.01, 1.0, 0.5)
    run_ = {"snap0": same, "snap1": same}
    assert ds.decode_ms(run_, "enqueue_s", "enqueued") is None
    assert ds.host_cpu_pct(run_) is None


@pytest.mark.parametrize("entry", NEW, ids=lambda m: m["name"])
def test_new_reader_returns_none_with_nothing_to_read(entry):
    """The parent of PR 37: an ``engine`` section without ``dispatch``
    and ``loop_cpu_s``, and no trace."""
    reader = run.load_reader(MANIFEST, entry["name"])
    eng = snapshot(1, 0.0, 0.0, 1, 0.0, 1.0, 1.0)["engine"]
    del eng["dispatch"], eng["loop_cpu_s"]
    parent = {"snap0": {"engine": eng}, "snap1": {"engine": eng},
              "seconds": 1.0, "trace_dir": None, "traffic": {},
              "cell": {"name": "none"}}
    assert reader.read(parent) is None


def test_the_manifest_lists_the_five_at_its_end():
    assert len(NEW) == 5 and MANIFEST["per_layer"][-5:] == NEW
    for entry in NEW:
        assert entry["workloads"] == BACKLOG
        assert entry["moves"] == "serve_tokens_per_s"


# ------------------------------- the loader against a real profile
@pytest.fixture(scope="module")
def cpu_profile(tmp_path_factory):
    import gc

    import jax
    import jax.numpy as jnp

    from paddle_tpu import profiler
    from paddle_tpu.profiler import RecordEvent

    step = jax.jit(lambda x: jnp.tanh(x @ x.T))
    x = jnp.ones((64, 32))
    step(x).block_until_ready()
    profiler.trace_full_collections()
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation(xplane.WINDOW_ANNOTATION):
        for _ in range(3):
            with RecordEvent("engine::decode_call"):
                with RecordEvent(ds.ENQUEUE):
                    with RecordEvent(ds.LAUNCH):
                        y = step(x)
                with RecordEvent(ds.HARVEST):
                    jax.device_get(y)
            with RecordEvent("other::span"):
                gc.collect()
    jax.profiler.stop_trace()
    return trace_dir


def test_load_keeps_the_spans_inside_the_phases(cpu_profile):
    trace = ds.load(cpu_profile, "/device:TPU:0")
    names = [s[0] for s in sorted(trace["spans"], key=lambda s: s[1])]
    assert names.count("engine::decode_call") == 3
    for name in ds.INNER:
        assert names.count(name) == 3
    assert "python::gc" in names and "other::span" not in names
    assert trace["window"] is not None and trace["ops"] == []  # a CPU
    s = ds.summarize(dict(trace, ops=[trace["window"]]))    # never idle
    assert {k: v["count"] for k, v in s["decode"].items()} == dict.fromkeys(
        ds.INNER, 3)
    assert s["gc"]["count"] >= 3 and s["idle_s"] == 0


# ------------------------------------ through the command line, on the CPU
def test_new_counters_in_a_rehearsal(tmp_path):
    """The tests' own manifest with this PR's entries given its tiny
    closed-loop cell: the four counters print values; the device
    trace's reader waits for a chip."""
    import json

    from test_rehearsal import DATA, bench
    manifest = common.load_json(os.path.join(DATA, "BENCHMARK.json"))
    manifest["per_layer"] += [dict(entry, workloads=["tiny-closed"])
                              for entry in NEW]
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(manifest))
    line = bench(str(path), "tiny-closed", trace=1)
    assert line["correct"] is True
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert "idle_enqueue_ms_per_step.backlog" not in got
    assert 0 < got["decode_launch_ms.backlog"] \
        <= got["decode_enqueue_ms.backlog"]
    assert got["decode_harvest_wait_ms.backlog"] >= 0
    assert 0 < got["engine_host_cpu_pct.backlog"] <= 101
