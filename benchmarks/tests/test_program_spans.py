"""``program_spans.py``: the wire-format reader against
``ProfileData`` on a real (CPU) profile, gap attribution and scope
sums on hand-made event lists, the readers of PR 25 on a run that has
nothing to read, and their rehearsal through the command line."""
import glob
import json
import os

import pytest

from benchmarks import common, paged_cost, program_spans as ps, run, xplane
from test_rehearsal import DATA, bench

MANIFEST = common.load_json(os.path.join(common.ROOT, "BENCHMARK.json"))
NEW = [m for m in MANIFEST["per_layer"] if m["name"].startswith((
    "engine_host_ms.", "prefill_share_pct.", "prefill_padding_pct.",
    "stream_stall_p99_ms.", "queue_wait_p90_ms.",
    "idle_host_ms_per_step.", "paged_attn_ms_per_step.",
    "paged_attn_roofline.", "prefill_slowest_shape_ms.",
    "flash_scope_ms_per_step", "train_step_idle_ms"))]

GATHER = "%fusion.4 = f32[64,2,8]{2,1,0} fusion(f32[8,4,2,8]{3,2,1,0} %p)"
SCORES = "%fusion.9 = f32[4,2,64]{2,1,0} fusion(f32[64,2,8]{2,1,0} %f)"
MATMUL = "%fusion.1 = f32[4,64]{1,0} fusion(f32[4,16]{1,0} %x)"
WHILE = "%while.2 = (s32[]) while((s32[]) %t), condition=%c, body=%b"
PRE = "jit(_decode)/jit(main)/paged_attention/"


def hand_made():
    """Two decode programs (10..40 and 60..90) and a prefill program
    (100..130) in a window 0..150; the engine's phases tile the host's
    timeline."""
    ops = [
        (MATMUL, 10, 5, "jit(_decode)/jit(main)/dot_general"),
        (GATHER, 15, 15, PRE + "kv_gather/gather"),
        (SCORES, 30, 10, PRE + "attend/dot_general"),
        (WHILE, 10, 30, PRE + "attend/while"),         # a container
        (MATMUL, 60, 5, "jit(_decode)/jit(main)/dot_general"),
        (GATHER, 65, 15, PRE + "kv_gather/gather"),
        (SCORES, 80, 10, PRE + "attend/dot_general"),
        (GATHER, 100, 10, "jit(_prefill)/jit(main)/paged_attention/"
                          "kv_write/scatter"),
        (SCORES, 110, 20, "jit(_prefill)/jit(main)/paged_attention/"
                          "attend/flash_attention/pallas_call"),
    ]
    modules = [("jit__decode(7)", 10, 30), ("jit__decode(7)", 60, 30),
               ("jit__prefill(9)", 100, 30)]
    line, no = "engine#1", {}
    spans = [
        ("engine::admit", 0, 4, line, no),
        ("engine::bookkeeping", 4, 2, line, no),
        ("engine::decode_feeds", 6, 2, line, no),
        ("engine::decode_call", 8, 34, line,           # 8..42
         {"active": 2, "context_tokens": 300}),
        ("engine::bookkeeping", 42, 3, line, no),
        ("engine::sample_emit", 45, 10, line, no),     # 45..55
        ("engine::admit", 55, 1, line, no),
        ("engine::decode_feeds", 56, 2, line, no),
        ("engine::decode_call", 58, 33, line,          # 58..91
         {"active": 4, "context_tokens": 500}),
        ("engine::sample_emit", 91, 5, line, no),      # 91..96
        ("engine::prefill", 96, 36, line, no),         # 96..132
        ("engine::wait", 132, 18, line, no),           # 132..150
        ("engine::admit", 20, 1, "another server#2", no),   # fewer: ignored
        ("serving::dispatch", 40, 20, "pipeline#3", no),
    ]
    return {"spans": spans, "window": (0.0, 150.0),
            "devices": {"/device:TPU:0": {"ops": ops, "modules": modules}}}


def test_idle_time_goes_to_the_phase_that_overlaps_it():
    s = ps.summarize(hand_made(), "decode")
    assert s["busy_s"] == pytest.approx(90e-9)     # 10..40 60..90 100..130
    assert s["idle_s"] == pytest.approx(60e-9)
    phases = {k: round(v * 1e9) for k, v in s["idle_s_by_phase"].items()}
    assert phases == {
        "engine::admit": 4 + 1, "engine::bookkeeping": 2 + 3,
        "engine::decode_feeds": 2 + 2,
        # dispatch before the program starts and the fetch after it
        "engine::decode_call": 2 + 2 + 2 + 1,
        "engine::sample_emit": 10 + 5,
        "engine::prefill": 4 + 2, "engine::wait": 18}
    assert s["idle_named_share"] == pytest.approx(1.0)
    assert s["idle_host_s"] == pytest.approx(42e-9)    # all but the wait
    assert s["idle_s_by_other_span"] == {
        "serving::dispatch": pytest.approx(20e-9)}     # 40..60
    assert s["engine_spans"] == 12
    assert s["other_spans"] == {"serving::dispatch": 1}


def test_device_time_goes_to_scope_and_sub_scope():
    s = ps.summarize(hand_made(), "decode")
    assert s["decode_programs"] == 2
    assert s["decode_device_s"] == pytest.approx(60e-9)
    scopes = {k: round(v * 1e9) for k, v in s["device_s_by_scope"].items()}
    # the container is left out; the prefill's flash call counts under
    # the scope that holds it
    assert scopes == {"paged_attention/kv_gather": 30,
                      "paged_attention/attend": 20 + 20,
                      "paged_attention/kv_write": 10}
    assert s["decode_device_s_by_scope"] == {
        "paged_attention": pytest.approx(50e-9)}
    assert s["scoped_ops"] == 6        # the two matmuls are in no scope


def test_per_step_readers_divide_by_the_decode_programs():
    run_ = {"_program_spans": ps.summarize(hand_made(), "decode")}
    assert ps.paged_attn_ms_per_step(run_) == pytest.approx(25e-6)
    assert ps.idle_host_ms_per_step(run_) == pytest.approx(21e-6)


def test_work_and_time_are_of_the_same_whole_steps():
    """The trace's stop cuts the window's last decode program short
    and its ``engine::decode_call`` never closes: neither the
    program's time nor a context counts. The roofline divides the
    least time for the mean context and lanes of the two whole steps
    by their mean time."""
    from types import SimpleNamespace
    from benchmarks import flash_cost
    trace = hand_made()
    dev = trace["devices"]["/device:TPU:0"]
    dev["modules"].append(("jit__decode(7)", 135, 10))
    dev["ops"].append((GATHER, 136, 9, PRE + "kv_gather/gather"))
    s = ps.summarize(trace, "decode")
    assert s["decode_programs"] == 2 and s["decode_calls"] == 2
    assert s["decode_context_tokens"] == 800 and s["decode_lanes"] == 6
    assert s["decode_device_s_by_scope"] == {
        "paged_attention": pytest.approx(50e-9)}
    shape = {"heads": 2, "head_dim": 8, "layers": 3}
    run_ = {"_program_spans": s, "device": {"kind": "TPU v5 lite"},
            "model_cfg": SimpleNamespace(num_heads=2, num_layers=3,
                                         hidden_size=16),
            "config": {"serve": {"page_size": 4}},
            "notes": {"pool_pages": 8,
                      "pool_bytes": 8 * 4 * 2 * 2 * 8 * 3 * 4}}
    least = flash_cost.roofline(paged_cost.paged_decode_step_cost(
        context_tokens=400.0, lanes=3.0, elem_bytes=4.0, **shape),
        common.chip_peaks("TPU v5 lite"))["min_seconds"]
    assert ps.paged_attn_roofline(run_) == pytest.approx(
        100.0 * least / 25e-9)
    # spans that say nothing of their context (a tree before the
    # argument): time is read, the share of the roofline is not
    for i, sp in enumerate(trace["spans"]):
        trace["spans"][i] = sp[:4] + ({},)
    run_["_program_spans"] = ps.summarize(trace, "decode")
    assert ps.paged_attn_ms_per_step(run_) == pytest.approx(25e-6)
    assert ps.paged_attn_roofline(run_) is None


def test_train_cell_readers_on_a_hand_made_trace():
    """Two steps in a window 0..100: the flash scope holds the kernel
    calls and an operation beside them, forward and backward; the
    idle time under ``train::step`` is the step's own."""
    flash = "%flash_attention.4 = bf16[2,8]{1,0} custom-call(bf16[2,8] %q)"
    bwd = "jit(step)/transpose(jvp(flash_attention))/flash_attention/"
    ops = [(MATMUL, 5, 10, "jit(step)/dot_general"),
           (flash, 15, 10, "jit(step)/flash_attention/pallas_call"),
           (MATMUL, 25, 5, bwd + "mul"), (flash, 30, 10, bwd + "pallas_call"),
           (MATMUL, 55, 10, "jit(step)/dot_general"),
           (flash, 65, 10, "jit(step)/flash_attention/pallas_call"),
           (MATMUL, 75, 5, bwd + "mul"), (flash, 80, 10, bwd + "pallas_call")]
    trace = {"spans": [("train::step", 2, 10, "main#1", {}),   # idle 2..5
                       ("train::step", 50, 10, "main#1", {})],  # 50..55
             "window": (0.0, 100.0), "devices": {"/device:TPU:0": {
                 "ops": ops, "modules": [("jit_step(1)", 5, 35),
                                         ("jit_step(1)", 55, 35)]}}}
    s = ps.summarize(trace, "")
    assert s["other_spans"] == {"train::step": 2}
    run_ = {"_program_spans": s, "trace_steps": 2}
    assert ps.flash_scope_ms_per_step(run_) == pytest.approx(25e-6)
    assert ps.train_step_idle_ms(run_) == pytest.approx((3 + 5) / 2 * 1e-6)
    # a tree without the span and the scope
    trace["spans"] = []
    for dev in trace["devices"].values():
        dev["ops"] = [op[:3] + ("",) for op in dev["ops"]]
    run_ = {"_program_spans": ps.summarize(trace, ""), "trace_steps": 2}
    assert ps.flash_scope_ms_per_step(run_) is None
    assert ps.train_step_idle_ms(run_) is None


def test_a_trace_without_spans_or_scopes_reads_as_nothing():
    trace = hand_made()
    trace["spans"] = []
    for dev in trace["devices"].values():
        # op_names as a tree before the scopes gives them
        dev["ops"] = [op[:3] + ("jit(_decode)/jit(main)/gather",)
                      for op in dev["ops"]]
    s = ps.summarize(trace, "decode")
    assert s["idle_s_by_phase"] == {} and s["device_s_by_scope"] == {}
    assert s["scoped_ops"] == 0 and s["engine_spans"] == 0
    run_ = {"_program_spans": s}
    assert ps.paged_attn_ms_per_step(run_) is None
    assert ps.idle_host_ms_per_step(run_) is None
    assert ps.paged_attn_roofline(run_) is None


@pytest.mark.parametrize("op_name,want", [
    (PRE + "kv_gather/gather", ("paged_attention", "kv_gather")),
    (PRE + "kv_gather/jit(remainder)/rem",
     ("paged_attention", "kv_gather")),
    ("jit(f)/paged_attention/mul", ("paged_attention", "")),
    ("jit(f)/flash_attention/pallas_call", ("flash_attention", "")),
    ("jit(f)/transpose(jvp(flash_attention))/flash_attention/pallas_call",
     ("flash_attention", "")),
    (PRE + "attend/flash_attention/pallas_call",
     ("paged_attention", "attend")),
    ("jit(f)/my_paged_attention/mul", None),
    ("jit(f)/dot_general", None), ("", None)])
def test_scope_of_an_op_name(op_name, want):
    assert ps.scope_of(op_name) == want


def test_instruction_name_of_an_event():
    assert ps.instruction_name(GATHER) == "fusion.4"
    assert ps.instruction_name("dot_general.1") == "dot_general.1"


# ------------------------------------------------- the engine's window
def snapshots():
    le = [1.0, 2.0, 4.0, 8.0]

    def engine(k):
        return {"loop_s": {"admit": 1.0 * k, "prefill": 2.0 * k,
                           "decode_feeds": 0.5 * k, "decode_call": 30.0 * k,
                           "sample_emit": 3.0 * k, "bookkeeping": 1.5 * k,
                           "wait": 2.0 * k},
                "prefill": {"prompt_tokens": 3_000 * k,
                            "padded_tokens": 4_000 * k,
                            "by_shape": {"2x128": 6 * k} | (
                                {"4x256": 4} if k > 1 else {}),
                            "call_s_by_shape": {"2x128": 0.06 * k} | (
                                {"4x256": 0.1} if k > 1 else {})},
                "stream_stall_ms": {"le": le, "counts": [
                    k, 2 * k, 9 * k, 10 * k, 10 * k]},
                "queue_wait_ms": {"le": le,
                                  "counts": [0, 0, k, 3 * k, 4 * k]}}
    occupancy = {"mean": 8.0, "steps": 100}
    return {"snap0": {"engine": engine(1), "batch_occupancy": occupancy},
            "snap1": {"engine": engine(3), "batch_occupancy":
                      {"mean": 8.0, "steps": 300}}}


def test_engine_window_is_the_difference_of_two_snapshots():
    eng = ps.engine_window(snapshots())
    assert eng["loop_s"]["decode_call"] == pytest.approx(60.0)
    assert eng["prefill"]["by_shape"] == {"2x128": 12, "4x256": 4}
    assert eng["stream_stall_ms"] == {"le": [1.0, 2.0, 4.0, 8.0],
                                      "counts": [2, 4, 18, 20, 20]}
    assert ps.host_ms_per_iteration(snapshots()) == pytest.approx(
        1e3 * (2.0 + 1.0 + 6.0 + 3.0) / 200)
    # 12 dispatches of 2x128 at 10 ms, 4 of 4x256 at 25 ms
    assert ps.prefill_slowest_shape_ms(snapshots()) == pytest.approx(25.0)


@pytest.mark.parametrize("name,q,want", [
    ("stream_stall_ms", 50, 4.0),     # rank 10 of 20: third bucket
    ("stream_stall_ms", 10, 1.0), ("stream_stall_ms", 99, 8.0),
    ("queue_wait_ms", 90, 8.0),       # rank 8 of 8 lies beyond 8.0:
    ("queue_wait_ms", 25, 4.0)])      # the last finite bound
def test_quantile_from_cumulative_buckets(name, q, want):
    eng = ps.engine_window(snapshots())
    assert ps.histogram_quantile(eng[name], q) == want


def test_no_observation_has_no_quantile():
    assert ps.histogram_quantile({"le": [1.0], "counts": [0, 0]}, 99) is None


@pytest.mark.parametrize("entry", NEW, ids=lambda m: m["name"])
def test_new_reader_returns_none_with_nothing_to_read(entry):
    """The parent of PR 25: a snapshot without ``"engine"``, a trace
    directory without the program's spans (here: none at all)."""
    reader = run.load_reader(MANIFEST, entry["name"])
    parent = {"snap0": {"batch_occupancy": {"mean": 1.0, "steps": 1}},
              "snap1": {"batch_occupancy": {"mean": 1.0, "steps": 9}},
              "seconds": 1.0, "trace_dir": None, "traffic": {},
              "cell": {"name": "none"}}
    assert reader.read(parent) is None


def test_the_manifest_lists_fifteen_new_metrics_at_its_end():
    assert len(NEW) == 15
    assert MANIFEST["per_layer"][-15:] == NEW


# ------------------------------------------------------------ the yardstick
def test_paged_cost_of_the_chat_cell():
    """16 heads of 128, 24 layers, a float32 pool, 16 lanes at a mean
    context of 350: 5,600 positions, 2.2 GB a step, memory-bound."""
    shape = {"heads": 16, "head_dim": 128, "layers": 24}
    elem = paged_cost.pool_elem_bytes(
        pool_bytes=1200 * 6 * 2 ** 20, pages=1200, page_size=16, **shape)
    assert elem == 4.0
    cost = paged_cost.paged_decode_step_cost(
        context_tokens=5600, lanes=16, elem_bytes=elem, **shape)
    assert cost["bytes"] == 2 * 16 * 128 * 24 * 4 * (5600 + 16)
    assert cost["flops"] == 4 * 16 * 128 * 24 * 5600
    from benchmarks import flash_cost
    least = flash_cost.roofline(cost, common.chip_peaks("TPU v5 lite"))
    assert least["bound"] == "memory"
    assert least["min_seconds"] == pytest.approx(2.2085e9 / 819e9, rel=1e-3)


# ------------------------------ the wire reader against a real profile
@pytest.fixture(scope="module")
def cpu_profile(tmp_path_factory):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.profiler import RecordEvent

    @jax.jit
    def step(x):
        with jax.named_scope("paged_attention"):
            with jax.named_scope("kv_gather"):
                y = jnp.take(x, jnp.arange(8) % 4, axis=0)
            with jax.named_scope("attend"):
                return jnp.tanh(y @ y.T)
    x = jnp.ones((64, 32))
    step(x).block_until_ready()
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation(xplane.WINDOW_ANNOTATION):
        for i in range(3):
            with RecordEvent("engine::decode_call", args={
                    "active": 2, "context_tokens": 10 + i}):
                step(x).block_until_ready()
            with RecordEvent("other::span"):
                pass
    jax.profiler.stop_trace()
    return trace_dir


def test_wire_reader_agrees_with_profile_data(cpu_profile):
    from jax.profiler import ProfileData
    path = xplane.find_xplane(cpu_profile)
    want = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            want[(plane.name, line.name)] = [
                (ev.name, float(ev.start_ns), float(ev.duration_ns))
                for ev in line.events]
    with open(path, "rb") as f:
        space = memoryview(f.read())
    got = {}
    for num, view in ps.fields(space):
        if num != 1:
            continue
        plane = ps.parse_plane(view)
        for line in plane["lines"]:
            got[(plane["name"], line["name"])] = [
                ev[:3] for ev in ps.events_of(plane, line)]
    assert got.keys() == want.keys() and got
    for key in want:
        assert [e[0] for e in got[key]] == [e[0] for e in want[key]]
        for a, b in zip(got[key], want[key]):
            assert a[1] == pytest.approx(b[1], abs=1.0)
            assert a[2] == pytest.approx(b[2], abs=1.0)


def test_load_keeps_the_programs_spans_and_the_window(cpu_profile):
    trace = ps.load(cpu_profile)
    names = [s[0] for s in trace["spans"]]
    assert names == ["engine::decode_call"] * 3          # no other::
    assert len({s[3] for s in trace["spans"]}) == 1      # one thread
    assert [s[4] for s in trace["spans"]] == [
        {"active": 2, "context_tokens": 10 + i} for i in range(3)]
    lo, hi = trace["window"]
    assert all(lo <= s[1] and s[1] + s[2] <= hi for s in trace["spans"])
    assert trace["devices"] == {}                        # a CPU has none


def test_scopes_are_read_from_the_hlo_proto_of_the_profile(cpu_profile):
    with open(xplane.find_xplane(cpu_profile), "rb") as f:
        space = memoryview(f.read())
    names = {}
    for num, view in ps.fields(space):
        plane = ps.parse_plane(view) if num == 1 else None
        if plane and plane["name"] == "/host:metadata":
            for module, stats in plane["event_meta"].values():
                for mid, value in stats:
                    if plane["stat_names"].get(mid) == "Hlo Proto" \
                            and module.startswith("jit_step"):
                        names = ps.hlo_op_names(value)
    scopes = {ps.scope_of(v) for v in names.values()}
    assert ("paged_attention", "kv_gather") in scopes
    assert ("paged_attention", "attend") in scopes


# ------------------------------------ through the command line, on the CPU
@pytest.mark.parametrize("cell,suffix,expect", [
    ("tiny-closed", ".backlog",
     {"engine_host_ms.backlog", "prefill_share_pct.backlog",
      "prefill_padding_pct.backlog"}),
    ("tiny-open", ".chat",
     {"engine_host_ms.chat", "stream_stall_p99_ms.chat",
      "queue_wait_p90_ms.chat", "prefill_slowest_shape_ms.chat"})])
def test_new_metrics_in_a_rehearsal(tmp_path, cell, suffix, expect):
    """The tests' own manifest with this PR's entries appended for its
    tiny cells: the readers that need no device trace print values."""
    manifest = common.load_json(os.path.join(DATA, "BENCHMARK.json"))
    for entry in NEW:
        if entry["name"].endswith(suffix):
            manifest["per_layer"].append(dict(entry, workloads=[cell]))
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(manifest))
    line = bench(str(path), cell, trace=1)
    assert line["correct"] is True
    assert expect <= set(line["metrics"])
    # nothing that needs a device trace on a CPU
    assert not {n for n in line["metrics"] if n.startswith((
        "idle_host_ms", "paged_attn"))}
    for name in expect:
        assert line["metrics"][name]["value"] > 0, name
    assert line["metrics"]["engine_host_ms" + suffix]["value"] < 1e3
    # the rehearsal's trace holds the engine's spans on one line
    trace_dir = os.path.join(common.scratch_dir(cell), "trace")
    if glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.pb")):
        spans = ps.load(trace_dir)["spans"]
        assert {"engine::decode_call", "engine::sample_emit"} <= {
            s[0] for s in ps.engine_line(spans)}
