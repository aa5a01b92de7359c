"""The trace reduction's arithmetic on a small hand-made event list."""
import pytest

from benchmarks import common, xplane


def test_interval_union_merges_overlap_and_touch():
    got = xplane.interval_union([(5, 7), (0, 2), (1, 3), (3, 4), (9, 9)])
    assert got == [(0, 4), (5, 7)]
    assert xplane.covered(got, 0, 10) == 6
    assert xplane.covered(got, 1, 6) == 4


def test_idle_gaps_are_what_the_union_leaves():
    merged = [(0, 4), (5, 7)]
    assert xplane.idle_gaps(merged, 0, 10) == [(4, 5), (7, 10)]
    assert xplane.idle_gaps(merged, 2, 6) == [(4, 5)]
    assert xplane.idle_gaps([], 0, 3) == [(0, 3)]
    assert xplane.idle_gaps([(1, 2)], 0, 3) == [(0, 1), (2, 3)]


def test_gap_takes_the_annotation_that_overlaps_it_longest():
    spans = [("bench.step", 0, 40), ("bench.loss_fetch", 40, 25),
             (xplane.WINDOW_ANNOTATION, 0, 100)]
    assert xplane.label_gap((38, 60), spans, "x") == "bench.loss_fetch"
    assert xplane.label_gap((70, 80), spans, "x") == "x"


def _trace():
    ops = [("fusion.1", 10, 20), ("custom-call.2", 30, 30),   # 10..60
           ("fusion.1", 70, 5),                               # 70..75
           ("copy.3", 75, 15)]                                # ..90
    modules = [("jit_step(1)", 10, 50), ("jit_step(1)", 70, 20)]
    return {"devices": {"/device:TPU:0": {xplane.OPS_LINE: ops,
                                          xplane.MODULES_LINE: modules}},
            "host_spans": [(xplane.WINDOW_ANNOTATION, 0, 100),
                           ("bench.batch", 60, 9)]}


def test_reduce_counts_busy_idle_and_the_heaviest_operations():
    red = xplane.reduce(_trace(), gap_default="unattributed")
    assert red["window_s"] == pytest.approx(100e-9)
    assert red["busy_s"] == pytest.approx(70e-9)      # 10..60 and 70..90
    assert red["device_ops"][0] == ["custom-call.2", pytest.approx(30e-9)]
    assert red["op_totals"]["fusion.1"]["count"] == 2
    assert red["op_totals"]["fusion.1"]["seconds"] == pytest.approx(25e-9)
    gaps = {(n, round(s * 1e9)) for n, s in red["idle_gaps"]}
    assert gaps == {("unattributed", 10), ("bench.batch", 10)}
    assert xplane.module_durations_ms(red, "jit_step") == \
        [pytest.approx(50e-6), pytest.approx(20e-6)]


def test_window_falls_back_to_the_device_events():
    trace = _trace()
    trace["host_spans"] = []
    red = xplane.reduce(trace)
    assert red["window_s"] == pytest.approx(80e-9)     # 10..90
    assert red["busy_s"] == pytest.approx(70e-9)


def test_a_trace_with_no_device_operation_is_an_error():
    with pytest.raises(ValueError):
        xplane.reduce({"devices": {}, "host_spans": []})


def test_percentile_is_nearest_rank():
    xs = list(range(1, 101))
    assert common.percentile(xs, 90) == 90
    assert common.percentile(xs, 99) == 99
    assert common.percentile([5.0], 99) == 5.0
    assert common.percentile([1, 2, 3, 4], 50) == 2
    assert common.median([1, 2, 3, 4]) == 2.5


HLO_WHILE = ("%while.10 = (u32[]{:T(128)}, bf16[2,2048,2048]{1,2,0:T(8,128)"
             "(2,1)}) while((u32[]{:T(128)}, bf16[2,2048,2048]{1,2,0}) "
             "%tuple.1), condition=%cond, body=%body")
HLO_FLASH = ('%closed_call.9 = (bf16[32,2048,128]{2,1,0:T(8,128)(2,1)S(1)}, '
             'f32[32,2048,128]{2,1,0:T(8,128)}) custom-call(bf16[32,2048,128]'
             '{2,1,0:T(8,128)(2,1)S(1)} %bitcast.330), '
             'custom_call_target="tpu_custom_call", frontend_attributes={}')
HLO_MARKER = ("%custom-call.15 = bf16[2,2048,2048]{1,2,0:T(8,128)(2,1)S(1)} "
              "custom-call(bf16[2,2048,2048]{1,2,0} %x), "
              'custom_call_target="AllocateBuffer"')
HLO_USER = ("%fusion.372 = bf16[24,8192,2048]{2,1,0:T(8,128)(2,1)} fusion("
            "bf16[2,2048,2048]{1,2,0:T(8,128)(2,1)S(1)} %custom-call.15), "
            "kind=kOutput, calls=%fused_computation.1")


def test_event_names_are_hlo_text_and_parse_to_an_opcode():
    from benchmarks import readers
    assert xplane.opcode(HLO_WHILE) == "while"
    assert xplane.opcode(HLO_FLASH) == "custom-call"
    assert xplane.opcode(HLO_USER) == "fusion"
    assert xplane.short_name(HLO_USER) == \
        "%fusion.372 fusion bf16[24,8192,2048]{2,1,0:T(8,128)(2,1)}"
    assert len(xplane.short_name(HLO_WHILE)) <= 96
    assert readers.is_mosaic_call(HLO_FLASH)
    assert not readers.is_mosaic_call(HLO_MARKER)
    assert not readers.is_mosaic_call(HLO_USER)   # only uses one


def test_containers_are_left_out_of_busy_time_and_totals():
    trace = _trace()
    lines = trace["devices"]["/device:TPU:0"]
    lines[xplane.OPS_LINE] = lines[xplane.OPS_LINE] + [(HLO_WHILE, 0, 100)]
    red = xplane.reduce(trace)
    assert red["busy_s"] == pytest.approx(70e-9)
    assert HLO_WHILE not in red["op_totals"]
