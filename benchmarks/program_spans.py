"""The program's own spans, scopes and counters, read for the metrics
that only the program can give (PR 25).

Three things are read here, all of them written by the program and
none by the benchmark:

- host spans ``engine::<phase>``, ``train::step`` and ``serving::*``
  (``paddle_tpu.profiler.RecordEvent`` -> ``TraceAnnotation``): they
  lie on the calling thread's line of the ``/host:CPU`` plane of the
  same ``.xplane.pb`` as the device's ``XLA Ops``, on one clock, with
  their whole-number arguments (``engine::decode_call`` says what its
  step's attention reads);
- the ``jax.named_scope``s round paged and flash attention: metadata
  of the compiled instructions (``op_name``), which the profile holds
  in the ``Hlo Proto`` of the ``/host:metadata`` plane (a TPU trace
  repeats it as the ``tf_op`` stat of an operation's event metadata:
  the same names for the same operations, so one way is read);
- ``metrics_snapshot()["engine"]``: cumulative phase sums, prefill
  dispatches by shape and two tail histograms, read as the difference
  of the runner's two snapshots.

``xplane.load`` keeps only the runner's ``bench.`` annotations and
``jax.profiler.ProfileData`` shows neither event metadata's stats nor
the metadata plane, so the file is read here by a small reader of the
protobuf wire format (``tsl/profiler/protobuf/xplane.proto``); after
that everything is arithmetic on tuples, tested on hand-made lists.

Every reader returns None, and says why on stderr, where the program
wrote nothing to read: the parent of PR 25 has no span, scope or
counter, and a compile cache can hand a scoped program an executable
compiled before the scopes (its key ignores metadata).
"""
from __future__ import annotations

import json
import math
import os
import re
import sys
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from . import common, xplane

SPAN_PREFIXES = ("engine::", "train::", "serving::")
ENGINE = "engine::"
WAIT = "engine::wait"
DECODE_CALL = "engine::decode_call"
TRAIN_STEP = "train::step"
# the engine's host work of one iteration: every phase that is neither
# a device call, nor a prefill, nor waiting for a request
HOST_PHASES = ("admit", "decode_feeds", "sample_emit", "bookkeeping")
HISTOGRAMS = ("stream_stall_ms", "queue_wait_ms")
SCOPES = ("paged_attention", "flash_attention")
_SCOPE = re.compile(r"(?:^|[/(])(" + "|".join(SCOPES) + r")(?=[/)]|$)")
_NAME = re.compile(r"^[A-Za-z_][A-Za-z0-9_]*$")

# name, start_ns, dur_ns, thread line, whole-number arguments
Span = Tuple[str, float, float, str, Dict[str, int]]
Op = Tuple[str, float, float, str]        # name, start_ns, dur_ns, op_name


def say(msg: str):
    print("program_spans: " + msg, file=sys.stderr, flush=True)


# ------------------------------------------------- protobuf wire format
def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf) -> Iterable[Tuple[int, object]]:
    """``(field number, value)`` of one message: a varint as an int, a
    length-delimited field as a slice of ``buf`` (a nested message, a
    string or bytes: the caller knows), fixed-width fields as slices."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value = buf[i:i + size]
            i += size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            value = buf[i:i + size]
            i += size
        else:
            raise ValueError(f"wire type {kind} at byte {i}")
        yield key >> 3, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_entry(view):
    key, value = 0, b""
    for num, v in fields(view):
        if num == 1:
            key = v
        elif num == 2:
            value = v
    return key, value


def parse_stat(view):
    """An ``XStat``: (metadata id, value), the value an int for a whole
    number, a slice for bytes and None for what nothing here reads
    (strings, references, doubles)."""
    mid, value = 0, None
    for num, v in fields(view):
        if num == 1:
            mid = v
        elif num in (3, 4, 6):      # uint64, int64 (not negative), bytes
            value = v
    return mid, value


def parse_plane(view) -> dict:
    """One ``XPlane``: its name, the names of its stats, its event
    metadata (name and raw stats) and its lines of raw events."""
    plane = {"name": "", "stat_names": {}, "event_meta": {}, "lines": []}
    for num, v in fields(view):
        if num == 2:
            plane["name"] = _text(v)
        elif num == 5:
            key, meta = _map_entry(v)
            plane["stat_names"][key] = next(
                (_text(x) for n, x in fields(meta) if n == 2), "")
        elif num == 4:
            key, meta = _map_entry(v)
            name, stats = "", []
            for n, x in fields(meta):
                if n == 2:
                    name = _text(x)
                elif n == 5:
                    stats.append(parse_stat(x))
            plane["event_meta"][key] = (name, stats)
        elif num == 3:
            line = {"name": "", "id": 0, "timestamp_ns": 0, "events": []}
            for n, x in fields(v):
                if n == 1:
                    line["id"] = x
                elif n == 2:
                    line["name"] = _text(x)
                elif n == 3:
                    line["timestamp_ns"] = x
                elif n == 4:
                    line["events"].append(x)
            plane["lines"].append(line)
    return plane


def events_of(plane: dict, line: dict, keep=None):
    """``(name, start_ns, duration_ns, raw stats)`` of a line's events,
    on ``ProfileData``'s clock: the line's timestamp plus the event's
    offset."""
    meta, t_line = plane["event_meta"], float(line["timestamp_ns"])
    for raw in line["events"]:
        mid = offset_ps = dur_ps = 0
        stats = []
        for n, x in fields(raw):
            if n == 1:
                mid = x
            elif n == 2:
                offset_ps = x
            elif n == 3:
                dur_ps = x
            elif n == 4:
                stats.append(x)
        name = meta.get(mid, ("", ()))[0]
        if keep is None or keep(name):
            yield name, t_line + offset_ps / 1e3, dur_ps / 1e3, stats


def whole_number_args(plane: dict, stats) -> Dict[str, int]:
    """An event's own stats that are whole numbers, by name: what a
    ``TraceAnnotation`` was given as keyword arguments."""
    out = {}
    for mid, value in map(parse_stat, stats):
        if isinstance(value, int):
            out[plane["stat_names"].get(mid, "")] = value
    return out


def hlo_op_names(hlo_proto) -> Dict[str, str]:
    """Instruction name -> ``metadata.op_name`` of every instruction of
    a serialized ``HloProto`` (``hlo_module`` = 1; a module's
    ``computations`` = 3; a computation's ``instructions`` = 2; an
    instruction's ``name`` = 1 and ``metadata`` = 7, whose ``op_name``
    = 2)."""
    out = {}
    for num, module in fields(hlo_proto):
        if num != 1:
            continue
        for n, comp in fields(module):
            if n != 3:
                continue
            for k, inst in fields(comp):
                if k != 2:
                    continue
                name, op_name = "", ""
                for f, x in fields(inst):
                    if f == 1:
                        name = _text(x)
                    elif f == 7:
                        op_name = next((_text(y) for g, y in fields(x)
                                        if g == 2), "")
                if op_name:
                    out[name] = op_name
    return out


def instruction_name(event_name: str) -> str:
    """``%fusion.49 = f32[..] fusion(..)`` -> ``fusion.49``."""
    return event_name.partition(" = ")[0].strip().lstrip("%")


# ---------------------------------------------------------------- load
def _host_spans(planes: Sequence[dict]):
    """The program's spans by thread line, and the traced window."""
    spans: List[Span] = []
    window = None
    for plane in planes:
        if not plane["name"].startswith("/host:"):
            continue
        for line in plane["lines"]:
            key = f'{line["name"]}#{line["id"]}'
            for name, start, dur, stats in events_of(
                    plane, line, lambda n: n.startswith(SPAN_PREFIXES)
                    or n == xplane.WINDOW_ANNOTATION):
                if name == xplane.WINDOW_ANNOTATION:
                    window = (start, start + dur)
                else:
                    spans.append((name, start, dur, key,
                                  whole_number_args(plane, stats)))
    return spans, window


def _module_op_names(planes: Sequence[dict]) -> Dict[str, Dict[str, str]]:
    """Module name -> {instruction name -> op_name}, from the ``Hlo
    Proto`` stats of the ``/host:metadata`` plane."""
    out: Dict[str, Dict[str, str]] = {}
    for plane in planes:
        if plane["name"] != "/host:metadata":
            continue
        for name, stats in plane["event_meta"].values():
            for mid, value in stats:
                if plane["stat_names"].get(mid) == "Hlo Proto" \
                        and value is not None:
                    out[name] = hlo_op_names(value)
    return out


def _device(plane: dict, by_module: Dict[str, Dict[str, str]]) -> dict:
    """A device plane's operations, each with its ``op_name``, and its
    executed programs. An operation's ``op_name`` is what the metadata
    plane's HLO says of the instruction of that name in the program
    whose event holds the operation's start."""
    lines = {ln["name"]: ln for ln in plane["lines"]}
    modules = sorted((ev[:3] for ev in events_of(plane, lines.get(
        xplane.MODULES_LINE, {"timestamp_ns": 0, "events": ()}))),
        key=lambda ev: ev[1])
    ops: List[Op] = []
    at = 0
    for name, start, dur, _ in sorted(events_of(plane, lines.get(
            xplane.OPS_LINE, {"timestamp_ns": 0, "events": ()})),
            key=lambda ev: ev[1]):
        op_name = ""
        if by_module and modules:
            while at + 1 < len(modules) and modules[at + 1][1] <= start:
                at += 1
            holder, m_start, m_dur = modules[at]
            if m_start <= start < m_start + m_dur:
                op_name = by_module.get(holder, {}).get(
                    instruction_name(name), "")
        ops.append((name, start, dur, op_name))
    return {"ops": ops, "modules": modules}


def load(trace_dir: str) -> dict:
    """``{"spans": [Span], "window": (lo, hi) | None, "devices":
    {plane: {"ops": [Op], "modules": [Event]}}}`` of the newest trace
    under ``trace_dir``. An operation's ``op_name`` is "" where the
    profile holds none for it."""
    with open(xplane.find_xplane(trace_dir), "rb") as f:
        space = memoryview(f.read())
    planes = [parse_plane(v) for num, v in fields(space) if num == 1]
    spans, window = _host_spans(planes)
    by_module = _module_op_names(planes)
    devices = {plane["name"]: _device(plane, by_module)
               for plane in planes if plane["name"].startswith("/device:")}
    return {"spans": spans, "window": window, "devices": devices}


# ---------------------------------------------------------- arithmetic
def scope_of(op_name: str) -> Optional[Tuple[str, str]]:
    """``(scope, sub-scope)`` of an ``op_name`` path: the outermost of
    ``SCOPES`` on it and the next path element below it that is a
    plain name ("" where the operation sits in the scope itself; the
    last element is the primitive, not a scope). None outside every
    scope. A transform wraps its element and the backward pass enters
    the scope again, ``transpose(jvp(flash_attention))/flash_attention
    /..``: still that scope, once."""
    m = _SCOPE.search(op_name)
    if not m:
        return None
    below = op_name[m.end():].split("/")[1:-1]
    sub = next((e for e in below if _NAME.match(e) and e != m.group(1)),
               "")
    return m.group(1), sub


def phase_overlap(gaps: Sequence[Tuple[float, float]],
                  spans: Sequence[Span]) -> Dict[str, float]:
    """Nanoseconds of ``gaps`` under each span name. Spans of one
    thread's timeline do not overlap, so a moment counts once."""
    out: Dict[str, float] = {}
    spans = sorted(spans, key=lambda s: s[1])
    first = 0
    for lo, hi in sorted(gaps):
        while first < len(spans) and \
                spans[first][1] + spans[first][2] <= lo:
            first += 1
        for name, start, dur, *_ in spans[first:]:
            if start >= hi:
                break
            both = min(hi, start + dur) - max(lo, start)
            if both > 0:
                out[name] = out.get(name, 0.0) + both
    return out


def engine_line(spans: Sequence[Span]) -> List[Span]:
    """The ``engine::`` spans of the thread line that holds most of
    them: one server's loop thread."""
    by_line: Dict[str, List[Span]] = {}
    for s in spans:
        if s[0].startswith(ENGINE):
            by_line.setdefault(s[3], []).append(s)
    return max(by_line.values(), key=len) if by_line else []


def inside(events: Sequence, holders: Sequence[Tuple[str, float, float]]
           ) -> List:
    """The ``events`` whose start lies inside one of ``holders``."""
    merged = xplane.interval_union((s, s + d) for _, s, d in holders)
    out, at = [], 0
    for ev in sorted(events, key=lambda e: e[1]):
        while at < len(merged) and merged[at][1] <= ev[1]:
            at += 1
        if at < len(merged) and merged[at][0] <= ev[1]:
            out.append(ev)
    return out


def held_by(events: Sequence, spans: Sequence[Span]) -> List[Tuple]:
    """``(event, span)`` for each of ``events`` whose start a span
    holds (``spans`` of one thread's timeline: they do not overlap)."""
    spans = sorted(spans, key=lambda s: s[1])
    out, at = [], 0
    for ev in sorted(events, key=lambda e: e[1]):
        while at < len(spans) and spans[at][1] + spans[at][2] <= ev[1]:
            at += 1
        if at < len(spans) and spans[at][1] <= ev[1]:
            out.append((ev, spans[at]))
    return out


def summarize(trace: dict, decode_module: str) -> dict:
    """Idle time by phase and device time by scope, in the traced
    window, on the busiest device plane (the plane ``xplane.reduce``
    takes); and what the decode programs executed in it had to read,
    as their ``engine::decode_call`` spans say."""
    if not trace["devices"]:
        raise ValueError("the trace holds no device plane")
    window = trace["window"]
    if window is None:
        starts = [(s, s + d) for dev in trace["devices"].values()
                  for _, s, d, _ in dev["ops"]]
        window = (min(s for s, _ in starts), max(e for _, e in starts))
    lo, hi = window
    best = None
    for plane, dev in trace["devices"].items():
        ops = [op for op in dev["ops"] if op[1] + op[2] > lo and op[1] < hi
               and xplane.opcode(op[0]) not in xplane.CONTAINER_OPCODES]
        merged = xplane.interval_union((s, s + d) for _, s, d, _ in ops)
        busy = xplane.covered(merged, lo, hi)
        if best is None or busy > best[0]:
            best = (busy, plane, ops, merged)
    busy, plane, ops, merged = best
    gaps = xplane.idle_gaps(merged, lo, hi)
    idle = sum(e - s for s, e in gaps)

    spans = [s for s in trace["spans"] if s[1] + s[2] > lo and s[1] < hi]
    engine = engine_line(spans)
    by_phase = phase_overlap(gaps, engine)
    others = [s for s in spans if not s[0].startswith(ENGINE)]
    by_span = phase_overlap(gaps, others)
    named = sum(by_phase.values())
    decodes = [m for m in trace["devices"][plane]["modules"]
               if decode_module in m[0] and m[1] >= lo
               and m[1] + m[2] <= hi]
    calls = [s for s in engine if s[0] == DECODE_CALL]
    if calls:
        # a call ends by fetching the logits and only a whole span is
        # recorded, so a program that starts inside one ran to its end
        # (the trace's stop cuts the window's last program short) and
        # the span's arguments say what it read
        pairs = held_by(decodes, calls)
        decodes = [m for m, _ in pairs]
        calls = [s[4] for _, s in pairs
                 if "context_tokens" in s[4] and "active" in s[4]]
    in_decode = inside(ops, decodes)
    by_scope: Dict[str, float] = {}
    decode_by_scope: Dict[str, float] = {}
    scoped = 0
    for name, _, dur, op_name in ops:
        found = scope_of(op_name)
        if found:
            scoped += 1
            key = found[0] + ("/" + found[1] if found[1] else "")
            by_scope[key] = by_scope.get(key, 0.0) + dur
    for name, _, dur, op_name in in_decode:
        found = scope_of(op_name)
        if found:
            decode_by_scope[found[0]] = \
                decode_by_scope.get(found[0], 0.0) + dur
    return {
        "window_s": (hi - lo) / 1e9, "busy_s": busy / 1e9,
        "idle_s": idle / 1e9, "plane": plane,
        "idle_s_by_phase": {k: v / 1e9 for k, v in sorted(
            by_phase.items(), key=lambda kv: -kv[1])},
        "idle_s_by_other_span": {k: v / 1e9 for k, v in sorted(
            by_span.items(), key=lambda kv: -kv[1])},
        "idle_named_share": named / idle if idle else None,
        "idle_host_s": sum(v for k, v in by_phase.items()
                           if k != WAIT) / 1e9,
        "engine_spans": len(engine),
        "other_spans": {n: sum(1 for s in others if s[0] == n)
                        for n in sorted({s[0] for s in others})},
        "decode_programs": len(decodes),
        "decode_device_s": sum(d for _, _, d in decodes) / 1e9,
        "decode_calls": len(calls),
        "decode_context_tokens": sum(a["context_tokens"] for a in calls),
        "decode_lanes": sum(a["active"] for a in calls),
        "scoped_ops": scoped,
        "device_s_by_scope": {k: v / 1e9 for k, v in sorted(
            by_scope.items(), key=lambda kv: -kv[1])},
        "decode_device_s_by_scope": {k: v / 1e9 for k, v in
                                     decode_by_scope.items()},
    }


# ------------------------------------------------ the engine's counters
def engine_window(run: dict) -> Optional[dict]:
    """``metrics_snapshot()["engine"]`` between the runner's two
    snapshots: every cumulative number as its difference (a histogram
    keeps its bounds). None where the program has no such section."""
    a = run.get("snap0", {}).get("engine")
    b = run.get("snap1", {}).get("engine")
    if a is None or b is None:
        return None

    def sub(x, y):
        if isinstance(y, dict):
            return {k: sub((x or {}).get(k, 0), v) for k, v in y.items()}
        return y - x
    out = {k: sub(a.get(k, 0), v) for k, v in b.items()
           if k not in HISTOGRAMS}
    for k in HISTOGRAMS:
        out[k] = {"le": b[k]["le"], "counts": [
            q - p for p, q in zip(a[k]["counts"], b[k]["counts"])]}
    return out


def host_ms_per_iteration(run: dict) -> Optional[float]:
    """The loop thread's host phases over the window's decode
    iterations (``batch_occupancy.steps``, as ``engine_loop_ms``)."""
    from . import readers
    eng = engine_window(run)
    if eng is None or readers.decode_steps(run) <= 0:
        return None
    return 1e3 * sum(eng["loop_s"][p] for p in HOST_PHASES) \
        / readers.decode_steps(run)


def prefill_slowest_shape_ms(run: dict) -> Optional[float]:
    """The mean decoder-call time of the padded prefill shape whose
    mean is largest, over the window's dispatches."""
    eng = engine_window(run)
    if eng is None:
        return None
    pre = eng["prefill"]
    means = [1e3 * pre["call_s_by_shape"][shape] / n
             for shape, n in pre["by_shape"].items() if n > 0]
    return max(means) if means else None


def histogram_quantile(hist: dict, q: float) -> Optional[float]:
    """The upper bound of the bucket that holds the q-th percentile
    (nearest rank) of a window's observations, from cumulative bucket
    counts (``le`` bounds, one more count for +Inf): the true value is
    at most one bucket ratio (1.05) below it. None for no observation;
    the last finite bound where the rank lies beyond it."""
    counts, le = hist["counts"], hist["le"]
    if not counts or counts[-1] <= 0:
        return None
    rank = max(1, math.ceil(q / 100.0 * counts[-1]))
    for bound, seen in zip(le, counts):
        if seen >= rank:
            return float(bound)
    return float(le[-1])


# ------------------------------------------------------------- per run
def of(run: dict) -> Optional[dict]:
    """The summary of this run's trace, read once a run and written to
    ``<scratch>/<cell>/program_spans.json`` and to stderr. None where
    no trace was taken."""
    if "_program_spans" in run:
        return run["_program_spans"]
    summary = None
    if run.get("trace_dir"):
        try:
            summary = summarize(load(run["trace_dir"]),
                                run["traffic"].get("decode_module", ""))
        except (OSError, ValueError) as e:
            say(f"no summary of {run['trace_dir']}: {e}")
    if summary is not None:
        eng = engine_window(run)
        if eng is not None:
            summary["window_engine"] = {"loop_s": eng["loop_s"],
                                        "prefill": eng["prefill"]}
        if run.get("requests") is not None:
            # the clients' side of the same run, for comparison with
            # what the engine's histograms say
            from . import readers
            gaps = readers.token_gaps_ms(run)
            summary["client_token_gap_p99_ms"] = \
                common.percentile(gaps, 99) if gaps else None
        if not summary["engine_spans"] \
                and TRAIN_STEP not in summary["other_spans"]:
            say("no engine:: or train::step span on the host plane: "
                "the program writes none (a tree before PR 25)")
        if not summary["scoped_ops"]:
            say("no device operation carries a scope: the program "
                "names none, or a compile cache served an executable "
                "compiled before the scopes (its key ignores metadata)")
        path = os.path.join(common.scratch_dir(run["cell"]["name"]),
                            "program_spans.json")
        with open(path, "w") as f:
            json.dump(summary, f, indent=1)
        say(json.dumps(summary))
    run["_program_spans"] = summary
    return summary


def idle_host_ms_per_step(run: dict) -> Optional[float]:
    s = of(run)
    if not s or not s["engine_spans"] or not s["decode_programs"]:
        return None
    return 1e3 * s["idle_host_s"] / s["decode_programs"]


def scope_seconds(by_scope: Dict[str, float], scope: str) -> float:
    return sum(v for k, v in by_scope.items()
               if k == scope or k.startswith(scope + "/"))


def paged_attn_ms_per_step(run: dict) -> Optional[float]:
    s = of(run)
    if not s or not s["decode_programs"]:
        return None
    seconds = s["decode_device_s_by_scope"].get("paged_attention")
    return 1e3 * seconds / s["decode_programs"] if seconds else None


def paged_attn_roofline(run: dict) -> Optional[float]:
    """The least time the chip could take for the decode step's paged
    attention (``paged_cost.py``) over the time its scope took, in
    percent. Work and time are of the same steps: the decode programs
    executed in the traced window, each with the context and live
    lanes its ``engine::decode_call`` span carries."""
    from . import flash_cost, paged_cost
    ms = paged_attn_ms_per_step(run)
    s = of(run)
    if not ms or not s["decode_calls"]:
        return None
    if s["decode_calls"] != s["decode_programs"]:
        say(f'{s["decode_programs"]} decode programs in the window, '
            f'{s["decode_calls"]} of them inside an engine::decode_call '
            "span that says its context: the mean is of those")
    cfg, serve, notes = run["model_cfg"], run["config"]["serve"], \
        run["notes"]
    shape = {"heads": cfg.num_heads, "layers": cfg.num_layers,
             "head_dim": cfg.hidden_size // cfg.num_heads}
    cost = paged_cost.paged_decode_step_cost(
        context_tokens=s["decode_context_tokens"] / s["decode_calls"],
        lanes=s["decode_lanes"] / s["decode_calls"],
        elem_bytes=paged_cost.pool_elem_bytes(
            pool_bytes=notes["pool_bytes"], pages=notes["pool_pages"],
            page_size=int(serve["page_size"]), **shape), **shape)
    least = flash_cost.roofline(
        cost, common.chip_peaks(run["device"]["kind"]))
    return 100.0 * least["min_seconds"] / (ms / 1e3)


# -------------------------------------------------------- the train cell
def flash_scope_ms_per_step(run: dict) -> Optional[float]:
    """Device time of every operation under the ``flash_attention``
    scope, forward and backward, per traced step: the Mosaic calls
    ``flash_attn_ms_per_step`` finds by name and what runs round them
    inside the attention call."""
    s = of(run)
    if not s or not run.get("trace_steps"):
        return None
    seconds = scope_seconds(s["device_s_by_scope"], "flash_attention")
    return 1e3 * seconds / run["trace_steps"] if seconds else None


def train_step_idle_ms(run: dict) -> Optional[float]:
    """Device idle time under the ``train::step`` spans per traced
    step: what ``TrainStep.__call__`` itself leaves the chip waiting
    for, without the runner's batch and loss fetch."""
    s = of(run)
    if not s or not run.get("trace_steps") \
            or TRAIN_STEP not in s["other_spans"]:
        return None
    return 1e3 * s["idle_s_by_other_span"].get(TRAIN_STEP, 0.0) \
        / run["trace_steps"]
