"""From the profiler's ``.xplane.pb`` to numbers: the reduction every
device metric of the benchmark goes through, kept here so that no
later change to the program can move it.

A trace is read with ``jax.profiler.ProfileData`` and turned into plain
lists first (``load``); everything after that (``interval_union``,
``idle_gaps``, ``reduce``) is arithmetic on ``(name, start_ns,
duration_ns)`` tuples and is tested on hand-made lists.

What the planes of a TPU trace hold (looked at by hand, PR 23): one
plane per chip, ``/device:TPU:<n>``, whose line ``XLA Ops`` has one
event per executed HLO operation and whose line ``XLA Modules`` has
one event per executed program; ``/host:CPU`` has one line per host
thread, with the ``TraceAnnotation``s the runner wrote (names starting
``bench.``). All start times count nanoseconds from the start of the
profile, on one clock.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Iterable, List, Sequence, Tuple

Event = Tuple[str, float, float]          # name, start_ns, duration_ns

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_ANNOTATION = "bench.trace_window"
# operations that only hold other operations: their events span their
# bodies' (a scanned decoder's ``while`` covers every layer's events),
# so counting them would hide the idle time inside and count the work
# twice
CONTAINER_OPCODES = frozenset({"while", "conditional", "call"})
_OPCODE = re.compile(r"\s([a-z][a-z0-9_\-]*)\(")


def opcode(hlo_text: str) -> str:
    """The opcode of an ``XLA Ops`` event, whose name is the HLO
    instruction's text: ``%name = shape opcode(operands), attributes``.
    Shapes hold no blank followed by a word and a parenthesis, so the
    first such word is the opcode."""
    m = _OPCODE.search(hlo_text)
    return m.group(1) if m else ""


def short_name(hlo_text: str, limit: int = 96) -> str:
    """``%name opcode result-shape``, cut to ``limit`` characters: what
    the breakdown prints in place of an instruction's whole text."""
    name, _, rest = hlo_text.partition(" = ")
    if not rest:
        return hlo_text[:limit]
    m = _OPCODE.search(rest)
    shape = rest[:m.start()] if m else ""
    return f"{name} {m.group(1) if m else ''} {shape}"[:limit].strip()


def interval_union(intervals: Iterable[Tuple[float, float]]
                   ) -> List[Tuple[float, float]]:
    """Merged, sorted ``(start, end)`` intervals covering exactly what
    the input covers. Touching and overlapping intervals merge."""
    merged: List[List[float]] = []
    for start, end in sorted(i for i in intervals if i[1] > i[0]):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(s, e) for s, e in merged]


def covered(merged: Sequence[Tuple[float, float]], lo: float, hi: float
            ) -> float:
    """Length of ``merged`` (already a union) inside ``[lo, hi]``."""
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged)


def idle_gaps(merged: Sequence[Tuple[float, float]], lo: float, hi: float
              ) -> List[Tuple[float, float]]:
    """The parts of ``[lo, hi]`` that ``merged`` does not cover."""
    gaps, at = [], lo
    for s, e in merged:
        if e <= lo:
            continue
        if s >= hi:
            break
        if s > at:
            gaps.append((at, min(s, hi)))
        at = max(at, e)
    if at < hi:
        gaps.append((at, hi))
    return gaps


def label_gap(gap: Tuple[float, float], spans: Sequence[Event],
              default: str) -> str:
    """What the host was doing in ``gap``: the annotation that overlaps
    it longest, or ``default`` where none does."""
    best, best_len = default, 0.0
    for name, start, dur in spans:
        if name == WINDOW_ANNOTATION:
            continue
        overlap = min(gap[1], start + dur) - max(gap[0], start)
        if overlap > best_len:
            best, best_len = name, overlap
    return best


# ---------------------------------------------------------------- load
def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(trace_dir: str) -> dict:
    """``{"devices": {plane: {line: [Event]}}, "host_spans": [Event]}``
    of the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(find_xplane(trace_dir))
    devices, host_spans = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {}
            for line in plane.lines:
                lines.setdefault(line.name, []).extend(
                    (ev.name, float(ev.start_ns), float(ev.duration_ns))
                    for ev in line.events)
            devices[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host_spans.extend(
                    (ev.name, float(ev.start_ns), float(ev.duration_ns))
                    for ev in line.events if ev.name.startswith("bench."))
    return {"devices": devices, "host_spans": host_spans}


# -------------------------------------------------------------- reduce
def trace_window(trace: dict) -> Tuple[float, float]:
    """The traced window: the runner's ``bench.trace_window``
    annotation where the host plane has it, else first start to last
    end of the device operations."""
    for name, start, dur in trace["host_spans"]:
        if name == WINDOW_ANNOTATION:
            return start, start + dur
    events = [ev for lines in trace["devices"].values()
              for ev in lines.get(OPS_LINE, [])]
    if not events:
        raise ValueError("the trace holds no device operation")
    return (min(s for _, s, _ in events),
            max(s + d for _, s, d in events))


def reduce(trace: dict, gap_default: str = "unattributed") -> dict:
    """Busy time, idle gaps and the heaviest operations of a trace.

    ``busy_s`` is the union of the intervals in which an operation ran
    (containers left out), inside the window, averaged over the device
    planes that ran any; gaps and operation totals come from the
    busiest plane.
    """
    lo, hi = trace_window(trace)
    per_plane = {}
    for plane, lines in trace["devices"].items():
        ops = [ev for ev in lines.get(OPS_LINE, [])
               if ev[1] + ev[2] > lo and ev[1] < hi
               and opcode(ev[0]) not in CONTAINER_OPCODES]
        if ops:
            merged = interval_union((s, s + d) for _, s, d in ops)
            per_plane[plane] = (ops, merged, covered(merged, lo, hi))
    if not per_plane:
        raise ValueError("no operation ran on a device inside the "
                         "traced window")
    busy_ns = sum(v[2] for v in per_plane.values()) / len(per_plane)
    plane = max(per_plane, key=lambda p: per_plane[p][2])
    ops, merged, _ = per_plane[plane]
    totals: dict = {}
    for name, _, dur in ops:
        tot = totals.setdefault(name, [0.0, 0])
        tot[0] += dur
        tot[1] += 1
    top = sorted(totals.items(), key=lambda kv: -kv[1][0])
    gaps = sorted(idle_gaps(merged, lo, hi),
                  key=lambda g: g[0] - g[1])[:10]
    modules = [ev for ev in trace["devices"][plane].get(MODULES_LINE, [])
               if ev[1] >= lo and ev[1] + ev[2] <= hi]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / 1e9,
        "plane": plane,
        "n_planes": len(per_plane),
        "op_totals": {n: {"seconds": t / 1e9, "count": c}
                      for n, (t, c) in top},
        "device_ops": [[short_name(n), t / 1e9] for n, (t, _) in top[:10]],
        "idle_gaps": [[label_gap(g, trace["host_spans"], gap_default),
                       (g[1] - g[0]) / 1e9] for g in gaps],
        "modules": modules,
    }


def module_durations_ms(reduced: dict, contains: str) -> List[float]:
    """Durations of the executed programs whose name holds
    ``contains``, in milliseconds."""
    return [d / 1e6 for n, _, d in reduced["modules"] if contains in n]
