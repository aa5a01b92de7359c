"""Inside the engine's phases (PR 37): the runner's and the decoder's
spans, the interpreter's full collections, and the counters beside them.

The program writes, nested inside its ``engine::`` phases and under
prefixes ``program_spans.SPAN_PREFIXES`` leaves out (so no reader of
``program_spans.py`` sees them):

- ``runner::enqueue`` round ``ProgramRunner.enqueue`` (the entry point's
  call and the pools' swap), and inside it ``decoder::launch`` round
  the executable's call in ``CachedDecoder._dispatch``: the runtime's
  side of the enqueue;
- ``runner::harvest`` round the ``jax.device_get`` of
  ``ProgramRunner.harvest``: the loop waiting for the chip;
- ``python::gc`` round each collection of the oldest generation, on
  the collecting thread's line;
- ``metrics_snapshot()["engine"]["dispatch"][kind]``: ``enqueued``,
  ``enqueue_s``, ``launch_s``, ``harvested``, ``harvest_s`` of the
  target's programs, cumulative, from the same clock readings as the
  spans; and ``["loop_cpu_s"]``, the loop thread's CPU time by phase
  beside ``loop_s``.

A span says nothing of its kind of program: a decode step's is one
whose start an ``engine::decode_call`` span holds
(``program_spans.held_by``). The trace is read here a second time, its
host lines and the ``XLA Ops`` line of the plane
``program_spans.summarize`` took (whose count of decode programs is
the denominator here too), with ``program_spans``' wire-format
functions, once a run; the summary goes to
``<scratch>/<cell>/dispatch_spans.json`` and to stderr.

Every reader returns None where the program wrote nothing to read: the
parent of PR 37 has neither the spans nor the counters.
"""
from __future__ import annotations

import json
import os
import sys
from typing import List, Optional, Sequence

from . import common, xplane
from . import program_spans as ps

ENQUEUE = "runner::enqueue"
LAUNCH = "decoder::launch"
HARVEST = "runner::harvest"
GC = "python::gc"
INNER = (ENQUEUE, LAUNCH, HARVEST)
LONGEST_GAPS = 5


def say(msg: str):
    print("dispatch_spans: " + msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------- load
def _keep(name: str) -> bool:
    return name.startswith(ps.ENGINE) or name in INNER or name == GC \
        or name == xplane.WINDOW_ANNOTATION


def load(trace_dir: str, plane_name: str) -> dict:
    """``{"spans": [Span], "window": (lo, hi) | None, "ops": [(start,
    end)]}`` of the newest trace under ``trace_dir``: the ``engine::``
    spans and those above, by thread line (no arguments), and the
    operations of the device plane ``plane_name``, containers left
    out."""
    with open(xplane.find_xplane(trace_dir), "rb") as f:
        space = memoryview(f.read())
    spans: List[ps.Span] = []
    window, ops = None, []
    for num, view in ps.fields(space):
        if num != 1:
            continue
        plane = ps.parse_plane(view)
        if plane["name"].startswith("/host:"):
            for line in plane["lines"]:
                key = f'{line["name"]}#{line["id"]}'
                for name, start, dur, _ in ps.events_of(plane, line, _keep):
                    if name == xplane.WINDOW_ANNOTATION:
                        window = (start, start + dur)
                    else:
                        spans.append((name, start, dur, key, {}))
        elif plane["name"] == plane_name:
            for line in plane["lines"]:
                if line["name"] == xplane.OPS_LINE:
                    ops = [(s, s + d) for name, s, d, _ in
                           ps.events_of(plane, line)
                           if xplane.opcode(name)
                           not in xplane.CONTAINER_OPCODES]
    return {"spans": spans, "window": window, "ops": ops}


# ---------------------------------------------------------- arithmetic
def _idle_under(gaps, spans: Sequence[ps.Span]) -> float:
    """Seconds of ``gaps`` under any of ``spans`` (of one name)."""
    return sum(ps.phase_overlap(gaps, spans).values()) / 1e9


def _total(spans: Sequence[ps.Span]) -> dict:
    return {"count": len(spans),
            "seconds": sum(s[2] for s in spans) / 1e9}


def summarize(trace: dict) -> dict:
    """Idle time in the traced window under the decode steps' runner
    and decoder spans (those on the loop's line whose start an
    ``engine::decode_call`` holds) and under ``python::gc``, their
    counts and lengths, and the window's longest gaps with the spans
    over them."""
    window = trace["window"]
    if window is None:
        if not trace["ops"]:
            raise ValueError("the trace holds no operation")
        window = (min(s for s, _ in trace["ops"]),
                  max(e for _, e in trace["ops"]))
    lo, hi = window
    merged = xplane.interval_union(
        (s, e) for s, e in trace["ops"] if e > lo and s < hi)
    gaps = xplane.idle_gaps(merged, lo, hi)
    spans = [s for s in trace["spans"] if s[1] + s[2] > lo and s[1] < hi]
    engine = ps.engine_line(spans)
    loop = engine[0][3] if engine else None
    calls = [s for s in engine if s[0] == ps.DECODE_CALL]
    decode = {name: [span for span, _ in ps.held_by(
        [s for s in spans if s[0] == name and s[3] == loop], calls)]
        for name in INNER}
    collections = [s for s in spans if s[0] == GC]
    longest = sorted(gaps, key=lambda g: g[1] - g[0],
                     reverse=True)[:LONGEST_GAPS]
    over = engine + [s for s in spans if s[0] in INNER and s[3] == loop]
    return {
        "window_s": (hi - lo) / 1e9,
        "idle_s": sum(e - s for s, e in gaps) / 1e9,
        "decode_calls": len(calls),
        "decode": {name: _total(v) for name, v in decode.items()},
        "decode_idle_s": {name: _idle_under(gaps, v)
                          for name, v in decode.items()},
        "gc": dict(_total(collections),
                   longest_ms=max((s[2] for s in collections),
                                  default=0.0) / 1e6,
                   idle_s=_idle_under(gaps, collections)),
        "longest_gaps": [
            {"at_ms": (s - lo) / 1e6, "ms": (e - s) / 1e6,
             "under_ms": {k: v / 1e6 for k, v in ps.phase_overlap(
                 [(s, e)], over + collections).items()}}
            for s, e in longest],
    }


# ------------------------------------------------ the engine's counters
def _window(run: dict, key: str):
    eng = ps.engine_window(run)
    return None if eng is None else eng.get(key)


def decode_ms(run: dict, seconds: str, count: str) -> Optional[float]:
    """Milliseconds of ``engine.dispatch.decode[seconds]`` a
    ``[count]`` in the window. None without the counter."""
    decode = (_window(run, "dispatch") or {}).get("decode")
    if not decode or decode[count] <= 0:
        return None
    return 1e3 * decode[seconds] / decode[count]


def host_cpu_pct(run: dict) -> Optional[float]:
    """The loop thread's CPU time over its wall time in the host
    phases (``program_spans.HOST_PHASES``), in the window."""
    cpu, wall = _window(run, "loop_cpu_s"), _window(run, "loop_s")
    if cpu is None or wall is None:
        return None
    wall_s = sum(wall[p] for p in ps.HOST_PHASES)
    return 100.0 * sum(cpu[p] for p in ps.HOST_PHASES) / wall_s \
        if wall_s > 0 else None


def counters(run: dict) -> Optional[dict]:
    """The window's ``engine.dispatch``, ``loop_s`` and ``loop_cpu_s``,
    and the share of the ``decode_call`` phase the decode steps' two
    halves account for."""
    eng = ps.engine_window(run)
    if eng is None or "dispatch" not in eng:
        return None
    out = {k: eng.get(k) for k in ("dispatch", "loop_s", "loop_cpu_s")}
    decode, call = eng["dispatch"].get("decode"), eng["loop_s"]["decode_call"]
    if decode and call > 0:
        out["decode_halves_share_of_call"] = \
            (decode["enqueue_s"] + decode["harvest_s"]) / call
    return out


# ------------------------------------------------------------- per run
def of(run: dict) -> Optional[dict]:
    """This run's summary, read once a run. None where no trace was
    taken or ``program_spans`` found no device plane in it."""
    if "_dispatch_spans" in run:
        return run["_dispatch_spans"]
    summary, spans = None, ps.of(run)
    if spans is not None:
        try:
            summary = summarize(load(run["trace_dir"], spans["plane"]))
        except (OSError, ValueError) as e:
            say(f"no summary of {run['trace_dir']}: {e}")
    if summary is not None:
        # the denominator of idle_host_ms_per_step, so that the two
        # per-step idle times compare
        summary["decode_programs"] = spans["decode_programs"]
        summary["window_counters"] = counters(run)
        if not summary["decode"][ENQUEUE]["count"]:
            say("no runner::enqueue span inside an engine::decode_call: "
                "the program writes none (a tree before PR 37)")
        path = os.path.join(common.scratch_dir(run["cell"]["name"]),
                            "dispatch_spans.json")
        with open(path, "w") as f:
            json.dump(summary, f, indent=1)
        say(json.dumps(summary))
    run["_dispatch_spans"] = summary
    return summary


def idle_enqueue_ms_per_step(run: dict) -> Optional[float]:
    s = of(run)
    if not s or not s["decode_programs"] \
            or not s["decode"][ENQUEUE]["count"]:
        return None
    return 1e3 * s["decode_idle_s"][ENQUEUE] / s["decode_programs"]
