"""Device time of a decode step by named scope, beside the work its
``engine::decode_call`` span says the step had, for any model: the scopes
to match, the span arguments that choose the steps and the cost function
are the caller's data (``summarize``). The source of
``retention_ms_per_step.retention``, ``retention_state_roofline.retention``
and ``decode_step_mfu_pct.retention``.

``decode_scopes.py``, ``hybrid_scopes.py`` and ``latent_scopes.py`` are
this reader three times over with their scopes and span arguments as
constants; this one takes them as arguments, so that a model adds a
scope list, not a reader. What is general comes from ``program_spans``
(``load``, ``engine_line``, ``held_by``, ``inside``).

Every reader returns None where the program wrote nothing to read (a
program without the scopes or without the span's arguments).
"""
from __future__ import annotations

import re
from typing import Callable, Dict, Optional, Sequence

from . import program_spans, xplane


def _matcher(scope: str):
    return re.compile(r"(?:^|[/(])" + re.escape(scope) + r"(?=[/)]|$)")


def summarize(trace: dict, scopes: Sequence[str], span_args: Sequence[str],
              cost: Optional[Callable[[dict], dict]] = None, *,
              decode_module: str = "decode") -> Optional[dict]:
    """Of the decode programs (``decode_module`` in the module's name)
    that ran whole inside the traced window under an
    ``engine::decode_call`` span carrying every one of ``span_args``:
    their count, their device seconds in all and under each of
    ``scopes``, the sums of their spans' arguments, and, with ``cost``,
    what ``cost`` says the mean step needed (it is handed the mean of
    each argument). None where no such program ran."""
    if not trace["devices"] or trace["window"] is None:
        return None
    lo, hi = trace["window"]
    plane = max(trace["devices"], key=lambda p: sum(
        d for _, s, d, _ in trace["devices"][p]["ops"] if lo <= s < hi))
    dev = trace["devices"][plane]
    calls = [s for s in program_spans.engine_line(trace["spans"])
             if s[0] == program_spans.DECODE_CALL
             and all(a in s[4] for a in span_args)]
    decodes = [m for m in dev["modules"] if decode_module in m[0]
               and m[1] >= lo and m[1] + m[2] <= hi]
    pairs = program_spans.held_by(decodes, calls)
    if not pairs:
        return None
    programs = [m for m, _ in pairs]
    ops = [op for op in dev["ops"] if xplane.opcode(op[0])
           not in xplane.CONTAINER_OPCODES]
    match = {scope: _matcher(scope) for scope in scopes}
    by_scope: Dict[str, float] = {}
    for _, _, dur, op_name in program_spans.inside(ops, programs):
        for scope in scopes:
            if match[scope].search(op_name):
                by_scope[scope] = by_scope.get(scope, 0.0) + dur
    out = {
        "steps": len(programs),
        "device_s": sum(d for _, _, d in programs) / 1e9,
        "device_s_by_scope": {k: v / 1e9 for k, v in by_scope.items()},
        "args": {a: sum(s[4][a] for _, s in pairs) for a in span_args},
    }
    if cost is not None:
        out["cost"] = cost({a: v / len(programs)
                            for a, v in out["args"].items()})
    return out


def of(run: dict, key: str, scopes: Sequence[str],
       span_args: Sequence[str],
       cost: Optional[Callable[[dict], dict]] = None) -> Optional[dict]:
    """This run's summary under ``key``, read once a run."""
    if key not in run:
        summary = None
        if run.get("trace_dir"):
            try:
                summary = summarize(
                    program_spans.load(run["trace_dir"]), scopes,
                    span_args, cost,
                    decode_module=run["traffic"].get("decode_module", ""))
            except (OSError, ValueError) as e:
                program_spans.say(f"{key}: no summary: {e}")
        if summary is not None:
            program_spans.say(f"{key}: " + repr(summary))
        run[key] = summary
    return run[key]


def ms_per_step(summary: Optional[dict], scope: str) -> Optional[float]:
    """Device ms under ``scope`` a step, or None where nothing ran
    under it."""
    if not summary or not summary["device_s_by_scope"].get(scope):
        return None
    return 1e3 * summary["device_s_by_scope"][scope] / summary["steps"]
