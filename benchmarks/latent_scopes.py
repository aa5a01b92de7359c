"""Device time of a decode step by the scopes a model with latent
attention and expert layers names (the ``glm-4.7-flash`` configuration),
beside the work its ``engine::decode_call`` span says the step had: the
source of ``mla_ms_per_step.latent``, ``latent_attn_roofline.latent``,
``moe_ms_per_step.latent`` and ``decode_step_mfu_pct.latent``.

A fifth trace reader, because the other four take their scopes and the
span arguments they require as constants: ``decode_scopes.py`` wants
``window_context_tokens`` (a model with window layers), and
``hybrid_scopes.py`` wants ``state_slots_live`` (one with state
layers); a model with neither writes ``active``, ``context_tokens`` and
``experts_touched``, and names ``mla`` beside ``moe`` and
``paged_attention``. It is ``hybrid_scopes.summarize`` with ``SCOPES``
and ``SPAN_ARGS`` below; what is general comes from ``program_spans``
and ``decode_scopes`` as there. The grouped matmuls of
``jax.lax.ragged_dot`` reach a TPU profile as ``ragged-dot*`` custom
calls without a scope and are counted under ``moe/experts``.

Every reader returns None where the program wrote nothing to read (a
tree without latent attention names no ``mla``, and the readers that
need it find no step of this configuration).
"""
from __future__ import annotations

import re
from typing import Dict, Optional

from . import decode_scopes, program_spans, xplane

KEY = "_latent_scopes"
SCOPES = ("mla", "moe", "moe/experts", "paged_attention")
_MATCH = {scope: re.compile(r"(?:^|[/(])" + scope + r"(?=[/)]|$)")
          for scope in SCOPES}
SPAN_ARGS = ("active", "context_tokens", "experts_touched")


def scopes_of(event_name: str, op_name: str) -> tuple:
    """Which of ``SCOPES`` an operation's device time counts under."""
    if program_spans.instruction_name(event_name).startswith(
            decode_scopes.RAGGED) or op_name.startswith(
                decode_scopes.RAGGED):
        return ("moe", "moe/experts")
    return tuple(scope for scope in SCOPES
                 if _MATCH[scope].search(op_name))


def summarize(trace: dict, decode_module: str) -> Optional[dict]:
    """Of the decode programs that ran whole inside the traced window
    under an ``engine::decode_call`` span carrying every one of
    ``SPAN_ARGS``: their count, their device seconds in all and by
    scope, and the sums of their spans' arguments."""
    if not trace["devices"] or trace["window"] is None:
        return None
    lo, hi = trace["window"]
    plane = max(trace["devices"], key=lambda p: sum(
        d for _, s, d, _ in trace["devices"][p]["ops"] if lo <= s < hi))
    dev = trace["devices"][plane]
    calls = [s for s in program_spans.engine_line(trace["spans"])
             if s[0] == program_spans.DECODE_CALL
             and all(a in s[4] for a in SPAN_ARGS)]
    decodes = [m for m in dev["modules"] if decode_module in m[0]
               and m[1] >= lo and m[1] + m[2] <= hi]
    pairs = program_spans.held_by(decodes, calls)
    if not pairs:
        return None
    programs = [m for m, _ in pairs]
    ops = [op for op in dev["ops"] if xplane.opcode(op[0])
           not in xplane.CONTAINER_OPCODES]
    by_scope: Dict[str, float] = {}
    for name, _, dur, op_name in program_spans.inside(ops, programs):
        for scope in scopes_of(name, op_name):
            by_scope[scope] = by_scope.get(scope, 0.0) + dur
    return {
        "steps": len(programs),
        "device_s": sum(d for _, _, d in programs) / 1e9,
        "device_s_by_scope": {k: v / 1e9 for k, v in by_scope.items()},
        **{a: sum(s[4][a] for _, s in pairs) for a in SPAN_ARGS},
    }


def of(run: dict) -> Optional[dict]:
    """This run's summary, read once."""
    if KEY not in run:
        summary = None
        if run.get("trace_dir"):
            try:
                summary = summarize(
                    program_spans.load(run["trace_dir"]),
                    run["traffic"].get("decode_module", ""))
            except (OSError, ValueError) as e:
                program_spans.say(f"latent_scopes: no summary: {e}")
        if summary is not None:
            program_spans.say("latent_scopes: " + repr(summary))
        run[KEY] = summary
    return run[KEY]


def scope_ms_per_step(run: dict, scope: str) -> Optional[float]:
    s = of(run)
    if not s or not s["device_s_by_scope"].get(scope):
        return None
    return 1e3 * s["device_s_by_scope"][scope] / s["steps"]
