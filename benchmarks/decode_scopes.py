"""Device time of a decode step by the scopes a model with expert
layers names (PR 29), beside the work its ``engine::decode_call`` span
says the step had: the source of ``moe_ms_per_step.*``,
``moe_experts_roofline.*``, ``paged_attn_roofline.longctx`` and
``decode_step_mfu_pct.*``.

``program_spans.SCOPES`` is a constant of two attention scopes and its
roofline counts the GPT block, so this reader has a matcher and cost
functions of its own (``moe_cost.py``, ``window_paged_cost.py``) and
uses ``program_spans`` for what is general: ``load`` (the trace, parsed
once a run here and kept on ``run`` under a key of its own),
``engine_line``, ``held_by``, ``inside``.

One thing the matcher knows beyond scope names: the grouped matmuls of
``jax.lax.ragged_dot`` reach a TPU profile as ``ragged-dot*`` custom
calls whose ``op_name`` XLA's own pass wrote over, so they carry no
scope; the expert layer is the only caller, and they are counted under
``moe/experts``.

Every reader returns None where the program wrote nothing to read (a
tree before PR 29 has neither the scope nor the span's arguments).
"""
from __future__ import annotations

import re
from typing import Dict, Optional

from . import common, program_spans, xplane

KEY = "_decode_scopes"
_MOE = re.compile(r"(?:^|[/(])moe(?=[/)]|$)")
_EXPERTS = re.compile(r"(?:^|[/(])moe/experts(?=[/)]|$)")
_PAGED = re.compile(r"(?:^|[/(])paged_attention(?=[/)]|$)")
RAGGED = "ragged-dot"
SPAN_ARGS = ("active", "context_tokens", "window_context_tokens",
             "experts_touched")


def scopes_of(event_name: str, op_name: str) -> tuple:
    """Which of ``moe``, ``moe/experts``, ``paged_attention`` an
    operation's device time counts under."""
    if program_spans.instruction_name(event_name).startswith(RAGGED) \
            or op_name.startswith(RAGGED):
        return ("moe", "moe/experts")
    found = []
    if _MOE.search(op_name):
        found.append("moe")
        if _EXPERTS.search(op_name):
            found.append("moe/experts")
    if _PAGED.search(op_name):
        found.append("paged_attention")
    return tuple(found)


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
                program_spans.say(f"decode_scopes: no summary: {e}")
        if summary is not None:
            program_spans.say("decode_scopes: " + repr(summary))
        run[KEY] = summary
    return run[KEY]


def scope_ms_per_step(run: dict, scope: str) -> Optional[float]:
    s = of(run)
    if not s or not s["device_s_by_scope"].get(scope):
        return None
    return 1e3 * s["device_s_by_scope"][scope] / s["steps"]


def _elem_bytes(run: dict) -> float:
    """Bytes of an element of the weights and the pool that follows
    them."""
    return {"float32": 4.0, "bfloat16": 2.0}[
        run["config"]["serve"]["weights_dtype"]]


def attention_cost(run: dict) -> Optional[dict]:
    """What the mean traced decode step's paged attention needs."""
    from . import window_paged_cost
    s = of(run)
    if not s:
        return None
    cfg = run["model_cfg"]
    full, window = window_paged_cost.layers_by_kind(cfg)
    return window_paged_cost.paged_decode_step_cost(
        context_tokens=s["context_tokens"] / s["steps"],
        window_context_tokens=s["window_context_tokens"] / s["steps"],
        lanes=s["active"] / s["steps"], full_layers=full,
        window_layers=window, heads=cfg.num_heads,
        kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
        elem_bytes=_elem_bytes(run))


def _share(run: dict, cost: dict, seconds: float) -> float:
    from . import flash_cost
    least = flash_cost.roofline(cost, common.chip_peaks(
        run["device"]["kind"]))
    return 100.0 * least["min_seconds"] / seconds


def paged_attn_roofline(run: dict) -> Optional[float]:
    ms = scope_ms_per_step(run, "paged_attention")
    cost = attention_cost(run)
    return None if not ms or cost is None \
        else _share(run, cost, ms / 1e3)


def moe_experts_roofline(run: dict) -> Optional[float]:
    from . import moe_cost
    ms = scope_ms_per_step(run, "moe/experts")
    s = of(run)
    if not ms or not s:
        return None
    cost = moe_cost.experts_step_cost(
        run["model_cfg"], experts_touched=s["experts_touched"] / s["steps"],
        lanes=s["active"] / s["steps"], elem_bytes=_elem_bytes(run))
    return _share(run, cost, ms / 1e3)


def decode_step_mfu_pct(run: dict) -> Optional[float]:
    """The whole decode program's share of its roofline: what the mean
    traced step needs over the mean device time of those programs."""
    from . import moe_cost
    s = of(run)
    cost = attention_cost(run)
    if not s or cost is None or not s["device_s"]:
        return None
    step = moe_cost.decode_step_cost(
        run["model_cfg"], experts_touched=s["experts_touched"] / s["steps"],
        lanes=s["active"] / s["steps"], elem_bytes=_elem_bytes(run),
        attention=cost)
    return _share(run, step, s["device_s"] / s["steps"])
