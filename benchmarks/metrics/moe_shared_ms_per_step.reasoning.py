"""Summed duration of the ``XLA Ops`` under the shared expert's scope
(``moe/shared``: the expert every token goes through, three plain products
beside the routed ones) inside the decode programs that ran whole in the
traced window under an ``engine::decode_call`` span, over their count. The
programs are chosen as ``decode_scopes.summarize`` chooses them; that
reader's matcher knows three scopes and this is a fourth."""
import re

from benchmarks import decode_scopes, program_spans, xplane

LAYER = 'ops (ops/moe.py)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'serve_tokens_per_s'

_SHARED = re.compile(r"(?:^|[/(])moe/shared(?=[/)]|$)")


def read(run):
    if not decode_scopes.of(run):       # no trace, or no step to read
        return None
    trace = program_spans.load(run["trace_dir"])
    lo, hi = trace["window"]
    dev = max(trace["devices"].values(), key=lambda d: sum(
        dur for _, s, dur, _ in d["ops"] if lo <= s < hi))
    calls = [s for s in program_spans.engine_line(trace["spans"])
             if s[0] == program_spans.DECODE_CALL
             and all(a in s[4] for a in decode_scopes.SPAN_ARGS)]
    module = run["traffic"].get("decode_module", "")
    programs = [m for m, _ in program_spans.held_by(
        [m for m in dev["modules"] if module in m[0] and m[1] >= lo
         and m[1] + m[2] <= hi], calls)]
    ops = [op for op in dev["ops"] if xplane.opcode(op[0])
           not in xplane.CONTAINER_OPCODES]
    ns = sum(dur for _, _, dur, op_name in program_spans.inside(
        ops, programs) if _SHARED.search(op_name))
    return ns / 1e6 / len(programs) if ns and programs else None
