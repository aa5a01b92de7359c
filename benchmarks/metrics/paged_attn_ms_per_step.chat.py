"""Summed duration of the ``XLA Ops`` under the scope ``paged_attention``
inside the decode program's whole executions in the traced window (those
that start inside a recorded ``engine::decode_call`` span: the trace's
stop cuts the last one short), over their count. The decode program is
found by the name the traffic file gives (``decode_module``)."""
from benchmarks import program_spans

LAYER = 'ops (ops/paged_attention.py)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'token_gap_p99_ms'


def read(run):
    return program_spans.paged_attn_ms_per_step(run)
