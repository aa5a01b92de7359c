"""Summed duration of the ``XLA Ops`` of the expert layers (scope ``moe``:
route, dispatch, experts, combine, and the ``ragged-dot*`` grouped matmuls
XLA names itself) inside the decode programs that ran whole in the traced
window, over their count (``decode_scopes.py``)."""
from benchmarks import decode_scopes

LAYER = 'ops (ops/moe.py)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'serve_tokens_per_s'


def read(run):
    return decode_scopes.scope_ms_per_step(run, "moe")
