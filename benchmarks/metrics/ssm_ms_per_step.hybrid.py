"""Summed duration of the ``XLA Ops`` of the state-space layers (scope
``ssm``: in_proj, conv, state_update, gate_norm, out_proj) inside the decode
programs that ran whole in the traced window under an ``engine::decode_call``
span, over their count (``hybrid_scopes.py``)."""
from benchmarks import hybrid_scopes

LAYER = 'ops (ops/ssm.py)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'serve_tokens_per_s'


def read(run):
    return hybrid_scopes.scope_ms_per_step(run, "ssm")
