"""Summed duration of the ``XLA Ops`` of the expert layers (scope ``moe``:
route, dispatch, experts, combine, the latent projections and the shared
expert, and the ``ragged-dot*`` grouped matmuls XLA names itself) inside the
decode programs that ran whole in the traced window under an
``engine::decode_call`` span, over their count (``hybrid_scopes.py``: the
steps are chosen without ``window_context_tokens``, which this model's spans
do not carry)."""
from benchmarks import hybrid_scopes

LAYER = 'ops (ops/moe.py)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'serve_tokens_per_s'


def read(run):
    return hybrid_scopes.scope_ms_per_step(run, "moe")
