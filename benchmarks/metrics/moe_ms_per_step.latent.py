"""Summed duration of the ``XLA Ops`` of the expert layers (scope ``moe``:
route with its selection bias, dispatch, experts, combine and the shared
expert, and the ``ragged-dot*`` grouped matmuls XLA names itself) inside
the decode programs that ran whole in the traced window under an
``engine::decode_call`` span, over their count (``latent_scopes.py``: the
steps are chosen without ``window_context_tokens`` or
``state_slots_live``, which this model's spans do not carry)."""
from benchmarks import latent_scopes

LAYER = 'ops (ops/moe.py)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'serve_tokens_per_s'


def read(run):
    return latent_scopes.scope_ms_per_step(run, "moe")
