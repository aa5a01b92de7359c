"""Summed duration of the ``XLA Ops`` under ``retention/state_update`` (each
live lane's slot of every layer read, the state advanced and the token's
output read off it, where the pools lie) inside the decode programs that ran
whole in the traced window under an ``engine::decode_call`` span, over their
count (``scoped_steps.py``, ``retention_cost.SCOPES``)."""
from benchmarks import retention_cost, scoped_steps

LAYER = 'ops (ops/retention.py)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'serve_tokens_per_s'


def read(run):
    return scoped_steps.ms_per_step(retention_cost.summary(run),
                                    "retention/state_update")
