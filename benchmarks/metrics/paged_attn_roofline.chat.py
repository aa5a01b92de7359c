"""The least time the chip could take for the paged attention of one
decode step (``paged_cost.py``: the live lanes' cached context read once
and one token written a lane, over the HBM peak; memory-bound) over the
time its scope took (``paged_attn_ms_per_step``). Work and time are of
the same steps: each decode program executed in the traced window has the
``context_tokens`` and ``active`` lanes of its ``engine::decode_call``
span."""
from benchmarks import program_spans

LAYER = 'ops (ops/paged_attention.py)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'
MOVES = 'token_gap_p99_ms'


def read(run):
    return program_spans.paged_attn_roofline(run)
