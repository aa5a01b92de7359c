"""The least time the chip could take for the paged attention of one decode
step of a model with two kinds of layer and grouped K/V heads
(``window_paged_cost.py``: K and V of ``context_tokens`` positions on the
full layers and of ``window_context_tokens`` on the window layers, read
once for a whole group of query heads, plus a token written a lane;
memory-bound) over the time under the scope ``paged_attention`` in the
same steps."""
from benchmarks import decode_scopes

LAYER = 'ops (ops/paged_attention.py)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'
MOVES = 'serve_tokens_per_s'


def read(run):
    return decode_scopes.paged_attn_roofline(run)
