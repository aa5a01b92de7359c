"""The least time the chip could take for the expert products of one decode
step (``moe_cost.py``: each touched expert's three projections read once,
``experts_touched`` of the steps' ``engine::decode_call`` spans, against 2
* 3 * hidden * inter operations an assignment; the larger of bytes over
the HBM peak and operations over the bf16 peak) over the time under
``moe/experts`` in the same steps."""
from benchmarks import decode_scopes

LAYER = 'ops (ops/moe.py)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'
MOVES = 'serve_tokens_per_s'


def read(run):
    return decode_scopes.moe_experts_roofline(run)
