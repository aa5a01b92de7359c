"""The whole decode program's share of its roofline: what one step needs
(``moe_cost.decode_step_cost``: the touched experts, every other weight
once, the cache as ``window_paged_cost.py`` counts it, against its
operations; the larger of bytes over the HBM peak and operations over the
bf16 peak) over the mean device time of the decode programs that ran whole
in the traced window. The name carries ``mfu`` because it is the cell's
share of the whole step; a decode step is bound by bytes."""
from benchmarks import decode_scopes

LAYER = 'decoder (serving/generation/model_fns.py)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'
MOVES = 'serve_tokens_per_s'


def read(run):
    return decode_scopes.decode_step_mfu_pct(run)
