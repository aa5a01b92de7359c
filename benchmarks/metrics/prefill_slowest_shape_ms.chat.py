"""The mean decoder-call time of the padded prefill shape whose mean is
largest, over the window's dispatches: ``engine.prefill.call_s_by_shape``
over ``by_shape``, between the runner's two snapshots. One prefill runs
between two decode steps of every live stream, so the slowest program
sets the stall."""
from benchmarks import program_spans

LAYER = 'decoder (serving/generation/model_fns.py)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'program_span'
MOVES = 'token_gap_p99_ms'


def read(run):
    return program_spans.prefill_slowest_shape_ms(run)
