"""The engine thread's host work of one loop iteration: the sum of
``metrics_snapshot()["engine"]["loop_s"]`` over the phases ``admit``,
``decode_feeds``, ``sample_emit`` and ``bookkeeping`` (each an
``engine::<phase>`` span), over the decode iterations
(``batch_occupancy.steps``), between the runner's two snapshots. Neither the decode call, nor prefills, nor waiting for a
request."""
from benchmarks import program_spans

LAYER = 'engine (serving/generation/engine.py)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'program_span'
MOVES = 'token_gap_p99_ms'


def read(run):
    return program_spans.host_ms_per_iteration(run)
