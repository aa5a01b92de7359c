"""p90 of ``paddle_decode_queue_wait_ms`` over the window: submit to slot
(the clock read once the admission loop has placed the request), per
admitted request. From the difference of the two snapshots'
cumulative bucket counts: the upper bound of the bucket that holds the
rank (ratio 1.05)."""
from benchmarks import program_spans

LAYER = 'engine (serving/generation/engine.py)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'program_span'
MOVES = 'token_gap_p99_ms'


def read(run):
    eng = program_spans.engine_window(run)
    if eng is None:
        return None
    return program_spans.histogram_quantile(eng["queue_wait_ms"], 90)
