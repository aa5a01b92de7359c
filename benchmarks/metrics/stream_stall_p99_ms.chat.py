"""p99 of ``paddle_decode_stream_stall_ms`` over the window: per loop
iteration that began with a live stream, the end of the last
``engine::sample_emit`` to the start of ``engine::decode_call``, i.e. what
every running stream waited beyond a decode step (admissions and prefill
groups). From the difference of the two snapshots' cumulative bucket
counts: the upper bound of the bucket that holds the rank (ratio 1.05)."""
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
    return program_spans.histogram_quantile(eng["stream_stall_ms"], 99)
