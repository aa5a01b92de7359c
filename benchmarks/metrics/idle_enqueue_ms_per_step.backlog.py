"""Device idle time in the traced window under the ``runner::enqueue``
spans whose start an ``engine::decode_call`` span holds, over the
decode programs executed in the window (the denominator of
``idle_host_ms_per_step``, on the plane it reads): the chip time the
decode step's enqueue costs. None where the program writes no such
span."""
from benchmarks import dispatch_spans

LAYER = 'engine (serving/generation/engine.py)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'serve_tokens_per_s'


def read(run):
    return dispatch_spans.idle_enqueue_ms_per_step(run)
