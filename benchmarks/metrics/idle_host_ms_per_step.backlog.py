"""Device idle time in the traced window that overlaps an ``engine::``
span other than ``engine::wait``, over the decode programs executed in
it: the chip time the engine's host work costs a step, without the gaps
in which no request was in flight."""
from benchmarks import program_spans

LAYER = 'engine (serving/generation/engine.py)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'serve_tokens_per_s'


def read(run):
    return program_spans.idle_host_ms_per_step(run)
