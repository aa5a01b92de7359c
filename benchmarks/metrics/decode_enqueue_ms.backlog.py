"""The host's time to hand the chip one decode step:
``metrics_snapshot()["engine"]["dispatch"]["decode"]`` ``enqueue_s``
over ``enqueued`` between the runner's two snapshots (each enqueue the
``runner::enqueue`` span round ``ProgramRunner.enqueue``: the entry
point's Python, the runtime's call and the pools' swap). None where the
program keeps no such counter."""
from benchmarks import dispatch_spans

LAYER = 'engine (serving/generation/engine.py)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'program_counter'
MOVES = 'serve_tokens_per_s'


def read(run):
    return dispatch_spans.decode_ms(run, "enqueue_s", "enqueued")
