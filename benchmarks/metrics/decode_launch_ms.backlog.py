"""The runtime's share of a decode step's enqueue: ``engine.dispatch
["decode"]`` ``launch_s`` over ``enqueued`` between the runner's two
snapshots (each the ``decoder::launch`` span round the executable's
call in ``CachedDecoder._dispatch``: jax's argument handling, the numpy
feeds' copies to the device and the launch). ``decode_enqueue_ms``
minus this is the package's own Python. None where the program keeps
no such counter."""
from benchmarks import dispatch_spans

LAYER = 'decoder (serving/generation/model_fns.py)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'program_counter'
MOVES = 'serve_tokens_per_s'


def read(run):
    return dispatch_spans.decode_ms(run, "launch_s", "enqueued")
