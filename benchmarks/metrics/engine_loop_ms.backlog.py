"""The engine loop's whole period: window length over the decode
iterations counted in it (``batch_occupancy.steps``, a snapshot at each
end). Host work and prefills included."""
from benchmarks import common, readers  # noqa: F401

LAYER = 'engine (serving/generation/engine.py)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'program_counter'
MOVES = 'serve_tokens_per_s'


def read(run):
    steps = readers.decode_steps(run)
    return 1e3 * run["seconds"] / steps if steps else None
