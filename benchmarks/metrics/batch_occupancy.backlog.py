"""Mean live lanes per decode iteration inside the window, over
``max_batch``."""
from benchmarks import common, readers  # noqa: F401

LAYER = 'engine (serving/generation/engine.py)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'program_counter'
MOVES = 'serve_tokens_per_s'


def read(run):
    occ = readers.window_occupancy(run)
    return None if occ is None else 100.0 * occ / run["max_batch"]
