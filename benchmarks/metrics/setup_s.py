"""Process start to the first measured step or request: import, weights
from the seed, pool, executables compiled or loaded, warm-up traffic."""
from benchmarks import common, readers  # noqa: F401

LAYER = None
UNIT = 's'
BETTER = 'lower'
SOURCE = 'host_clock'
MOVES = None


def read(run):
    return run["setup_s"]
