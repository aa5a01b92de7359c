"""Generated tokens streamed to the clients inside the window, over the
window: a saturated server's rate."""
from benchmarks import common, readers  # noqa: F401

LAYER = None
UNIT = 'tokens/s'
BETTER = 'higher'
SOURCE = 'host_clock'
MOVES = None


def read(run):
    if run["kind"] != "closed":
        return None
    return readers.window_tokens(run) / run["seconds"]
