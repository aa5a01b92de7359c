"""Tokens through ``TrainStep.__call__`` in the window, every step ended
by fetching its loss: all steps of the window over all its time."""
from benchmarks import common, readers  # noqa: F401

LAYER = None
UNIT = 'tokens/s'
BETTER = 'higher'
SOURCE = 'host_clock'
MOVES = None


def read(run):
    if run["kind"] != "train-steps":
        return None
    return len(run["step_s"]) * run["tokens_per_step"] / run["seconds"]
