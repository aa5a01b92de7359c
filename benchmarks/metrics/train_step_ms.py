"""Host clock around one ``TrainStep.__call__`` and its loss fetch,
median over the window's steps (the batch's making is outside it)."""
from benchmarks import common, readers  # noqa: F401

LAYER = 'step (jit/train_step.py)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'host_clock'
MOVES = 'train_tokens_per_s'


def read(run):
    if run["kind"] != "train-steps":
        return None
    return 1e3 * common.median(run["step_s"])
