"""Device idle time in the traced window that lies under a ``train::step``
span (``TrainStep.__call__`` round ``_call_inner``), per traced step: what
the step's own dispatch leaves the chip waiting for, without the runner's
batch and loss fetch."""
from benchmarks import program_spans

LAYER = 'step (jit/train_step.py)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'train_tokens_per_s'


def read(run):
    return program_spans.train_step_idle_ms(run)
