"""Summed duration of the Mosaic custom-call events on the device plane,
per traced step. In this step program the only Pallas kernels are the
flash-attention forward and backward, so this is attention and nothing
else; a later kernel of another kind in the step needs a reader that
tells them apart."""
from benchmarks import common, readers  # noqa: F401

LAYER = 'ops (ops/pallas_attention.py)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'train_tokens_per_s'


def read(run):
    if "trace" not in run:
        return None
    return 1e3 * readers.mosaic_seconds(run) / run["trace_steps"]
