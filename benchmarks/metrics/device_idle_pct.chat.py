"""1 - (union of the device operations' intervals) / (traced window),
from the device plane's ``XLA Ops`` line."""
from benchmarks import common, readers  # noqa: F401

LAYER = 'device (TPU v5e)'
UNIT = '%'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'token_gap_p99_ms'


def read(run):
    return readers.device_idle_pct(run)
