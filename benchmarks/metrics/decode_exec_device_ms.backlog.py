"""Median duration of the decode executable's events on the device
plane's ``XLA Modules`` line, in the traced part of the window. The
program is found by the name the traffic file gives
(``decode_module``)."""
from benchmarks import common, readers  # noqa: F401

LAYER = 'decoder (serving/generation/model_fns.py)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'serve_tokens_per_s'


def read(run):
    return readers.module_median_ms(run, run["traffic"]["decode_module"])
