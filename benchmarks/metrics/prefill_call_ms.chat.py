"""The host's time for one prefill group's call, logits fetched:
``metrics_snapshot()['step_ms']['prefill']`` median at the window's end."""
from benchmarks import common, readers  # noqa: F401

LAYER = 'decoder (serving/generation/model_fns.py)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'program_span'
MOVES = 'token_gap_p99_ms'


def read(run):
    return readers.snapshot_step_median_ms(run, "prefill")
