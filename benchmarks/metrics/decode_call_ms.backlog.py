"""The host's time for one ``CachedDecoder.decode`` call, ended by fetching
the ``[max_batch, vocab]`` logits: ``metrics_snapshot()['step_ms']
['decode']`` median at the window's end (the engine's sliding window of
its last 2,048 steps)."""
from benchmarks import common, readers  # noqa: F401

LAYER = 'decoder (serving/generation/model_fns.py)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'program_span'
MOVES = 'serve_tokens_per_s'


def read(run):
    return readers.snapshot_step_median_ms(run, "decode")
