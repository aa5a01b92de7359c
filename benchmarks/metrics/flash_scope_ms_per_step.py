"""Summed duration of every device operation under the ``flash_attention``
scope (``ops/flash_attention.py`` forward, ``ops/pallas_attention.py``
backward), per traced step: the Mosaic calls ``flash_attn_ms_per_step``
finds by name plus what runs round them inside the attention call."""
from benchmarks import program_spans

LAYER = 'ops (ops/pallas_attention.py)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'train_tokens_per_s'


def read(run):
    return program_spans.flash_scope_ms_per_step(run)
