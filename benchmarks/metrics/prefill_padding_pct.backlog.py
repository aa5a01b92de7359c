"""The share of the window's prefill dispatches' positions that were
padding: 100 x (1 - ``prompt_tokens`` / ``padded_tokens``), where a
dispatch's padded tokens are its padded rows x its sequence bucket."""
from benchmarks import program_spans

LAYER = 'engine (serving/generation/engine.py)'
UNIT = '%'
BETTER = 'lower'
SOURCE = 'program_counter'
MOVES = 'serve_tokens_per_s'


def read(run):
    eng = program_spans.engine_window(run)
    if eng is None or not eng["prefill"]["padded_tokens"]:
        return None
    pre = eng["prefill"]
    return 100.0 * (1.0 - pre["prompt_tokens"] / pre["padded_tokens"])
