"""The share of the engine thread's time inside ``engine::prefill`` (building
a group's feeds, the prefill call, the logits' fetch):
``loop_s["prefill"]`` over the sum of all phases, between the runner's
two snapshots."""
from benchmarks import program_spans

LAYER = 'engine (serving/generation/engine.py)'
UNIT = '%'
BETTER = 'lower'
SOURCE = 'program_span'
MOVES = 'serve_tokens_per_s'


def read(run):
    eng = program_spans.engine_window(run)
    if eng is None:
        return None
    whole = sum(eng["loop_s"].values())
    return 100.0 * eng["loop_s"]["prefill"] / whole if whole else None
