"""How often a share of the experts handed its grouped products the
capacity's rows alone (twice what even routing gives its experts, in
tiles of 128) and not every sorted row: ``engine.moe.narrow_calls`` over
``narrow_calls + wide_calls`` of the window's programs, prefill and
decode alike, each an expert layer's call. A layer that computes every
held expert (a wide decode step of latent experts) counts neither. None
where the program keeps no such counter, or counted no call."""
from benchmarks import program_spans

LAYER = 'ops (ops/moe.py)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'program_counter'
MOVES = 'serve_tokens_per_s'


def read(run):
    moe = (program_spans.engine_window(run) or {}).get("moe") or {}
    if "narrow_calls" not in moe:
        return None
    calls = moe["narrow_calls"] + moe.get("wide_calls", 0)
    return 100.0 * moe["narrow_calls"] / calls if calls > 0 else None
