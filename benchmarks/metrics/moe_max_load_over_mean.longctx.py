"""How uneven the routing was in the window: the rows of each expert layer's
fullest expert, summed over layers and programs
(``engine.moe.max_expert_load``), over the rows an expert would get were
they even (``assignments`` / experts). 1 is even; prefill and decode
programs alike."""
from benchmarks import program_spans

LAYER = 'ops (ops/moe.py)'
UNIT = 'x'
BETTER = 'lower'
SOURCE = 'program_counter'
MOVES = 'serve_tokens_per_s'


def read(run):
    eng = program_spans.engine_window(run)
    moe = (eng or {}).get("moe")
    if not moe or not moe.get("assignments"):
        return None
    return moe["max_expert_load"] * run["model_cfg"].moe_num_experts \
        / moe["assignments"]
