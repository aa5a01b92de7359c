"""How much of the routed work this chip's share of the experts got in the
window: ``engine.moe.local_assignments`` (assignments the held experts
computed) over ``engine.moe.assignments`` (every assignment of every routed
token, wherever its expert lives), prefill and decode programs alike. 16 of
128 experts held: 12.5 is even routing. Higher is more work done here, not a
better program: the routers are the seed's."""
from benchmarks import kexaone_cost

LAYER = 'ops (ops/moe.py)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'program_counter'
MOVES = 'serve_tokens_per_s'


def read(run):
    share = kexaone_cost.local_share(run)
    return None if share is None else 100.0 * share
