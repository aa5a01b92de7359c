"""The loop thread's CPU time over its wall time in its host phases
(``admit``, ``decode_feeds``, ``sample_emit``, ``bookkeeping``: the
phases of ``engine_host_ms``), from ``metrics_snapshot()["engine"]``
``loop_cpu_s`` and ``loop_s`` between the runner's two snapshots, in
percent. Under 100 by the share of that work during which the thread
did not run: the GIL held elsewhere, or no free core. None where the
program keeps no CPU clock."""
from benchmarks import dispatch_spans

LAYER = 'engine (serving/generation/engine.py)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'program_counter'
MOVES = 'serve_tokens_per_s'


def read(run):
    return dispatch_spans.host_cpu_pct(run)
