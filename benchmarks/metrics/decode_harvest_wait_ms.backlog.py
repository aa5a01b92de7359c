"""How long the loop blocks on the chip a decode step: ``engine.dispatch
["decode"]`` ``harvest_s`` over ``harvested`` between the runner's two
snapshots (each the ``runner::harvest`` span round the ``device_get``
of ``ProgramRunner.harvest``). Near 0 where the host binds, near the
program less the host's work where the chip does. None where the
program keeps no such counter."""
from benchmarks import dispatch_spans

LAYER = 'engine (serving/generation/engine.py)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'program_counter'
MOVES = 'serve_tokens_per_s'


def read(run):
    return dispatch_spans.decode_ms(run, "harvest_s", "harvested")
