"""How late the generator sent against its schedule, 99th percentile over
the requests due in the window: a starved generator must not read as a
fast server."""
from benchmarks import common, readers  # noqa: F401

LAYER = 'entry (benchmarks load generator)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'host_clock'
MOVES = 'token_gap_p99_ms'


def read(run):
    if run["kind"] != "open":
        return None
    return common.percentile(readers.lateness_ms(run), 99)
