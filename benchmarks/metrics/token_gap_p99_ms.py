"""Gap between consecutive streamed tokens of one request, 99th
percentile over all gaps that ended inside the window."""
from benchmarks import common, readers  # noqa: F401

LAYER = None
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'host_clock'
MOVES = None


def read(run):
    if run["kind"] != "open":
        return None
    return common.percentile(readers.token_gaps_ms(run), 99)
