"""Time from when a request was *due* to its first streamed token, median
over the requests due in the window (see ``ttft_p90_ms``)."""
from benchmarks import common, readers  # noqa: F401

LAYER = 'engine (serving/generation/engine.py)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'host_clock'
MOVES = 'token_gap_p99_ms'


def read(run):
    if run["kind"] != "open":
        return None
    return common.percentile(readers.ttft_ms(run), 50)
