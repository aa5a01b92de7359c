"""Time from when a request was *due* to its first streamed token, 90th
percentile over the requests due in the window; one that got no first
token misses (infinite). Admission wait plus the request's prefill
group, which the engine schedules. A per-layer metric and not an
end-to-end one since PR 23: below this server's knee a 40 s window holds
some 45 requests, and no statistic of 45 first tokens repeats to better
than 10% (PERF.md section 6)."""
from benchmarks import common, readers  # noqa: F401

LAYER = 'engine (serving/generation/engine.py)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'host_clock'
MOVES = 'token_gap_p99_ms'


def read(run):
    if run["kind"] != "open":
        return None
    return common.percentile(readers.ttft_ms(run), 90)
