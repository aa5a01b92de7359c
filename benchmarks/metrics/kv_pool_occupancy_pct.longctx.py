"""Pages in use over capacity of the fuller of the two kinds of pool (full-
context layers, window layers) at the window's end:
``metrics_snapshot()["engine"]["kv"]``."""
from benchmarks import common  # noqa: F401

LAYER = 'cache (serving/generation/kv_cache.py)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'program_counter'
MOVES = 'serve_tokens_per_s'


def read(run):
    kv = run["snap1"].get("engine", {}).get("kv")
    if not kv or not kv.get("capacity"):
        return None
    return 100.0 * max(kv["pages_in_use"][kind] / cap
                       for kind, cap in kv["capacity"].items() if cap)
