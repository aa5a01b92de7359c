"""Device bytes of the recurrent-state pools (the ``state`` kind of
``metrics_snapshot()["engine"]["kv"]``: every state-space layer's convolution
tails and SSM states, counted from the arrays) over their slots, the trash
slot among them, in MiB: what one more lane costs in memory, and half of what
its decode step moves. It reads the state's precision: 20.29 with the float32
state the configuration states, 10.29 were it kept in bfloat16."""
from benchmarks import common  # noqa: F401

LAYER = 'cache (serving/generation/kv_cache.py)'
UNIT = 'MiB'
BETTER = 'lower'
SOURCE = 'program_counter'
MOVES = 'serve_tokens_per_s'


def read(run):
    kv = run["snap1"].get("engine", {}).get("kv") or {}
    held = kv.get("pool_bytes", {}).get("state")
    slots = kv.get("capacity", {}).get("state")
    if not held or not slots:
        return None
    return held / (slots + 1) / 2.0 ** 20
